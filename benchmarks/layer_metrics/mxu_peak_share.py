"""Closed-form flops of the routine at (n, nrhs) over chips x the
PUBLISHED bf16 peak, over the device-busy seconds of one traced
solve. The f32 tier ``bf16_6x`` spends six MXU passes on a product, so
it cannot pass 16.7 %; a 3-pass tier 33.3 %. Never divided by peak/6."""

from __future__ import annotations

from benchmarks.harness.flops import routine_flops
from benchmarks.harness.peaks import peaks_for

HEADER = {"name": "mxu_peak_share", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "solve_s"}


def compute(run: dict):
    trace = run["trace"]
    if trace is None or run["device"]["platform"] != "tpu":
        return None         # a rehearsal's backend has no published peak
    config = run["spec"]["config"]
    flops = routine_flops(run["spec"]["traffic"]["routine"],
                          config["n"], config["nrhs"])
    peak = peaks_for(run["device"]["kind"])["bf16_flops"]
    busy_per_solve = trace.busy_s() / len(trace.solves)
    least_s = flops / (len(trace.devices) * peak)
    return 100.0 * least_s / busy_per_solve
