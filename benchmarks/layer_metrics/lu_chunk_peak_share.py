"""The chunk programs' share of their roofline: closed-form
``getrf(n)`` flops (``harness/flops.py``) over chips x the PUBLISHED
bf16 peak of one chip, over ``lu_chunk_s``. Compute bounds it, as
``lu_factor_peak_share`` argues for the one-chip LU (2n^3/3 flops
against two passes over 4n^2 bytes, both divided by the chips: 3.7 ms
against 0.66 ms at n=16384 on four). At the f32 tier ``bf16_6x`` a
product takes six MXU passes, so the share cannot pass 16.7 %; it is
never divided by peak/6. The panel every chip factors redundantly is
not counted twice: the flops are the algorithm's, not the program's."""

from __future__ import annotations

from benchmarks.harness import flops
from benchmarks.harness.peaks import peaks_for
from benchmarks.layer_metrics import lu_chunk_s

HEADER = {"name": "lu_chunk_peak_share", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "solve_s"}


def compute(run: dict):
    if run["device"]["platform"] != "tpu":
        return None         # a rehearsal's backend has no published peak
    seconds = lu_chunk_s.compute(run)
    if not seconds:
        return None
    peak = peaks_for(run["device"]["kind"])["bf16_flops"]
    chips = len(run["trace"].devices)
    least_s = flops.getrf(run["spec"]["config"]["n"]) / (chips * peak)
    return 100.0 * least_s / seconds
