"""``unmtr_hb2st``'s share of its roofline, which is memory: the
blocked form's least traffic ``unmtr_hb2st_bytes(n, band)`` = 8 n^3 /
band bytes in f32 (``harness/flops_eig.py``: every block of ``band``
sweeps at one chase step reads and writes the 2 * band rows it touches
once) over the PUBLISHED HBM bandwidth of one chip, over the device-0
busy seconds inside ``jit__apply_bulge_jit``. ``band`` is the one the
program chased at (the root span's label). Its 2 n^3 flops at six
passes take a third of that time at the bf16 peak, so memory is the
ceiling while the blocks are read once."""

from __future__ import annotations

from benchmarks.harness import busy_inside, flops_eig
from benchmarks.harness.peaks import peaks_for
from benchmarks.layer_metrics.eig_back_transform_s import SWEEPS
from benchmarks.layer_metrics.eig_chase_peak_share import band_of

HEADER = {"name": "eig_back_hbm_share", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "eigen",
          "moves": "solve_s"}


def compute(run: dict):
    trace = run["trace"]
    if trace is None or run["device"]["platform"] != "tpu":
        return None
    band = band_of(run)
    seconds = busy_inside.per_solve(trace, SWEEPS)
    if band is None or not seconds:
        return None
    config = run["spec"]["config"]
    itemsize = {"float32": 4}[config["dtype"]]
    least_s = flops_eig.unmtr_hb2st_bytes(config["n"], band, itemsize) \
        / peaks_for(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
