"""``unmbr_tb2bd``'s share of its roofline, which is memory: the
blocked form's least traffic over both sides,
``unmbr_tb2bd_bytes(n, band)`` = 2 x 8 n^3 / band bytes in f32
(``harness/flops_svd.py``), over the PUBLISHED HBM bandwidth of one
chip, over the device-0 busy seconds inside ``jit__apply_bulge_jit``
(its two runs a call). ``band`` is the one the program chased at (the
root span's label)."""

from __future__ import annotations

from benchmarks.harness import busy_inside, flops_svd
from benchmarks.harness.peaks import peaks_for
from benchmarks.layer_metrics.eig_chase_peak_share import band_of
from benchmarks.layer_metrics.svd_back_transform_s import SWEEPS

HEADER = {"name": "svd_back_hbm_share", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "svd", "moves": "solve_s"}


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
    least_s = flops_svd.unmbr_tb2bd_bytes(config["n"], band, itemsize) \
        / peaks_for(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
