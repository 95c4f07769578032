"""The bidiagonal bulge chase's share of the chip's peak: closed-form
``tb2bd(n, band)`` = 8 n^2 band (``harness/flops_svd.py``) over the
PUBLISHED bf16 peak, over ``svd_chase_s``. Small by nature: the chase
is O(n^2 b) work on a serial dependency chain of n sweeps, none of it
on the MXU; the count is what stays fixed when the chaser changes.
``band`` is the one the program chased at (the root span's label)."""

from __future__ import annotations

from benchmarks.harness import flops_svd
from benchmarks.layer_metrics import svd_chase_s
from benchmarks.layer_metrics.eig_band_reduce_peak_share import share
from benchmarks.layer_metrics.eig_chase_peak_share import band_of

HEADER = {"name": "svd_chase_peak_share", "unit": "%",
          "better": "higher", "source": "device_trace", "layer": "svd",
          "moves": "solve_s"}


def compute(run: dict):
    band = band_of(run)
    if band is None:
        return None
    return share(run, flops_svd.tb2bd(run["spec"]["config"]["n"], band),
                 svd_chase_s.compute(run))
