"""Closed-form flops of one ``slate.gesvd`` of the tall m x n operand
with both sets of vectors through the two stages
(``flops_svd.gesvd_vectors(m, n, band)``: ge2tb + tb2bd + the
bidiagonal divide and conquer + two back-transforms a side) over the
PUBLISHED bf16 peak, over the device-busy seconds of one traced call.
Stands where ``mxu_peak_share`` stands in the solver cells (its reader
knows no ``gesvd``). The count is LAPACK's, whatever deflation saves,
whatever the Golub-Kahan form multiplies and whatever the sweeps move."""

from __future__ import annotations

from benchmarks.harness import flops_svd
from benchmarks.layer_metrics.eig_band_reduce_peak_share import share
from benchmarks.layer_metrics.eig_chase_peak_share import band_of
from benchmarks.layer_metrics.svd_band_reduce_peak_share import shape_of

HEADER = {"name": "svd_mxu_peak_share", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "svd", "moves": "solve_s"}


def compute(run: dict):
    band = band_of(run)
    if band is None:
        return None
    trace = run["trace"]
    return share(run, flops_svd.gesvd_vectors(*shape_of(run), band),
                 trace.busy_s() / len(trace.solves))
