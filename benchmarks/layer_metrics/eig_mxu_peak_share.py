"""Closed-form flops of one ``slate.heev`` with all n vectors through
the two stages (``flops_eig.heev_vectors(n, band)``: he2hb + hb2st +
stedc + the two back-transforms) over the PUBLISHED bf16 peak, over the
device-busy seconds of one traced call. Stands where ``mxu_peak_share``
stands in the solver cells (its reader knows no ``heev``). The count is
LAPACK's, whatever deflation saves and whatever the sweeps move."""

from __future__ import annotations

from benchmarks.harness import flops_eig
from benchmarks.layer_metrics.eig_band_reduce_peak_share import share
from benchmarks.layer_metrics.eig_chase_peak_share import band_of

HEADER = {"name": "eig_mxu_peak_share", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "eigen",
          "moves": "solve_s"}


def compute(run: dict):
    band = band_of(run)
    if band is None:
        return None
    trace = run["trace"]
    return share(run, flops_eig.heev_vectors(run["spec"]["config"]["n"],
                                             band),
                 trace.busy_s() / len(trace.solves))
