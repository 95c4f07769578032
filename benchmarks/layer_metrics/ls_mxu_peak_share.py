"""Closed-form flops of one ``slate.gels`` by Householder QR
(``flops_ls.gels(m, n, nrhs)``: geqrf + unmqr + trsm) over the
PUBLISHED bf16 peak, over the device-busy seconds of one traced call.
Stands where ``mxu_peak_share`` stands in the solver cells (its reader
knows no ``gels``). The count is LAPACK's, whatever the program
multiplies."""

from __future__ import annotations

from benchmarks.harness import flops_ls
from benchmarks.layer_metrics.eig_band_reduce_peak_share import share
from benchmarks.layer_metrics.ls_factor_peak_share import shape_of

HEADER = {"name": "ls_mxu_peak_share", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "least squares",
          "moves": "solve_s"}


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    m, n, nrhs, _ = shape_of(run)
    return share(run, flops_ls.gels(m, n, nrhs),
                 trace.busy_s() / len(trace.solves))
