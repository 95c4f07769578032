"""Device-0 busy seconds of one traced ``slate.gesvd`` inside the
bidiagonal solve's XLA modules: ``stedc``'s four on the Golub-Kahan
tridiagonal of order 2n (``jit__leaves_jit``, ``jit__zrows_jit``,
``jit__secular_jit``, ``jit__merge_jit``: the accepted
``eig_merge_device_s`` names them) and ``jit__gk_halves_jit``, which
cuts U_B and V_B out of its Z. Read against ``svd_bidiag_s``: the rest
of that wall is the host's."""

from __future__ import annotations

from benchmarks.harness import busy_inside
from benchmarks.layer_metrics import eig_merge_device_s

HEADER = {"name": "svd_bidiag_device_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "svd", "moves": "solve_s"}
MODULES = eig_merge_device_s.MODULES + ("jit__gk_halves_jit",)


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return busy_inside.per_solve(trace, MODULES)
