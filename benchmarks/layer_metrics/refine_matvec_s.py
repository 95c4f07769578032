"""Device-0 busy seconds of one traced call inside the ``gemm``
programs (``jit__gemm*``): every residual b - A·x and every product of
A with a preconditioned Krylov vector. The right-hand side is one real
column stored as a 1024-wide tile column, and the time says how many
of those columns ``gemm`` multiplies."""

from __future__ import annotations

from benchmarks.harness import module_seconds

HEADER = {"name": "refine_matvec_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "drivers",
          "moves": "solve_s"}

MODULES = ("jit__gemm",)


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return module_seconds.per_solve(trace, MODULES)
