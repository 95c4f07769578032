"""Device-0 busy seconds of one traced call inside the programs that
apply the low-precision factors: ``jit__apply_*`` (the factor's row
order: ``jit__apply_order_jit`` is one gather, ``jit__apply_piv_jit``
the serial replay of LAPACK pivots) and ``jit__trsm*`` (the two
triangular solves). One application for the initial x, one an Arnoldi
step, one an update: ``refine_steps_per_solve`` of them a call."""

from __future__ import annotations

from benchmarks.harness import module_seconds

HEADER = {"name": "refine_solve_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "drivers",
          "moves": "solve_s"}

MODULES = ("jit__apply_", "jit__trsm")


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return module_seconds.per_solve(trace, MODULES)
