"""Device-0 busy seconds of one traced solve inside the module that
applies the pivots to B's rows: ``jit__apply_piv_jit`` (LAPACK pivots
replayed as mt*nb dependent swaps, then one gather) off the LU fast
path, ``jit__apply_order_jit`` (an elimination order, one gather) on
it."""

from __future__ import annotations

from benchmarks.harness import module_seconds

HEADER = {"name": "pivot_apply_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "drivers",
          "moves": "solve_s"}

MODULES = ("jit__apply_piv", "jit__apply_order")


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return module_seconds.per_solve(trace, MODULES)
