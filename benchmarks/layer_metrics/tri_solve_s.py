"""Device-0 busy seconds of one traced solve inside the triangular
solves: the modules named ``jit__trsm*`` (``jit__trsm_left_jit``, two a
solve: ``potrs``'s L and Lᴴ, ``getrs``'s unit-lower L and U). What is
left of a solve once the factorization (``lu_factor_s`` on the gesv
cells) and the pivots (``pivot_apply_s``) are taken out."""

from __future__ import annotations

from benchmarks.harness import module_seconds

HEADER = {"name": "tri_solve_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "drivers",
          "moves": "solve_s"}

MODULES = ("jit__trsm",)


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return module_seconds.per_solve(trace, MODULES)
