"""Device-0 busy seconds of one traced ``slate.gels`` inside the
triangular solve with R: the module named ``jit__trsm_left_jit`` (one a
call: the n x n upper triangle against the top n rows of Q^T B, carried
at the width nrhs needs). ``tri_solve_s``'s number for this cell, by
``harness/busy_inside.py``'s one walk."""

from __future__ import annotations

from benchmarks.harness import busy_inside

HEADER = {"name": "ls_tri_solve_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "least squares",
          "moves": "solve_s"}
MODULES = ("jit__trsm",)


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return busy_inside.per_solve(trace, MODULES)
