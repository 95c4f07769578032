"""Device-0 busy seconds of one traced ``slate.gels`` inside
``jit__unmqr_jit``: Q^T B, the block reflectors of every panel applied
to B in turn (per panel: the gather and mask of a full-height V, two
einsums with B at its stored tile width, one with T)."""

from __future__ import annotations

from benchmarks.harness import busy_inside

HEADER = {"name": "ls_apply_q_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "least squares",
          "moves": "solve_s"}
MODULES = ("jit__unmqr",)


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return busy_inside.per_solve(trace, MODULES)
