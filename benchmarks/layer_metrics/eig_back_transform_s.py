"""Device-0 busy seconds of one traced ``slate.heev`` inside the two
back-transforms' XLA modules: ``jit__apply_bulge_jit`` (``unmtr_hb2st``:
the chase's reflectors, sweep by sweep, on the rows of Z) and
``jit__unmtr_he2hb_jit`` (the band reduction's block reflectors)."""

from __future__ import annotations

from benchmarks.harness import busy_inside

HEADER = {"name": "eig_back_transform_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "eigen",
          "moves": "solve_s"}
SWEEPS = ("jit__apply_bulge_jit",)
MODULES = SWEEPS + ("jit__unmtr_he2hb_jit",)


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return busy_inside.per_solve(trace, MODULES)
