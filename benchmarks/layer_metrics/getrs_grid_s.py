"""Device-0 busy seconds of one traced solve inside what ``getrs``
launches on a grid: ``jit__apply_piv_jit`` (B gathered, the LAPACK
pivots replayed one dependent swap at a time, one row gather) and the
two ``jit__trsm_left_jit`` (unit-lower L, then U; with B one tile
column on q > 1 the X-moving form). ``lu_chunk_s`` + this + the trivial
programs is the device's busy time of a solve; the ``breakdown`` line
splits it by op."""

from __future__ import annotations

from benchmarks.harness import module_seconds

HEADER = {"name": "getrs_grid_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "drivers",
          "moves": "solve_s"}

MODULES = ("jit__apply_piv", "jit__trsm")


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return module_seconds.per_solve(trace, MODULES)
