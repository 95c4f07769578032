"""Device-0 busy seconds of one traced ``slate.heev`` inside the
tridiagonal stage's XLA modules: ``jit__leaves_jit`` (Z's block
diagonal from the leaves), ``jit__zrows_jit`` (the two rows a merge's z
is made of), ``jit__secular_jit`` (the secular solve and the
Gu-Eisenstat vector) and ``jit__merge_jit`` (G's assembly and the
product with Z). Read against ``eig_tridiag_s``: the rest of that wall
is the host's."""

from __future__ import annotations

from benchmarks.harness import busy_inside

HEADER = {"name": "eig_merge_device_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "eigen",
          "moves": "solve_s"}
MODULES = ("jit__leaves_jit", "jit__zrows_jit", "jit__secular_jit",
           "jit__merge_jit")


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return busy_inside.per_solve(trace, MODULES)
