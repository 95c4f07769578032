"""Device-0 busy seconds of one traced ``slate.gels`` inside the
Householder QR's XLA module: ``jit__geqrf_fast_core`` (the exact-shape
one-chip program: a panel kernel on the shrinking column, the
Gram-based blocked T, three plain matmuls) or ``jit__geqrf_jit`` (the
SPMD one-program: gathered full-height masked panels, ``larft``'s scan,
masked einsum trailing). Which of the two ran is the library's choice
(the root span's ``program`` label)."""

from __future__ import annotations

from benchmarks.harness import busy_inside

HEADER = {"name": "ls_factor_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "least squares",
          "moves": "solve_s"}
MODULES = ("jit__geqrf",)


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return busy_inside.per_solve(trace, MODULES)
