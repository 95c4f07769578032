"""Device-0 busy seconds of one traced solve inside the LU
factorization's own XLA modules, by the names the trace prints:
``jit__getrf_core`` (the one-program LU off the fast path) or
``jit__getrf_fast_core`` (the Pallas-panel fast path). What is left of
the device's busy time is ``getrs`` (pivots and two ``trsm``) and the
trivial programs around them."""

from __future__ import annotations

from benchmarks.harness import module_seconds

HEADER = {"name": "lu_factor_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "kernels",
          "moves": "solve_s"}

MODULES = ("jit__getrf",)


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return module_seconds.per_solve(trace, MODULES)
