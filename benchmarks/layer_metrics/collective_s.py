"""Union of the collective ops' intervals (all-gather, all-reduce,
collective-permute, ...) on device 0 per traced solve."""

from __future__ import annotations

from benchmarks.harness.trace_reduce import is_collective

HEADER = {"name": "collective_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "interconnect",
          "moves": "solve_s"}


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return trace.per_solve(trace.first.where(is_collective))
