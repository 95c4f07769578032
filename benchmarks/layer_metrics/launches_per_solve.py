"""XLA module executions on device 0 per traced solve (all the
programs the trace holds, over the number of solves): how many programs
the driver launches for one public call."""

from __future__ import annotations

HEADER = {"name": "launches_per_solve", "unit": "count", "better": "lower",
          "source": "device_trace", "layer": "drivers",
          "moves": "solve_s"}


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return len(trace.first.modules) / len(trace.solves)
