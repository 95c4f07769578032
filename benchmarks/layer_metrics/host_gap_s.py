"""The part of one traced solve during which device 0 runs nothing:
the host span of the traced solves minus the union of device-0 op
intervals, over the number of solves."""

from __future__ import annotations

from benchmarks.harness.trace_reduce import total

HEADER = {"name": "host_gap_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "drivers",
          "moves": "solve_s"}


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return (trace.window_s - total(trace.first.busy())) / len(trace.solves)
