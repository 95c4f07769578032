"""1 - (union of op intervals) / (span of the traced solves), mean
over the cell"s devices."""

from __future__ import annotations

HEADER = {"name": "device_idle_share", "unit": "%", "better": "lower",
          "source": "device_trace", "layer": "device",
          "moves": "solve_s"}


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s)
