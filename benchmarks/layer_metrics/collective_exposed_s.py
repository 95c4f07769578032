"""The part of ``collective_s`` during which no other op runs on
device 0 (ops that only span other ops, such as a ``while``, are not
"another op"), per traced solve."""

from __future__ import annotations

from benchmarks.harness.trace_reduce import is_collective, subtract

HEADER = {"name": "collective_exposed_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "interconnect",
          "moves": "solve_s"}


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    dev0 = trace.first
    others = dev0.leaf_busy(keep=lambda stats: not is_collective(stats))
    return trace.per_solve(subtract(dev0.where(is_collective), others))
