"""``module_seconds.per_solve`` for a trace with hundreds of thousands
of device ops: the same number (device-0 busy seconds a traced solve
inside the XLA modules whose names start with one of ``prefixes``), by
one walk over the two sorted interval lists where
``program_spans.intersect`` subtracts twice (44 s a reader on this
cell's 69,417 busy intervals, 0.1 s here: PERF.md section 6, PR 41).
"""

from __future__ import annotations

from benchmarks.harness.trace_reduce import merge


def overlap(a: list, b: list) -> float:
    """Seconds covered by both of two merged, sorted interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def per_solve(trace, prefixes: tuple):
    """Seconds a traced solve in which an op runs on device 0 inside a
    module whose name starts with one of ``prefixes``; None when the
    trace holds no such module."""
    inside = merge((s, e) for name, s, e in trace.first.modules
                   if name.startswith(prefixes))
    if not inside:
        return None
    return overlap(trace.first.busy(), inside) / len(trace.solves)
