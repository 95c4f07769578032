"""Device-0 busy seconds inside the executions of named XLA modules.

The trace's ``XLA Modules`` line holds one event per executed program,
named ``jit_<function>`` once ``load_xplane`` has dropped the
fingerprint (``jit__getrf_core``, ``jit__apply_piv_jit``). A program is
told from the rest by the prefix of that name, which is the jitted
function's own name in the program: a refactor that renames the
function moves the prefix with it, and the reader then finds nothing
and leaves its metric out of the line, which a traced run of a cell
that lists it is refused for.
"""

from __future__ import annotations

from benchmarks.harness.program_spans import intersect
from benchmarks.harness.trace_reduce import merge


def per_solve(trace, prefixes: tuple):
    """Seconds a traced solve in which an op runs on device 0 inside a
    module whose name starts with one of ``prefixes``; None when the
    trace holds no such module."""
    inside = merge((s, e) for name, s, e in trace.first.modules
                   if name.startswith(prefixes))
    if not inside:
        return None
    return trace.per_solve(intersect(trace.first.busy(), inside))
