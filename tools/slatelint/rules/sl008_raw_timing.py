"""SL008 raw-timing — wall-clock timing goes through
``slate_tpu.obs``, not hand-rolled ``perf_counter`` loops.

bench.py's timing ends every timed window on a scalar materialized
to the host and subtracts a measured dispatch round trip from every
sample.  That discipline lived as copy-pasted ``time.perf_counter()``
arithmetic in bench.py and was one fork away from drifting (a copy
that forgets the subtraction reads differently from every other
row).  ``slate_tpu.obs.timing`` is now the single
implementation — ``roundtrip_latency`` / ``timed_scalar_median`` /
``timed_regen_median`` — and spans (``obs.span``) cover the
non-subtracting "how long did this phase take" case.

Scope: any call to ``perf_counter``/``perf_counter_ns`` — dotted
(``time.perf_counter()``) or bare after ``from time import
perf_counter`` — outside the exempt implementation sites:
``slate_tpu/obs/`` (the timing layer itself), ``robust/watchdog.py``
(SIGALRM deadline bookkeeping, not measurement), and ``bench.py``
(the driver's budget/section walls).

Fix: wrap the region in ``obs.span(...)`` or time it with
``obs.timed_scalar_median`` / ``obs.timed_regen_median``; report an
externally-timed result with ``obs.record_span``.
"""

from __future__ import annotations

import ast

from ..engine import LintContext, Rule, register
from ..astutil import dotted

_CLOCKS = {"perf_counter", "perf_counter_ns"}
_EXEMPT_SUFFIXES = (("robust", "watchdog.py"),)


def _exempt(path: str) -> bool:
    parts = path.replace("\\", "/").split("/")
    if "obs" in parts and "slate_tpu" in parts:
        return True
    if parts[-1] == "bench.py":
        return True
    return any(tuple(parts[-len(s):]) == s for s in _EXEMPT_SUFFIXES)


def _bare_clock_imports(tree: ast.AST) -> set[str]:
    """Local names bound to time.perf_counter* by a from-import
    (including aliases: ``from time import perf_counter as pc``)."""
    names: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "time":
            for alias in node.names:
                if alias.name in _CLOCKS:
                    names.add(alias.asname or alias.name)
    return names


@register
class RawTiming(Rule):
    id = "SL008"
    name = "raw-timing"
    rationale = ("raw perf_counter timing outside slate_tpu/obs forks "
                 "the round-trip discipline — timed windows must "
                 "materialize a scalar and subtract the measured "
                 "round trip (obs.timing owns that logic)")

    def check(self, ctx: LintContext):
        if _exempt(ctx.path):
            return
        bare = _bare_clock_imports(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            d = dotted(node.func)
            if d is None:
                continue
            parts = d.split(".")
            is_dotted = (len(parts) >= 2 and parts[-1] in _CLOCKS
                         and parts[-2] == "time")
            is_bare = len(parts) == 1 and parts[0] in bare
            if is_dotted or is_bare:
                yield self.finding(
                    ctx, node,
                    f"raw {d}() timing outside slate_tpu/obs — use "
                    "obs.span / obs.timed_scalar_median / "
                    "obs.record_span so the materialize-and-subtract-"
                    "round-trip discipline stays single")
