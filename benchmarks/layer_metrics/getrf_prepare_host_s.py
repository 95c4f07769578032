"""Host wall of one solve inside the program's ``getrf.prepare`` span,
median over the traced solves: what ``getrf()`` does on the caller's
thread before its first launch (fault hooks, ``materialize``, the tuned
table, the ABFT monitor). A program without that span (a commit from
before it, or the LU fast path, which does not call ``getrf()``) gives
nothing to read."""

from __future__ import annotations

import statistics

from benchmarks.harness import program_spans

HEADER = {"name": "getrf_prepare_host_s", "unit": "s", "better": "lower",
          "source": "program_span", "layer": "drivers",
          "moves": "solve_s"}

SPAN = "getrf.prepare"


def compute(run: dict):
    solves = program_spans.solves_of(run)
    if solves is None:
        return None
    found = [[s for s in solve.spans if s["name"] == SPAN]
             for solve in solves]
    if not any(found):
        return None
    return statistics.median(
        sum(s["end_ns"] - s["start_ns"] for s in mine) * 1e-9
        for mine in found)
