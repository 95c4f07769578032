"""Host wall of one solve inside the program's ``matrix.materialize``
and ``matrix.redistribute`` spans (a nested pair counts once), median
over the traced solves: what re-laying a matrix out costs the caller's
thread."""

from __future__ import annotations

from benchmarks.harness import program_spans
from benchmarks.harness.trace_reduce import merge, total

HEADER = {"name": "relayout_host_s", "unit": "s", "better": "lower",
          "source": "program_span", "layer": "layout",
          "moves": "solve_s"}


def compute(run: dict):
    return program_spans.per_solve_median(run, lambda solve: total(merge(
        solve.on_axis(s) for s in program_spans.relayout_spans(solve))))
