"""Bytes of LU panels one device receives in one solve: the
``panel_gather_bytes`` label of the solve's ``getrf`` span (kt * M * nb
* itemsize: every step the whole [M, nb] panel lands on every device),
median over the traced solves. A program without that label (a commit
from before it, or a factorization that is not chunked) gives nothing
to read."""

from __future__ import annotations

import statistics

from benchmarks.harness import program_spans

HEADER = {"name": "panel_gather_bytes_per_solve", "unit": "bytes",
          "better": "lower", "source": "program_counter",
          "layer": "interconnect", "moves": "solve_s"}

SPAN, LABEL = "getrf", "panel_gather_bytes"


def compute(run: dict):
    solves = program_spans.solves_of(run)
    if solves is None:
        return None
    found = [[s["labels"][LABEL] for s in solve.spans
              if s["name"] == SPAN and LABEL in s["labels"]]
             for solve in solves]
    if not any(found):
        return None
    return statistics.median(sum(mine) for mine in found)
