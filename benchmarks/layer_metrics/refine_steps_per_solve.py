"""Applications of the low-precision factors in one call, from the
labels the root span ``slate.<call>`` carries at its end: ``inner``
(Arnoldi steps, or corrections of plain refinement) + ``outer`` (one
update a cycle) + 1 (the initial x). Median over the traced calls. A
program that opens no such root gives nothing to read."""

from __future__ import annotations

from benchmarks.harness import refine_spans

HEADER = {"name": "refine_steps_per_solve", "unit": "count",
          "better": "lower", "source": "program_counter",
          "layer": "drivers", "moves": "solve_s"}


def steps_of(solve):
    labels = solve.root["labels"]
    return labels["inner"] + labels["outer"] + 1


def compute(run: dict):
    return refine_spans.per_solve_median(run, steps_of)
