"""Blocking device-to-host reads the program makes inside one solve:
its spans labelled ``sync=1`` (``int(info)``, the pivot order brought
to the host), median over the traced solves. The caller's own
``block_until_ready`` and ``int(info)`` are not the program's."""

from __future__ import annotations

from benchmarks.harness import program_spans

HEADER = {"name": "host_syncs_per_solve", "unit": "count",
          "better": "lower", "source": "program_counter",
          "layer": "drivers", "moves": "solve_s"}


def compute(run: dict):
    return program_spans.per_solve_median(run, lambda solve: sum(
        1 for s in solve.spans if s["labels"].get("sync") == 1))
