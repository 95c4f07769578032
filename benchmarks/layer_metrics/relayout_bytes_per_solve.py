"""Bytes of matrix storage one solve re-lays out: the sum of the
``bytes`` labels of its ``matrix.materialize`` and
``matrix.redistribute`` spans, median over the traced solves."""

from __future__ import annotations

from benchmarks.harness import program_spans

HEADER = {"name": "relayout_bytes_per_solve", "unit": "bytes",
          "better": "lower", "source": "program_counter",
          "layer": "layout", "moves": "peak_hbm_gib"}


def compute(run: dict):
    return program_spans.per_solve_median(run, lambda solve: sum(
        s["labels"].get("bytes", 0)
        for s in program_spans.relayout_spans(solve)))
