"""Host wall of one ``slate.gesvd`` inside the program's span
``gesvd.bidiag``: the SVD of the bidiagonal (the divide and conquer on
its Golub-Kahan form, with vectors), stage 3, median over the traced
calls. It is a host wall on purpose: the stage is a host loop over the
levels of the tree (sort, deflation walk, two blocking reads a level)
around device programs, and what the caller waits for is the loop."""

from __future__ import annotations

from benchmarks.harness import program_spans

HEADER = {"name": "svd_bidiag_s", "unit": "s", "better": "lower",
          "source": "program_span", "layer": "svd", "moves": "solve_s"}
SPAN = "gesvd.bidiag"


def compute(run: dict):
    def wall(solve):
        spans = [s for s in solve.spans if s["name"] == SPAN]
        if not spans:
            raise ValueError(f"a traced call opened no {SPAN} span")
        return sum(s["end_ns"] - s["start_ns"] for s in spans) * 1e-9
    return program_spans.per_solve_median(run, wall)
