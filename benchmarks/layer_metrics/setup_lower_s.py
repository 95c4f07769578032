"""Seconds of set-up spent lowering jaxprs to MLIR, Mosaic kernels
included: the sum of the program's ``compile.lower`` records that ended
before the window (``harness/setup_ledger.py``). No compile cache skips
it, and it is the figure that swung 18 -> 27-29 s on the one-chip LU
with two more span labels in ``gesv()`` (ROADMAP S2)."""

from __future__ import annotations

from benchmarks.harness import setup_ledger

HEADER = {"name": "setup_lower_s", "unit": "s", "better": "lower",
          "source": "program_span", "layer": "entry",
          "moves": "setup_s"}


def compute(run: dict):
    return setup_ledger.compile_seconds_before(run, "compile.lower")
