"""Seconds of set-up inside backend compiles that the persistent cache
did not serve: the sum of the program's ``compile.backend`` records with
``cache`` = ``miss`` that ended before the window
(``harness/setup_ledger.py``). 0 on a warm run; the ``compile_ledger``
line names the program when a key drew a miss."""

from __future__ import annotations

from benchmarks.harness import setup_ledger

HEADER = {"name": "cache_miss_compile_s", "unit": "s", "better": "lower",
          "source": "program_span", "layer": "executable caches",
          "moves": "setup_s"}


def compute(run: dict):
    return setup_ledger.compile_seconds_before(run, "compile.backend",
                                               cache="miss")
