"""Public calls of the whole process in which a trace, lowering or
backend compile landed: the program's kept roots with ``compiled``.
Three in a closed-loop cell (two generators, the first solve); more
means the second warm-up call or the window compiled, and the
``compile_ledger`` line's ``roots`` name it."""

from __future__ import annotations

from benchmarks.harness import setup_ledger

HEADER = {"name": "calls_that_compiled", "unit": "count",
          "better": "lower", "source": "program_counter",
          "layer": "executable caches", "moves": "setup_s"}


def compute(run: dict):
    ledger = setup_ledger.read(run)
    if ledger is None:
        return None
    return sum(1 for r in ledger["roots"] if r["compiled"])
