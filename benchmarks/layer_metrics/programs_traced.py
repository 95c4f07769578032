"""Outermost traces (Python bodies run to make a jaxpr: jitted
programs and every eager op's first call) before the window: the
program's ``compile.trace`` records that ended by then, plus the traces
the ledger counted past its bound (``dropped``; its per-program counts
stay exact). Traces nested in another trace are that record's
``inner_traces`` and are not counted here."""

from __future__ import annotations

from benchmarks.harness import setup_ledger

HEADER = {"name": "programs_traced", "unit": "count", "better": "lower",
          "source": "program_counter", "layer": "entry",
          "moves": "setup_s"}
NAME = "compile.trace"


def compute(run: dict):
    setup = setup_ledger.cut(run)
    if setup is None:
        return None
    ledger = setup.ledger
    made = sum(totals["trace"][1] for totals in ledger["by_program"].values()
               if "trace" in totals)
    kept = sum(1 for r in ledger["records"] if r["name"] == NAME)
    return len(setup.compiles(NAME)) + (made - kept)
