"""Host seconds inside the program's generators: the sum of the root
spans ``slate.random_matrix`` / ``slate.random_spd`` that ended before
the first solver root. Their tracing, lowering, cache loads and
dispatch; the device fills the tiles after they return, and that wait is
the benchmark's (``setup_unattributed_s``)."""

from __future__ import annotations

from benchmarks.harness import setup_ledger

HEADER = {"name": "operands_s", "unit": "s", "better": "lower",
          "source": "program_span", "layer": "entry",
          "moves": "setup_s"}


def compute(run: dict):
    setup = setup_ledger.cut(run)
    if setup is None:
        return None
    return sum(setup_ledger.seconds(r) for r in setup.generators())
