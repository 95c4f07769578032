"""What is left of ``setup_s`` (the benchmark's host clock) after the
program's own extents before the window: the union of ``slate.import``,
the kept roots and the compile records outside every root
(``harness/setup_ledger.by_phase``). It holds the TPU runtime's start,
jax's own import, the benchmark's code and the wait for the device to
finish the operands. Prints one line ``{"step": "setup_by_phase", ...}``
whose ``seconds`` sum to ``setup_s``, and beside them
``unattributed_where``: the unattributed seconds by the gap between the
program's extents they lie in."""

from __future__ import annotations

import json

from benchmarks.harness import setup_ledger

HEADER = {"name": "setup_unattributed_s", "unit": "s", "better": "lower",
          "source": "program_span", "layer": "entry",
          "moves": "setup_s"}


def compute(run: dict):
    phases = setup_ledger.by_phase(run)
    if phases is None:
        return None
    print(json.dumps({"step": "setup_by_phase", "setup_s": run["setup_s"],
                      "seconds": phases,
                      "unattributed_where":
                          setup_ledger.unattributed_where(run),
                      **run["device"]}), flush=True)
    return phases["unattributed"]
