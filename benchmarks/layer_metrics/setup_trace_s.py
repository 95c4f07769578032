"""Seconds of set-up spent tracing Python to jaxprs: the sum of the
program's ``compile.trace`` records (outermost traces only, so wall
time) that ended before the window (``harness/setup_ledger.py``: the
end of the last warm-up call). No compile cache skips it. Prints the
whole ledger as one line ``{"step": "compile_ledger", ...}``: by
program, largest first, seconds and counts by kind, the persistent
cache's answers and the call that paid; the seconds by kind after the
cut (the traced solves, the window, the check); ``listener_s`` (what
the program's listeners cost) and ``dropped``."""

from __future__ import annotations

import json

from benchmarks.harness import setup_ledger

HEADER = {"name": "setup_trace_s", "unit": "s", "better": "lower",
          "source": "program_span", "layer": "entry",
          "moves": "setup_s"}


def compute(run: dict):
    setup = setup_ledger.cut(run)
    if setup is None:
        return None
    after = {}
    for r in setup.after:
        kind = setup_ledger.KINDS.get(r["name"])
        if kind:
            after[kind] = after.get(kind, 0.0) + setup_ledger.seconds(r)
    print(json.dumps({
        "step": "compile_ledger",
        "programs": setup_ledger.by_program(setup),
        "roots": [{"name": r["name"], "compiled": r["compiled"],
                   "seconds": setup_ledger.seconds(r),
                   "before_window": r["end_ns"] <= setup.cut_ns}
                  for r in setup.ledger["roots"]],
        "seconds_after_cut": after,
        "records": len(setup.ledger["records"]),
        "dropped": setup.ledger["dropped"],
        "listener_s": setup.ledger["listener_s"], **run["device"]}),
        flush=True)
    return sum(setup_ledger.seconds(r)
               for r in setup.compiles("compile.trace"))
