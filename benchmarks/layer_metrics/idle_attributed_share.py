"""Of device 0's idle time inside the traced solves (each ``bench.solve``
interval minus the union of device-0 op intervals), the share whose
instant lies inside a program span deeper than the root
``slate.<routine>``: the part of the stall that has a name. Prints one
line ``{"step": "idle_by_span", ...}`` with the idle seconds per
innermost span name, over all traced solves."""

from __future__ import annotations

import json

from benchmarks.harness import program_spans

HEADER = {"name": "idle_attributed_share", "unit": "%",
          "better": "higher", "source": "program_span",
          "layer": "drivers", "moves": "solve_s"}
UNNAMED = ("(root)", "(outside)")


def compute(run: dict):
    by_span = program_spans.idle_by_span(run)
    if by_span is None:
        return None
    idle = sum(by_span.values())
    ranked = sorted(by_span.items(), key=lambda kv: -kv[1])
    print(json.dumps({"step": "idle_by_span", "idle_s": idle,
                      "solves": len(run["trace"].solves),
                      "seconds": dict(ranked), **run["device"]}),
          flush=True)
    if idle <= 0.0:
        return None
    named = sum(s for name, s in by_span.items() if name not in UNNAMED)
    return 100.0 * named / idle
