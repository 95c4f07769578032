#!/usr/bin/env python3
"""Cut a real ``.xplane.pb`` down to a small recorded trace for
``test_trace_reduce.py``.

    python benchmarks/tests/cut_trace.py <file.xplane.pb> <out.json> \
        [--solves 2] [--events 400]

Keeps the first ``--solves`` annotated solves and, of the device ops,
those that end before a cut time chosen so that about ``--events`` of
them remain; the solves' spans and the programs' spans are cut at the
same time. Names (as ``trace_reduce.load_xplane`` shortens them),
starts and durations are as recorded.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import trace_reduce as tr     # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("out")
    ap.add_argument("--solves", type=int, default=2)
    ap.add_argument("--events", type=int, default=400)
    args = ap.parse_args(argv)
    raw = tr.load_xplane(args.path)
    solves = sorted(ev for plane in raw["planes"]
                    if not tr.DEVICE_PLANE.match(plane["name"])
                    for line in plane["lines"] for ev in line["events"]
                    if ev[0] == tr.ANNOTATION)[:args.solves]
    solves.sort(key=lambda ev: ev[1])
    device_events = sorted(
        ev[1] + ev[2] for plane in raw["planes"]
        if tr.DEVICE_PLANE.match(plane["name"])
        for line in plane["lines"] if line["name"] == tr.OPS_LINE
        for ev in line["events"])
    per_device = sum(1 for p in raw["planes"]
                     if tr.DEVICE_PLANE.match(p["name"]))
    hi_all = solves[-1][1] + solves[-1][2]
    cut = min(device_events[min(args.events * per_device,
                                len(device_events)) - 1], hi_all)
    planes = []
    for plane in raw["planes"]:
        lines = []
        for line in plane["lines"]:
            if line["name"] == tr.OPS_LINE:
                events = [ev for ev in line["events"]
                          if ev[1] + ev[2] <= cut]
            elif line["name"] == tr.MODULES_LINE:
                events = [[ev[0], ev[1], min(ev[2], cut - ev[1]), ev[3]]
                          for ev in line["events"] if ev[1] < cut]
            else:
                events = [[ev[0], ev[1], min(ev[2], cut - ev[1]), ev[3]]
                          for ev in solves if ev[1] < cut]
                solves = []         # once, on the first host line
            if events:
                lines.append({"name": line["name"], "events": events})
        planes.append({"name": plane["name"], "lines": lines})
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump({"planes": planes}, f, separators=(",", ":"))
    kept = sum(len(ln["events"]) for p in planes for ln in p["lines"])
    print(f"kept {kept} events up to {cut:.0f} ns in {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
