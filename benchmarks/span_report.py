#!/usr/bin/env python3
"""From a kept profiler trace: where device 0 sat idle, by the program's
own host span, and where it was busy, by the program's named scopes.

    python benchmarks/run.py --workload <cell> ... --trace 1 --keep-trace DIR
    python benchmarks/span_report.py DIR/<file>.xplane.pb

The shared-clock cross-check of ``idle_attributed_share``: that metric
anchors spans kept in the program's memory to the ``bench.solve``
intervals; this reads the same spans where the profiler itself put them,
as ``slate.*`` ``TraceAnnotation``s on the ``/host:CPU`` plane (stats
``id``, ``parent``, ``solve``), on the trace's own axis. Device time by
``jax.named_scope`` (``panel``, ``panel_bcast``, ``trailing``,
``pivot_gather``, ``diag_solve``, ``update``) needs the op's name stack,
which the chip's trace keeps as the ``tf_op`` stat of the event
*metadata*; ``jax.profiler.ProfileData`` shows only per-event stats, so
it is read from the raw proto where tensorflow's ``xplane_pb2`` can be
imported (else everything reads ``(no scope)``). Scopes only show in
executables compiled from source that has them (a persistent cache
entry from before does not: use a fresh ``JAX_COMPILATION_CACHE_DIR``).
By hand, like ``dump_trace.py``; takes ``.xplane.pb`` or ``.xplane.pb.gz``;
prints one JSON object.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness.program_spans import idle_by_innermost  # noqa: E402
from benchmarks.harness.trace_reduce import (  # noqa: E402
    ANNOTATION, DEVICE_PLANE, HOST_PLANE, OPS_LINE, merge, self_times,
    subtract, total)

PREFIX = "slate."
SCOPE = re.compile(
    r"/(panel_bcast|panel|trailing|pivot_gather|diag_solve|update)/")


def name_stacks(raw: bytes) -> dict:
    """``{HLO text of an op: its name stack}`` from the ``tf_op`` stat of
    the first device plane's event metadata; empty where the proto's
    classes cannot be imported."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        return {}
    space = xplane_pb2.XSpace()
    space.ParseFromString(raw)
    for plane in space.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        key = {i for i, m in plane.stat_metadata.items()
               if m.name == "tf_op"}
        return {meta.name: stat.str_value
                for meta in plane.event_metadata.values()
                for stat in meta.stats if stat.metadata_id in key}
    return {}


def read(path: str) -> dict:
    """``{"solves": [(s, e)], "spans": [{name, start, end, id, parent,
    solve}], "ops": [(text, s, e)]}`` in seconds, device 0 only."""
    import jax
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        raw = f.read()
    stacks = name_stacks(raw)
    solves, spans, ops = [], [], []
    first_device = None
    for plane in jax.profiler.ProfileData.from_serialized_xspace(raw).planes:
        dev = DEVICE_PLANE.match(plane.name)
        if dev and first_device is None:
            first_device = plane.name
        if not (plane.name == HOST_PLANE or plane.name == first_device):
            continue
        for line in plane.lines:
            if dev and line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if dev:
                    ops.append((f"{ev.name} {stacks.get(ev.name, '')}", s, e))
                elif ev.name == ANNOTATION:
                    solves.append((s, e))
                elif ev.name.startswith(PREFIX):
                    stats = dict(ev.stats)
                    if "parent" in stats and "solve" in stats:
                        spans.append({"name": ev.name[len(PREFIX):],
                                      "start": s, "end": e, **stats})
    return {"solves": sorted(solves), "spans": spans, "ops": ops}


def idle_by_annotation(solves, spans, busy) -> dict:
    """Idle seconds of ``busy``'s complement inside ``solves``, by the
    innermost annotation at that instant (a root's own part under its
    full name, ``slate.posv``)."""
    idle = subtract(merge(solves), busy)
    named = [{**s, "name": s["name"] if s["parent"] else PREFIX + s["name"]}
             for s in spans]
    out = idle_by_innermost(idle, named)
    out["(no span)"] = total(subtract(idle, merge(
        (s["start"], s["end"]) for s in spans)))
    return out


def busy_by_scope(ops) -> dict:
    """Device self seconds by the first named scope in an op's text."""
    labelled = []
    for text, s, e in ops:
        m = SCOPE.search(text)
        labelled.append((m.group(1) if m else "(no scope)", s, e))
    return self_times(labelled)


def report(path: str) -> dict:
    raw = read(path)
    busy = merge((s, e) for _, s, e in raw["ops"])
    idle = idle_by_annotation(raw["solves"], raw["spans"], busy)
    ranked = dict(sorted(idle.items(), key=lambda kv: -kv[1]))
    whole = sum(idle.values())
    below = sum(v for k, v in idle.items()
                if k != "(no span)" and not k.startswith(PREFIX))
    return {"solves": len(raw["solves"]), "annotations": len(raw["spans"]),
            "idle_s": whole, "idle_by_annotation_s": ranked,
            "idle_below_root_share":
                100.0 * below / whole if whole > 0 else None,
            "busy_by_scope_s": dict(sorted(
                busy_by_scope(raw["ops"]).items(), key=lambda kv: -kv[1]))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    args = ap.parse_args(argv)
    print(json.dumps(report(args.path), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
