"""The program's own spans, put on the profiler trace's axis.

While a ``jax.profiler`` session is on, ``slate_tpu.obs`` keeps every
span of every public call in memory (``obs.captured_spans()``: name,
``start_ns``/``end_ns`` by ``time.perf_counter_ns``, ``id``, ``parent``,
``solve``, ``labels``). ``run.py`` deletes the trace file before the
per-layer readers run, and ``trace_reduce.load_xplane`` keeps no host
event but ``bench.solve``, so the readers take the spans from the
program, in the same process, after the window. ``ProfileData`` times
are relative to the session's start, not to any clock of the process:
the spans are placed by anchoring. The i-th root span ``slate.<routine>``
is the call inside the i-th ``bench.solve`` annotation, and
``solves[i].start - root[i].start`` is that solve's offset; the offsets
of one session have to agree to a millisecond, or the pairing is wrong
and the run fails. (The device's clock is only good to ~0.5 ms against
the host's anyway: PERF.md section 3.)

A program without ``captured_spans`` (a parent commit from before the
spans) or a run without ``--trace 1`` gives ``None`` everywhere, and the
metrics that read this are left out of the line.
"""

from __future__ import annotations

import statistics
from dataclasses import dataclass

from benchmarks.harness.trace_reduce import merge, subtract, total

OFFSET_AGREEMENT_S = 1e-3
RELAYOUT_SPANS = ("matrix.materialize", "matrix.redistribute")


@dataclass
class Solve:
    """One traced public call. ``spans`` are its spans below the root,
    instants left out; ``offset_s`` added to ``perf_counter`` seconds
    gives seconds on the trace's axis; ``window`` is its ``bench.solve``
    interval there."""
    root: dict
    spans: list
    offset_s: float
    window: tuple

    def on_axis(self, span: dict) -> tuple:
        return (span["start_ns"] * 1e-9 + self.offset_s,
                span["end_ns"] * 1e-9 + self.offset_s)


def captured(run: dict):
    """The program's captured spans: ``run["program_spans"]`` where a
    test put them, else ``slate_tpu.obs.captured_spans()``; None when
    the program has no such function or kept nothing."""
    if "program_spans" in run:
        return run["program_spans"] or None
    from slate_tpu import obs
    read = getattr(obs, "captured_spans", None)
    return (read() or None) if read else None


def pair(spans: list, windows: list, routine: str) -> list:
    """``Solve``s of the roots named ``slate.<routine>``, paired in
    order with the ``bench.solve`` ``windows`` of the trace."""
    roots = sorted((s for s in spans if s["parent"] == 0
                    and s["name"] == f"slate.{routine}"),
                   key=lambda s: s["start_ns"])
    if len(roots) != len(windows):
        raise ValueError(
            f"{len(roots)} captured slate.{routine} root span(s) for "
            f"{len(windows)} bench.solve interval(s)")
    offsets = [w[0] - r["start_ns"] * 1e-9 for r, w in zip(roots, windows)]
    if max(offsets) - min(offsets) > OFFSET_AGREEMENT_S:
        raise ValueError(f"root spans do not line up with bench.solve: "
                         f"offsets {offsets}")
    by_solve: dict = {}
    for s in spans:
        if s["parent"] != 0 and s["end_ns"] > s["start_ns"]:
            by_solve.setdefault(s["solve"], []).append(s)
    return [Solve(root=r, spans=by_solve.get(r["solve"], []),
                  offset_s=off, window=tuple(w))
            for r, off, w in zip(roots, offsets, windows)]


def solves_of(run: dict):
    """The traced solves of ``run`` with their spans, or None."""
    trace = run.get("trace")
    if trace is None:
        return None
    spans = captured(run)
    if spans is None:
        return None
    return pair(spans, trace.solves, run["spec"]["traffic"]["routine"])


def intersect(a, b):
    """The part of merged ``a`` that merged ``b`` covers."""
    return subtract(a, subtract(a, b))


def idle_by_innermost(idle, spans) -> dict:
    """Seconds of merged ``idle`` by the innermost of ``spans`` at each
    instant: ``{name: seconds}``. ``spans`` are ``{"name", "id",
    "parent", "start", "end"}`` on the axis of ``idle``; a span's own
    part is its interval minus its children's."""
    children: dict = {}
    for s in spans:
        children.setdefault(s["parent"], []).append(s)
    out: dict = {}
    for s in spans:
        own = subtract([(s["start"], s["end"])], merge(
            (c["start"], c["end"]) for c in children.get(s["id"], [])))
        out[s["name"]] = out.get(s["name"], 0.0) + total(
            intersect(idle, own))
    return out


def idle_by_span(run: dict):
    """Device-0 idle seconds inside the traced solves, by the innermost
    program span the instant lies in: ``{name: seconds}``, with the time
    under the root alone as ``(root)`` and the time of ``bench.solve``
    outside the root span (the caller waiting for the device, reading
    ``info``) as ``(outside)``. None without spans."""
    solves = solves_of(run)
    if solves is None:
        return None
    busy = run["trace"].first.busy()
    out = {"(outside)": 0.0}
    for solve in solves:
        idle = subtract([solve.window], busy)
        placed = []
        for s in [solve.root] + solve.spans:
            start, end = solve.on_axis(s)
            placed.append({"name": "(root)" if s is solve.root
                           else s["name"], "id": s["id"],
                           "parent": s["parent"], "start": start,
                           "end": end})
        for name, seconds in idle_by_innermost(idle, placed).items():
            out[name] = out.get(name, 0.0) + seconds
        out["(outside)"] += total(subtract(
            idle, [solve.on_axis(solve.root)]))
    return out


def per_solve_median(run: dict, value):
    """Median over the traced solves of ``value(solve)``, or None."""
    solves = solves_of(run)
    if solves is None:
        return None
    return statistics.median(value(s) for s in solves)


def relayout_spans(solve: Solve) -> list:
    return [s for s in solve.spans if s["name"] in RELAYOUT_SPANS]
