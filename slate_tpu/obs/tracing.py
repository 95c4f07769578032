"""Span tracing: Chrome/Perfetto trace JSON + span aggregates.

Absorbs and extends ``utils/trace.py`` (reference
src/auxiliary/Trace.cc ``trace::Block`` RAII spans): spans are
context managers buffering host-side complete events ("ph": "X"),
instants are "ph": "i" markers (demotions, fault injections,
timeouts), and :func:`finish` writes Chrome trace JSON loadable in
ui.perfetto.dev or chrome://tracing.

Extensions over the old stub:

* spans carry labels (the Chrome ``args`` dict) — routine, dims,
  phase — which also key the metrics span aggregates
  (:func:`slate_tpu.obs.metrics.record_span_stat`), so the same span
  feeds both the timeline and the per-phase GFLOP/s table;
* :func:`record_span` logs a region timed externally (the bench's
  median-of-iters timing) with an explicit duration;
* :func:`finish` RESETS the session clock — a second trace session
  starts at t=0 instead of inheriting the first session's offset
  (the old stub's ``_t0`` bug);
* every span knows its parent and its solve, and a solve that runs
  inside a ``jax.profiler`` session puts its tree on the profiler's
  host plane and into :func:`captured_spans` (docs/observability.md
  "Under ``jax.profiler``"). No switch: capture follows the profiler;
* the cold path is kept with no session at all: every trace, lowering
  and backend compile is a record under the span that paid for it, and
  a process's first root spans and every root that compiled are kept
  at their end (:func:`compile_ledger`).

slateflight additions: every span exit / instant also lands in the
always-on flight-recorder ring (:mod:`slate_tpu.obs.flight`) so a
crash bundle has the recent timeline even when no trace was armed,
and events inside a :class:`slate_tpu.obs.correlation.bind` extent
are stamped with the request's ``rid`` (Chrome ``args`` + ring only —
never the metrics aggregation key).

Overhead contract: with tracing, metrics AND the flight recorder off
(``SLATE_TPU_FLIGHT=0``), :func:`span` returns a shared no-op context
manager — no allocation, no lock, a single combined boolean test.
"""

from __future__ import annotations

import collections
import contextvars
import itertools
import json
import time

import jax

from . import correlation as _correlation
from . import flight as _flight
from . import metrics as _metrics
from ..runtime import sync

_enabled = False
_events: list[dict] = []
_lock = sync.Lock(name="obs.tracing.events")
# ONE clock: every record is ``time.perf_counter_ns``; the Chrome
# buffer shows it relative to the session start ``_t0_ns`` and the
# flight ring as wall time through one anchor sampled at import
_t0_ns = time.perf_counter_ns()
_WALL0 = time.time() - time.perf_counter_ns() * 1e-9

# the innermost open span of this thread / task (a fresh thread starts
# with none, so every thread grows its own tree)
_CUR: contextvars.ContextVar = contextvars.ContextVar(
    "slate_tpu_span", default=None)
_ids = itertools.count(1)
_solves = itertools.count(1)
_profiling = getattr(jax.profiler.TraceAnnotation, "is_enabled",
                     lambda: False)
ANNOTATION_PREFIX = "slate."    # names on the profiler's host plane

# spans kept while a ``jax.profiler`` session is on: one list per
# finished root span ("solve"), the oldest solve dropped whole
CAPTURE_CAP = 65_536
_captured: collections.deque = collections.deque()
_captured_n = 0

# the cold path, kept with or without a session (``compile_ledger``):
# one record a trace, lowering or backend compile (``compile_record``,
# fed by the ``jax.monitoring`` listeners of ``obs/__init__.py``), the
# process's first ``COLD_ROOTS`` root spans and every later root that a
# record landed in. Records and roots are each bounded by
# ``CAPTURE_CAP``; the per-program totals are exact past it.
COLD_ROOTS = 16     # a cell's set-up is two generators and two warm-ups
_records: list[dict] = []
_roots: list[dict] = []
_cold_left = COLD_ROOTS
_by_program: dict[str, dict] = {}   # program -> {kind: [seconds, count],
#                                     "cache": {answer: count}}
_dropped = 0
_listener_ns = 0


def on() -> None:
    global _enabled
    _enabled = True


def off() -> None:
    global _enabled
    _enabled = False


def is_on() -> bool:
    return _enabled


class _NoopSpan:
    """Shared disabled-mode span: enter/exit do nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def label(self, **more):
        pass


_NOOP = _NoopSpan()


def _finish(name, start_ns, end_ns, labels, span=None) -> None:
    """One finished span into every sink that is on; ``span`` is the
    ``_Span`` of a tree (None for an externally timed region)."""
    dur = (end_ns - start_ns) * 1e-9
    rid = _correlation.current()
    if _enabled:
        ev = {"name": name, "ph": "X", "ts": (start_ns - _t0_ns) / 1e3,
              "dur": dur * 1e6, "pid": 0,
              "tid": sync.get_ident() % 1_000_000}
        args = dict(labels) if labels else {}
        if rid:
            args["rid"] = rid
        if args:
            ev["args"] = args
        with _lock:
            _events.append(ev)
    _metrics.record_span_stat(name, dur, labels)
    if _flight.enabled():
        _flight.record("span", name, _WALL0 + start_ns * 1e-9, dur,
                       labels or None, rid)
    if span is not None and span._bag is not None:
        span._bag.append(_kept(name, start_ns, end_ns, span.id,
                               span.parent, span.solve, labels))


def _kept(name, start_ns, end_ns, sid, parent, solve, labels) -> dict:
    return {"name": name, "start_ns": start_ns, "end_ns": end_ns,
            "id": sid, "parent": parent, "solve": solve,
            "labels": dict(labels)}


class _Span:
    """RAII span (reference trace::Block). Knows its parent (the
    enclosing span of the same thread) and its solve (the root's
    sequence number, or the bound correlation rid). A root that opens
    inside a ``jax.profiler`` session puts its whole tree on the
    profiler's host plane as ``TraceAnnotation``s and into
    :func:`captured_spans`."""

    __slots__ = ("name", "labels", "id", "parent", "solve", "_bag",
                 "_ann", "_up", "_start", "_compiled")

    def __init__(self, name: str, labels: dict):
        self.name = name
        self.labels = labels

    def __enter__(self):
        up = self._up = _CUR.get()
        self.id = next(_ids)
        if up is None:
            self.parent = 0
            self.solve = _correlation.current() or next(_solves)
            self._bag = [] if _profiling() else None
            self._compiled = False      # a compile record landed here
        else:
            self.parent, self.solve, self._bag = up.id, up.solve, up._bag
        self._ann = None
        if self._bag is not None:
            self._ann = jax.profiler.TraceAnnotation(
                self.name if self.name.startswith(ANNOTATION_PREFIX)
                else ANNOTATION_PREFIX + self.name,
                id=self.id, parent=self.parent, solve=self.solve)
            self._ann.__enter__()
        _CUR.set(self)
        self._start = time.perf_counter_ns()
        return self

    def label(self, **more):
        """Labels known only after the span opened (all are read at
        its end)."""
        self.labels.update(more)

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        _CUR.set(self._up)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _finish(self.name, self._start, end, self.labels, self)
        if self.parent == 0:
            if self._bag is not None:
                _keep(self._bag)
            if self._compiled or _cold_left > 0:
                _keep_root(self, end)
        return False


def _keep(bag: list) -> None:
    global _captured_n
    with _lock:
        _captured.append(bag)
        _captured_n += len(bag)
        while _captured_n > CAPTURE_CAP and len(_captured) > 1:
            _captured_n -= len(_captured.popleft())


def _keep_root(root: _Span, end_ns: int) -> None:
    global _cold_left
    kept = _kept(root.name, root._start, end_ns, root.id, 0, root.solve,
                 root.labels)
    kept["compiled"] = root._compiled
    with _lock:
        _cold_left = max(0, _cold_left - 1)
        if len(_roots) < CAPTURE_CAP:
            _roots.append(kept)


def compile_record(name: str, kind: str, start_ns: int, end_ns: int,
                   labels: dict, listener_ns: int = 0) -> None:
    """One trace, lowering or backend compile as a span record
    ``name`` (``compile.trace`` / ``.lower`` / ``.backend``) with its
    ``program`` among the ``labels``, under the innermost open span of
    this thread (parent and solve 0 outside every root). It goes to the
    ledger, marks its root as one that compiled and, inside a captured
    tree, is kept there too; never to the flight ring. ``kind`` keys
    the program's totals, which stay exact past the ledger's bound.
    ``listener_ns`` is what the listeners spent on this event before
    the call; the call adds its own time."""
    global _dropped, _listener_ns
    entered = time.perf_counter_ns()
    up = _CUR.get()
    parent = solve = 0
    bag = None
    if up is not None:
        parent, solve, bag = up.id, up.solve, up._bag
        root = up
        while root._up is not None:
            root = root._up
        root._compiled = True
    rec = _kept(name, start_ns, end_ns, next(_ids), parent, solve, labels)
    if bag is not None:
        bag.append(rec)
    with _lock:
        totals = _by_program.setdefault(labels["program"], {})
        _add(totals, kind, (end_ns - start_ns) * 1e-9)
        if "cache" in labels:
            answers = totals.setdefault("cache", {})
            answers[labels["cache"]] = answers.get(labels["cache"], 0) + 1
        if "retrieval_s" in labels:
            _add(totals, "cache_retrieval", labels["retrieval_s"])
        if len(_records) < CAPTURE_CAP:
            _records.append(rec)
        else:
            _dropped += 1
        _listener_ns += listener_ns + time.perf_counter_ns() - entered


def _add(totals: dict, kind: str, seconds: float) -> None:
    total = totals.setdefault(kind, [0.0, 0])
    total[0] += seconds
    total[1] += 1


def import_record(start_ns: int, **labels) -> None:
    """``slate_tpu/__init__.py``'s own extent, from ``start_ns`` to
    now, as the ledger's record ``slate.import``."""
    rec = _kept("slate.import", start_ns, time.perf_counter_ns(),
                next(_ids), 0, 0, labels)
    with _lock:
        _records.append(rec)


def program_totals() -> dict:
    """``{program: {kind: [seconds, count], "cache": {answer: n}}}`` of
    every compile record made, kept or dropped."""
    with _lock:
        return _copy_totals()


def _copy_totals() -> dict:
    return {program: {k: (dict(v) if k == "cache" else list(v))
                      for k, v in totals.items()}
            for program, totals in _by_program.items()}


def compile_ledger() -> dict:
    """What this process spent getting programs ready, call by call,
    with or without a profiler session: ``{"records": [...], "roots":
    [...], "by_program": {...}, "dropped": n, "listener_s": s}``.

    ``records`` are spans in :func:`captured_spans`' form, oldest
    first: ``compile.trace`` (Python → jaxpr; the outermost trace of a
    nest, the ones inside it counted in its ``inner_traces`` label),
    ``compile.lower`` (jaxpr → MLIR), ``compile.backend`` (XLA, or the
    persistent cache's load: labels ``cache`` = ``hit`` / ``miss`` /
    ``off`` and, on a hit, ``retrieval_s``), each with its ``program``
    and, as ``parent`` / ``solve``, the innermost span that was open on
    the compiling thread (0 outside every root); and one
    ``slate.import`` for the package's own import. ``roots`` are the
    process's first ``COLD_ROOTS`` root spans and every later root in
    which a record landed, with ``compiled``. ``by_program`` is
    :func:`program_totals`. Past ``CAPTURE_CAP`` records a new one only
    adds to its program's totals and to ``dropped``. ``listener_s`` is
    what keeping all this has cost: the seconds spent inside the
    ``jax.monitoring`` listeners."""
    with _lock:
        return {"records": [dict(r) for r in _records],
                "roots": [dict(r) for r in _roots],
                "by_program": _copy_totals(), "dropped": _dropped,
                "listener_s": _listener_ns * 1e-9}


def captured_spans() -> list[dict]:
    """The span trees of the solves that ran inside a ``jax.profiler``
    session, oldest first: ``{"name", "start_ns", "end_ns"
    (``perf_counter_ns``), "id", "parent" (0: a root), "solve",
    "labels"}``. Empty when no session was on."""
    with _lock:
        return [dict(s) for bag in _captured for s in bag]


def span(name: str, **labels):
    """Span context manager. ``labels`` become Chrome ``args`` and the
    metrics aggregation key; give ``routine=``/dims (``n=``, ``m=``,
    ``k=``, ``nb=``…) to get achieved-GFLOP/s in ``obs.dump()``."""
    if not (_enabled or _metrics.enabled() or _flight.enabled()
            or _profiling()):
        return _NOOP
    return _Span(name, labels)


def sync_read(name: str, read, x, **labels):
    """``read(x)``, a blocking device→host read (``int(info)``,
    ``np.asarray(order)``), in a span labelled ``sync=1`` (and
    ``labels``) and counted as ``host.sync``."""
    with span(name, sync=1, **labels):
        _metrics.inc("host.sync", site=name)
        return read(x)


def record_span(name: str, seconds: float, **labels) -> None:
    """Log an externally-timed region (duration measured by the
    caller — e.g. the bench's median-of-iters with round-trip
    subtraction) as a span ending now."""
    if not (_enabled or _metrics.enabled() or _flight.enabled()):
        return
    now = time.perf_counter_ns()
    _finish(name, now - int(seconds * 1e9), now, labels)


def instant(name: str, **labels) -> None:
    """Instant event in the timeline (Trace::comment analog) —
    demotions, injected faults, timeouts.  Always lands in the flight
    ring (when the recorder is on), even with tracing unarmed, and in
    the captured tree of the solve it happened in."""
    fl = _flight.enabled()
    up = _CUR.get()
    bag = up._bag if up is not None else None
    if not (_enabled or fl or bag is not None):
        return
    now = time.perf_counter_ns()
    rid = _correlation.current()
    if bag is not None:
        bag.append(_kept(name, now, now, next(_ids), up.id, up.solve,
                         labels))
    if fl:
        _flight.record("instant", name, _WALL0 + now * 1e-9,
                       labels=labels or None, rid=rid)
    if not _enabled:
        return
    ev = {"name": name, "ph": "i", "s": "g", "ts": (now - _t0_ns) / 1e3,
          "pid": 0, "tid": sync.get_ident() % 1_000_000}
    args = dict(labels) if labels else {}
    if rid:
        args["rid"] = rid
    if args:
        ev["args"] = args
    with _lock:
        _events.append(ev)


def comment(msg: str) -> None:
    """Back-compat alias for the old trace.comment API."""
    instant(msg)


def block(name: str, **labels):
    """Back-compat alias for the old trace.block API."""
    return span(name, **labels)


def events() -> list[dict]:
    """Copy of the buffered events (tests / obs.dump)."""
    with _lock:
        return [dict(e) for e in _events]


def finish(path: str = "trace.json") -> str | None:
    """Write buffered events as Chrome trace JSON and START A FRESH
    SESSION: the buffer is cleared and the session clock reset, so a
    second ``on() … finish()`` cycle gets timestamps from t=0 (the
    old stub kept the first session's ``_t0``, skewing every later
    session)."""
    global _t0_ns
    with _lock:
        if not _events:
            _t0_ns = time.perf_counter_ns()
            return None
        with open(path, "w") as f:
            json.dump({"traceEvents": _events}, f)
        _events.clear()
        _t0_ns = time.perf_counter_ns()
    return path


def reset() -> None:
    """Drop buffered events, captured spans and the compile ledger and
    restart the session clock (tests)."""
    global _t0_ns, _captured_n, _cold_left, _dropped, _listener_ns
    with _lock:
        _events.clear()
        _captured.clear()
        _captured_n = 0
        _records.clear()
        _roots.clear()
        _by_program.clear()
        _cold_left, _dropped, _listener_ns = COLD_ROOTS, 0, 0
        _t0_ns = time.perf_counter_ns()
