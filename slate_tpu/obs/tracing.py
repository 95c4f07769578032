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
* :func:`device_trace` degrades to a warned no-op when
  ``jax.profiler`` is unavailable on the platform.

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

import json
import time
import warnings

from . import correlation as _correlation
from . import flight as _flight
from . import metrics as _metrics
from ..runtime import sync

_enabled = False
_events: list[dict] = []
_lock = sync.Lock(name="obs.tracing.events")
_t0 = time.perf_counter()


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


_NOOP = _NoopSpan()


class _Span:
    """RAII span (reference trace::Block): buffers a complete event
    when tracing is on and feeds the metrics span aggregate when
    metrics are on."""

    __slots__ = ("name", "labels", "_start")

    def __init__(self, name: str, labels: dict):
        self.name = name
        self.labels = labels
        self._start = 0.0

    def __enter__(self):
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        dur = end - self._start
        rid = _correlation.current()
        if _enabled:
            ev = {"name": self.name, "ph": "X",
                  "ts": (self._start - _t0) * 1e6,
                  "dur": dur * 1e6, "pid": 0,
                  "tid": sync.get_ident() % 1_000_000}
            args = dict(self.labels) if self.labels else {}
            if rid:
                args["rid"] = rid
            if args:
                ev["args"] = args
            with _lock:
                _events.append(ev)
        _metrics.record_span_stat(self.name, dur, self.labels)
        if _flight.enabled():
            _flight.record("span", self.name, time.time() - dur, dur,
                           self.labels or None, rid)
        return False


def span(name: str, **labels):
    """Span context manager. ``labels`` become Chrome ``args`` and the
    metrics aggregation key; give ``routine=``/dims (``n=``, ``m=``,
    ``k=``, ``nb=``…) to get achieved-GFLOP/s in ``obs.dump()``."""
    if not (_enabled or _metrics.enabled() or _flight.enabled()):
        return _NOOP
    return _Span(name, labels)


def record_span(name: str, seconds: float, **labels) -> None:
    """Log an externally-timed region (duration measured by the
    caller — e.g. the bench's median-of-iters with round-trip
    subtraction) as a span ending now."""
    if not (_enabled or _metrics.enabled() or _flight.enabled()):
        return
    rid = _correlation.current()
    if _enabled:
        now = time.perf_counter()
        ev = {"name": name, "ph": "X",
              "ts": (now - seconds - _t0) * 1e6,
              "dur": seconds * 1e6, "pid": 0,
              "tid": sync.get_ident() % 1_000_000}
        args = dict(labels) if labels else {}
        if rid:
            args["rid"] = rid
        if args:
            ev["args"] = args
        with _lock:
            _events.append(ev)
    _metrics.record_span_stat(name, seconds, labels)
    if _flight.enabled():
        _flight.record("span", name, time.time() - seconds, seconds,
                       labels or None, rid)


def instant(name: str, **labels) -> None:
    """Instant event in the timeline (Trace::comment analog) —
    demotions, injected faults, timeouts.  Always lands in the flight
    ring (when the recorder is on), even with tracing unarmed."""
    fl = _flight.enabled()
    if not (_enabled or fl):
        return
    rid = _correlation.current()
    if fl:
        _flight.record("instant", name, time.time(),
                       labels=labels or None, rid=rid)
    if not _enabled:
        return
    ev = {"name": name, "ph": "i", "s": "g",
          "ts": (time.perf_counter() - _t0) * 1e6,
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


def device_trace(logdir: str):
    """Wrap a region in a ``jax.profiler`` session (device timeline —
    the analog of the reference's per-GPU trace rows). A warned no-op
    when the profiler is unavailable on the platform."""
    return _DeviceTrace(logdir)


class _DeviceTrace:
    __slots__ = ("logdir", "_active")

    def __init__(self, logdir: str):
        self.logdir = logdir
        self._active = False

    def __enter__(self):
        try:
            import jax
            prof = getattr(jax, "profiler", None)
            if prof is None:
                raise AttributeError("jax.profiler unavailable")
            prof.start_trace(self.logdir)
            self._active = True
        except Exception as e:  # noqa: BLE001 — degrade, don't crash
            warnings.warn(
                f"obs.device_trace: jax.profiler unavailable on this "
                f"platform ({type(e).__name__}: {e}); device timeline "
                "disabled for this region", RuntimeWarning,
                stacklevel=2)
        return self

    def __exit__(self, *exc):
        if self._active:
            import jax
            jax.profiler.stop_trace()
            self._active = False
        return False


def finish(path: str = "trace.json") -> str | None:
    """Write buffered events as Chrome trace JSON and START A FRESH
    SESSION: the buffer is cleared and the session clock reset, so a
    second ``on() … finish()`` cycle gets timestamps from t=0 (the
    old stub kept the first session's ``_t0``, skewing every later
    session)."""
    global _t0
    with _lock:
        if not _events:
            _t0 = time.perf_counter()
            return None
        with open(path, "w") as f:
            json.dump({"traceEvents": _events}, f)
        _events.clear()
        _t0 = time.perf_counter()
    return path


def reset() -> None:
    """Drop buffered events and restart the session clock (tests)."""
    global _t0
    with _lock:
        _events.clear()
        _t0 = time.perf_counter()
