"""slateprobe — unified tracing, metrics, and flop accounting.

One layer answering "where did the time go" across the whole stack
(the visibility SLATE gets from ``trace::Block`` + its testers'
GFLOP/s columns, and the BLASX/TPU-QR papers call load-bearing for
tile-runtime performance work):

* **spans** (:func:`span`, :func:`record_span`) — RAII regions with
  labels, buffered into Chrome/Perfetto trace JSON and aggregated
  into per-(name, labels) totals;
* **metrics** (:func:`count`, :func:`gauge`, :func:`observe`) —
  labeled counters/gauges/histograms (ladder demotions, injected
  faults, collective counts, jit compiles);
* **flop accounting** (:mod:`.flops`) — closed-form operation counts
  per routine, so any span labeled ``routine=``/dims reports achieved
  GFLOP/s (and %-of-peak where the platform peak is known) in
  :func:`dump`;
* **timing** (:mod:`.timing`) — the round-trip-subtracting timing
  discipline the tuning sweep uses (single source of truth; slatelint
  SL008 bans raw ``perf_counter`` timing elsewhere).

Activation (no code changes needed):

* ``SLATE_TPU_TRACE=path.json`` — span tracing on; the Chrome trace
  is written to ``path.json`` at process exit (or call
  :func:`finish_trace` earlier);
* ``SLATE_TPU_METRICS=1`` — metrics + span aggregation on;
  ``SLATE_TPU_METRICS=path.json`` additionally writes the
  :func:`dump` snapshot there at process exit;
* ``SLATE_TPU_METRICS_PORT=<port>`` — slateflight live exporter: a
  background HTTP thread serving ``/metrics`` (OpenMetrics),
  ``/healthz``, and ``/vars`` (implies metrics on; see
  :mod:`.export`, or call :func:`serve_metrics` directly);
* ``SLATE_TPU_FLIGHT_DIR=<dir>`` — forensic flight bundles are
  auto-dumped there on failure (the in-memory ring is always on;
  ``SLATE_TPU_FLIGHT=0`` kills it — see :mod:`.flight`).

``python -m slate_tpu.obs report <file>`` prints the per-phase
summary table for either export (``flight <bundle>`` renders a
forensic bundle).  docs/observability.md is the user-facing guide.
"""

from __future__ import annotations

import atexit
import json
import os
import time as _time

from ..runtime import sync

from . import (correlation, costmodel, export, flight, flops, hbm, metrics,
               overlap, roofline, timeline, timing, tracing)
from .correlation import new_id as new_request_id
from .export import serve_metrics, stop_metrics
from .flops import flop_count, peak_gflops
from .metrics import counter_value
from .report import enrich_span
from .timing import (roundtrip_latency, timed_regen_median,
                     timed_scalar_median)
from .tracing import (captured_spans, compile_ledger, instant,
                      record_span, span, sync_read)

# verb-named metric entry points
count = metrics.inc
gauge = metrics.set_gauge
observe = metrics.observe
count_total = metrics.counter_total

ENV_TRACE = "SLATE_TPU_TRACE"
ENV_METRICS = "SLATE_TPU_METRICS"
ENV_METRICS_PORT = "SLATE_TPU_METRICS_PORT"


def trace_on() -> None:
    tracing.on()


def trace_off() -> None:
    tracing.off()


def tracing_enabled() -> bool:
    return tracing.is_on()


def metrics_on() -> None:
    metrics.enable()
    install_jax_hooks()


def metrics_off() -> None:
    metrics.disable()


def metrics_enabled() -> bool:
    return metrics.enabled()


def enabled() -> bool:
    """Any observability active (spans are recorded)?"""
    return tracing.is_on() or metrics.enabled()


def finish_trace(path: str = "trace.json") -> str | None:
    """Write the buffered Chrome trace JSON and reset the session."""
    return tracing.finish(path)


def reset() -> None:
    """Clear every buffer and aggregate (tests, repeated sessions)."""
    tracing.reset()
    metrics.reset()
    costmodel.reset()
    timeline.reset()
    flight.reset()
    correlation.reset()


# ---------------------------------------------------------------------------
# snapshot
# ---------------------------------------------------------------------------

def dump() -> dict:
    """Machine-readable snapshot: span aggregates (flop-enriched —
    achieved GFLOP/s per routine-labeled span), counters, gauges,
    histograms.  JSON-ready; ``/vars`` serves it live."""
    snap = metrics.snapshot()
    snap["spans"] = [enrich_span(s) for s in snap["spans"]]
    costs = costmodel.snapshot()
    if costs:
        snap["costmodel"] = costs
    snap["trace_enabled"] = tracing.is_on()
    snap["metrics_enabled"] = metrics.enabled()
    return snap


def dump_json(path: str) -> str:
    with open(path, "w") as f:
        json.dump(dump(), f, indent=1)
    return path


# ---------------------------------------------------------------------------
# collective accounting (internal/comm.py calls this at trace time)
# ---------------------------------------------------------------------------

def comm_event(kind: str, axis, x, axis_size=None, tiled=None) -> None:
    """Count one collective issued by ``internal/comm.py``.  These
    fire at TRACE time (inside shard_map tracing), so the counters
    report collectives per compiled program — the schedule the device
    executes — not per runtime step.

    When the caller knows the mesh-axis size, the per-link wire bytes
    are modeled too (``comm.link_bytes``), ring-algorithm figures per
    link: all-reduce (psum/bcast) ``2(p-1)/p`` of the payload,
    reduce-scatter (psum_scatter) ``(p-1)/p``, all-gather ``(p-1)``
    local shards, a permute exactly the payload.

    ``tiled`` disambiguates the all-gather frame of reference: with
    ``tiled=False`` (new leading axis of size p) ``x`` is the local
    input shard, so the wire carries ``(p-1)·|x|`` per link; with
    ``tiled=True`` (concatenation along an existing axis) callers
    reason — and pass ``x`` — in the gathered *global* extent, so the
    local shard is ``|x|/p`` and the wire carries ``(p-1)/p·|x|``.
    Before this distinction the tiled case was overcounted by p×."""
    if not metrics.enabled():
        return
    metrics.inc("comm.collectives", kind=kind, axis=str(axis))
    try:
        nbytes = int(x.size) * int(x.dtype.itemsize)
    except (AttributeError, TypeError):
        nbytes = 0
    if not nbytes:
        return
    metrics.inc("comm.bytes", value=float(nbytes), kind=kind)
    p = None
    try:
        p = int(axis_size) if axis_size is not None else None
    except (TypeError, ValueError):
        p = None
    if p and p > 1:
        if kind.startswith("psum_scatter") or kind.startswith("rscatter"):
            link = (p - 1) / p * nbytes    # ring reduce-scatter
        elif kind.startswith("psum") or kind.startswith("bcast"):
            link = 2.0 * (p - 1) / p * nbytes
        elif kind.startswith("allgather"):
            shard = nbytes / p if tiled else float(nbytes)
            link = (p - 1) * shard
        else:                              # rotate/permute: one hop
            link = float(nbytes)
        metrics.inc("comm.link_bytes", value=link, kind=kind,
                    axis=str(axis), link=_axis_link(axis))


def _axis_link(axis) -> str:
    """Which interconnect class a mesh axis crosses.  The grid layer's
    axis-role registry is authoritative (runtime.distributed.dcn_grid
    registers the host-crossing axis of a hybrid mesh as DCN — a ring
    hop on mesh axis p then bills DCN bytes/bandwidth while axis q
    stays ICI); axes it doesn't know keep the name heuristic (anything
    called "dcn"/"host"/"x" is cross-host)."""
    a = str(axis).lower()
    try:
        from ..grid import _AXIS_ROLES
        if a in _AXIS_ROLES:
            return _AXIS_ROLES[a]
    except Exception:  # noqa: BLE001 — accounting must never crash
        pass
    if "dcn" in a or "host" in a or a == "x":
        return "dcn"
    return "ici"


class link_window:
    """Per-link occupancy meter: ``with obs.link_window("potrf"): ...``
    snapshots ``comm.link_bytes`` on entry, and on exit records
    ``comm.link_occupancy{kind,axis,link}`` gauges = bytes moved in
    the window ÷ window ÷ nominal link bandwidth
    (:func:`roofline.link_bw_gbs`, SLATE_TPU_ICI_GBS/_DCN_GBS
    overridable).  An occupancy near 1.0 says the link — not the MXU —
    owns the window.

    Caveat: trace-time byte counters against a runtime window means a
    window that triggers compilation attributes the whole program's
    schedule to itself — meter *warmed* windows."""

    __slots__ = ("where", "_t0", "_base")

    def __init__(self, where: str = ""):
        self.where = where
        self._t0 = 0.0
        self._base: dict = {}

    def __enter__(self):
        if metrics.enabled():
            self._base = metrics.counters_named("comm.link_bytes")
            self._t0 = _time.perf_counter()
        return self

    def __exit__(self, *exc):
        if not metrics.enabled() or not self._t0:
            return False
        dt = _time.perf_counter() - self._t0
        if dt <= 0:
            return False
        for lk, v in metrics.counters_named("comm.link_bytes").items():
            delta = v - self._base.get(lk, 0.0)
            if delta <= 0:
                continue
            labels = dict(lk)
            # counters minted after the axis-role registry carry their
            # link class as a label; older/foreign rows fall back to
            # the axis-name mapping
            link = labels.get("link") or _axis_link(labels.get("axis", ""))
            bw = roofline.link_bw_gbs(link)
            if not bw:
                continue
            metrics.set_gauge(
                "comm.link_occupancy", delta / dt / (bw * 1e9),
                kind=str(labels.get("kind", "?")),
                axis=str(labels.get("axis", "?")), link=link,
                **({"where": self.where} if self.where else {}))
        return False


# ---------------------------------------------------------------------------
# jit retrace / compile accounting (jax.monitoring listeners)
# ---------------------------------------------------------------------------

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_COMPILE_EVENTS = {     # jax's event -> (kind, record name)
    _TRACE: ("trace", "compile.trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("lower", "compile.lower"),
    _BACKEND: ("backend_compile", "compile.backend"),
}
# what the persistent cache says inside an open backend compile: it
# served the executable or it stored the fresh one; ``off`` when it did
# neither (none placed, or the program under the cache's thresholds)
_CACHE_ANSWERS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_jax_hooks_installed = False


class _Compiling:
    """What one thread has open of jax's compile events. Plain counters,
    so the tens of thousands of nested traces of one large program cost
    two integer updates each and allocate nothing."""

    __slots__ = ("open", "traces", "inner_traces", "cache", "retrieval_s",
                 "ns")

    def __init__(self):
        self.open = 0               # events of any kind not closed yet
        self.traces = 0             # of them, traces
        self.inner_traces = 0       # traces closed inside the outermost
        self.cache = "off"          # the open backend compile's answer
        self.retrieval_s = None
        self.ns = 0                 # spent in the listeners, not yet booked


_open: dict[int, _Compiling] = {}   # thread -> what it has open


def compile_seconds() -> dict:
    """What this process has spent getting programs ready, always
    counted: ``{"seconds": {kind: s}, "counts": {kind: n}, "top":
    [[program, trace + lower seconds], ...]}`` for the kinds ``trace``
    (Python → jaxpr; only the outermost trace of a nest, so the sum is
    wall time), ``lower`` (jaxpr → MLIR, Mosaic kernels included),
    ``backend_compile`` (XLA, or the persistent cache's load) and
    ``cache_retrieval``: the sums of :func:`compile_ledger`'s
    per-program totals."""
    seconds: dict = {}
    counts: dict = {}
    by_fun: dict = {}
    for program, totals in tracing.program_totals().items():
        for kind, total in totals.items():
            if kind == "cache":
                continue
            seconds[kind] = seconds.get(kind, 0.0) + total[0]
            counts[kind] = counts.get(kind, 0) + total[1]
            if kind in ("trace", "lower"):
                by_fun[program] = by_fun.get(program, 0.0) + total[0]
    top = sorted(by_fun.items(), key=lambda kv: -kv[1])[:8]
    return {"seconds": seconds, "counts": counts,
            "top": [list(kv) for kv in top]}


def install_jax_hooks() -> bool:
    """Register the ``jax.monitoring`` listeners (at import): every
    trace, lowering and backend compile becomes a record of the compile
    ledger (:func:`compile_ledger`, :func:`compile_seconds`), always;
    the ``jax.events{event=…}`` counters (+ duration histograms) only
    while metrics are on. Idempotent (jax only offers a global
    clear)."""
    global _jax_hooks_installed
    if _jax_hooks_installed:
        return True
    try:
        from jax import monitoring as _mon
        _mon.register_event_listener(_on_event)
        _mon.register_scalar_listener(_on_scalar)
        _mon.register_event_duration_secs_listener(_on_duration)
        _mon.register_event_time_span_listener(_on_compile_span)
        _jax_hooks_installed = True
        return True
    except Exception:  # noqa: BLE001 — observability must never crash
        return False


def _on_event(event, **kw):
    answer = _CACHE_ANSWERS.get(event)
    if answer is not None:
        _in_open_backend("cache", answer)
    if metrics.enabled():
        metrics.inc("jax.events", event=event)


def _on_scalar(event, value, **kw):
    # jax announces a trace, lowering or backend compile as it opens:
    # what happens on this thread until the event's time span arrives
    # happens inside it
    if event in _COMPILE_EVENTS:
        t0 = _time.perf_counter_ns()
        tid = sync.get_ident()
        mine = _open.get(tid)
        if mine is None:
            mine = _open[tid] = _Compiling()
        mine.open += 1
        if event == _TRACE:
            mine.traces += 1
        elif event == _BACKEND:
            mine.cache, mine.retrieval_s = "off", None
        mine.ns += _time.perf_counter_ns() - t0


def _on_duration(event, duration, **kw):
    if event == _RETRIEVAL:
        _in_open_backend("retrieval_s", duration)
    if metrics.enabled():
        metrics.inc("jax.events", event=event)
        metrics.observe("jax.event_duration_s", duration, event=event)


def _in_open_backend(field: str, value) -> None:
    """``compile_or_get_cached`` runs inside the backend compile's
    extent and names no program: its events belong to the one this
    thread has open."""
    mine = _open.get(sync.get_ident())
    if mine is not None:
        setattr(mine, field, value)


def _on_compile_span(event, start_time, end_time, fun_name="", **kw):
    """A trace, lowering or backend compile has ended: one record of
    the ledger, on the spans' clock through the one anchor. A trace
    inside another trace of its thread is a count on the outer one."""
    names = _COMPILE_EVENTS.get(event)
    if names is None:
        return
    t0 = _time.perf_counter_ns()
    tid = sync.get_ident()
    mine = _open.get(tid)
    if mine is None:                # opened before the listeners were
        mine = _Compiling()
    mine.open -= 1
    if mine.open <= 0:
        _open.pop(tid, None)
    if event == _TRACE:
        mine.traces -= 1
        if mine.traces > 0:
            mine.inner_traces += 1
            mine.ns += _time.perf_counter_ns() - t0
            return
    labels = {"program": _program(str(fun_name))}
    if event == _TRACE:
        labels["inner_traces"], mine.inner_traces = mine.inner_traces, 0
    elif event == _BACKEND:
        labels["cache"] = mine.cache
        if mine.retrieval_s is not None:
            labels["retrieval_s"] = mine.retrieval_s
    kind, name = names
    spent, mine.ns = mine.ns, 0
    tracing.compile_record(
        name, kind, int((start_time - tracing._WALL0) * 1e9),
        int((end_time - tracing._WALL0) * 1e9), labels,
        spent + _time.perf_counter_ns() - t0)


def _program(fun_name: str) -> str:
    """jax names a trace ``f`` and its lowering and compile
    ``jit(f)``: one program."""
    return fun_name[4:-1] if fun_name.startswith("jit(") else fun_name


def jit_event_total() -> float:
    """Total jax compile/trace events counted so far (all kinds)."""
    return metrics.counter_total("jax.events")


# ---------------------------------------------------------------------------
# env activation
# ---------------------------------------------------------------------------

def _init_from_env() -> None:
    tpath = os.environ.get(ENV_TRACE, "")
    if tpath:
        tracing.on()
        atexit.register(_finish_to, tpath)
    mval = os.environ.get(ENV_METRICS, "")
    if mval and mval not in ("0", "false", "no"):
        metrics_on()
        if mval not in ("1", "true", "yes"):
            atexit.register(_dump_to, mval)
    pval = os.environ.get(ENV_METRICS_PORT, "")
    if pval:
        try:
            export.serve_metrics(port=int(pval))
            install_jax_hooks()
        except (ValueError, OSError) as e:
            import warnings
            warnings.warn(f"obs: cannot serve metrics on port "
                          f"{pval!r}: {e}", RuntimeWarning)


def _finish_to(path: str) -> None:
    try:
        tracing.finish(path)
    except Exception:  # noqa: BLE001 — exit hooks must not raise
        pass


def _dump_to(path: str) -> None:
    try:
        dump_json(path)
    except Exception:  # noqa: BLE001 — exit hooks must not raise
        pass


install_jax_hooks()
_init_from_env()
