"""slatetimeline — per-device timeline capture.

The host-side span layer (:mod:`.tracing`) sees one wall clock per
process: it can say a ``potrf.chunk`` took 40 ms, but not which
device was busy, which link a collective crossed, or whether the
panel broadcast of step k+1 actually hid under the trailing update of
step k — the attribution gap per-device event timelines close for
BLASX-style schedulers, and the number every multi-host overlap claim
("Large Scale Distributed Linear Algebra With TPUs") must be graded
against.

This module captures **device-resolved, step-indexed events**:

* everywhere — including the forced multi-device CPU mesh CI runs on
  (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) — the
  source is **timed host-callback barriers**:
  :func:`mark` plants a ``jax.debug.callback`` inside the SPMD step
  body whose operands are (step, device-ordinal, a scalar probe of
  the phase's input/output), so the callback cannot fire before that
  tensor is ready and the host timestamp approximates when the
  device passed that program point.  The drivers
  (``linalg/potrf.py``, ``linalg/getrf.py``, ``linalg/geqrf.py``)
  mark three phases per factorization step — ``panel_bcast``
  (collective), ``trailing`` (compute), and the ``step`` envelope —
  and ``runtime/hosttask.py`` marks its superstep DAG tasks as host
  tracks (:func:`host_phase`).

Capture is OFF by default and costs one module-global boolean test
per :func:`mark` call at trace time (the disabled mark returns its
argument untouched — the traced program is bit-identical to an
uninstrumented one).  Toggling clears the jax trace caches so
programs retrace with/without the callbacks; the slatecache executable
key carries :func:`key_token` so an instrumented program can never be
satisfied by an uninstrumented cached executable (or vice versa).

Outputs:

* :func:`finish` — one **per-process timeline file** carrying the raw
  events plus a wall-clock anchor (``anchor_unix_s`` sampled against
  the same ``perf_counter`` origin as the events), so ``python -m
  slate_tpu.obs timeline --merge`` can clock-align files from
  different processes into one multi-track Perfetto timeline;
* skew/straggler series — on finish (and on demand via
  :func:`record_metrics`) each step's per-device completion spread is
  observed as ``timeline.skew_s`` histograms and any device more than
  2σ behind its peers is counted under ``timeline.straggler`` — see
  :mod:`.overlap` for the analyzer;
* the overlap analyzer (:mod:`.overlap`) consumes :func:`snapshot`
  or a merged file and reports per-step compute-busy / collective-
  busy / overlapped fractions.

Fault semantics: an armed ``preempt`` fault
(:mod:`slate_tpu.robust.faults`) stalls ONE seed-deterministic
device's step-end barrier during capture — the timeline's view of a
preempted core resuming late — so the chaos suite can assert the
straggler detector flags injected preemptions.

Caveats (documented, not hidden): callback timestamps are assigned on
the host callback thread, so they carry scheduling jitter of ~0.1 ms
on an idle box; and on a single-process CPU "mesh" the virtual
devices share host cores, so absolute overlap fractions there
exercise the *instrument*, not the hardware claim.
"""

from __future__ import annotations

import json
import os
import time
from functools import partial

from . import metrics as _metrics
from ..runtime import sync

ENV = "SLATE_TPU_TIMELINE"

# phase-kind vocabulary (the analyzer classifies intervals by these)
KIND_COLLECTIVE = "collective"
KIND_COMPUTE = "compute"
KIND_STEP = "step"

_enabled = False
_lock = sync.Lock(name="obs.timeline.events")
_events: list[dict] = []
# wall-clock anchor: (unix seconds, perf_counter seconds) sampled
# back-to-back at session start — the merge CLI aligns per-process
# clocks through it
_anchor: tuple[float, float] = (time.time(), time.perf_counter())
# device stall bookkeeping for the preempt chaos hook: records the
# injection once per session, not once per stalled barrier
_stall_recorded = False


def on() -> None:
    """Enable capture.  Clears the jax trace caches so every program
    retraces WITH the callback barriers (a program traced while
    capture was off contains none)."""
    global _enabled, _anchor, _stall_recorded
    if _enabled:
        return
    _enabled = True
    _stall_recorded = False
    _anchor = (time.time(), time.perf_counter())
    _clear_jax_caches()


def off() -> None:
    """Disable capture (and retrace back to uninstrumented programs)."""
    global _enabled
    if not _enabled:
        return
    _enabled = False
    _clear_jax_caches()


def is_on() -> bool:
    return _enabled


def key_token() -> str:
    """Executable-cache key component: instrumented and uninstrumented
    programs are different machine code and must never share a cache
    entry (cache/jitcache.py includes this in every key)."""
    return "tl1" if _enabled else ""


def _clear_jax_caches() -> None:
    try:
        import jax
        jax.clear_caches()
    except Exception:  # noqa: BLE001 — capture toggles must never crash
        pass


def reset() -> None:
    """Drop buffered events and restart the session anchor."""
    global _anchor, _stall_recorded
    with _lock:
        _events.clear()
        _anchor = (time.time(), time.perf_counter())
        _stall_recorded = False


def events() -> list[dict]:
    """Copy of the buffered raw events."""
    with _lock:
        return [dict(e) for e in _events]


snapshot = events


# ---------------------------------------------------------------------------
# the device-side barrier
# ---------------------------------------------------------------------------

def _probe(x):
    """A scalar derived from ``x``: the callback operand that makes
    the barrier wait for ``x`` to be ready.  One element, one cast —
    noise next to the tile ops it fences."""
    import jax.numpy as jnp
    try:
        if getattr(x, "ndim", 0) == 0:
            v = x
        else:
            v = jnp.ravel(x)[0]
        if jnp.issubdtype(v.dtype, jnp.complexfloating):
            v = jnp.real(v)
        return v.astype(jnp.float32)
    except Exception:  # noqa: BLE001 — a failed probe must not kill tracing
        return jnp.zeros((), jnp.float32)


def _record_cb(phase, kind, edge, routine, ndev, step, dev, tok):
    """Host side of the barrier (runs on the runtime callback thread).
    ``step``/``dev`` arrive as numpy scalars from the device."""
    dev = int(dev)
    if edge == "e" and kind == KIND_STEP:
        _maybe_stall(dev, int(ndev))
    ev = {"t": time.perf_counter(), "dev": dev, "step": int(step),
          "phase": phase, "kind": kind, "edge": edge,
          "routine": routine}
    with _lock:
        _events.append(ev)


def _maybe_stall(dev: int, ndev: int) -> None:
    """The ``preempt`` chaos hook: when a preempt fault is armed, ONE
    seed-deterministic device's step-end barriers are stalled — the
    timeline of a preempted core resuming late.  Watchdog-section
    preemption semantics (robust/watchdog.py) are untouched; this
    path only exists inside an active capture."""
    global _stall_recorded
    try:
        from ..robust import faults as _faults
        spec = _faults.enabled("preempt", "timeline")
        if spec is None or ndev <= 0:
            return
        target = spec.seed % ndev
        if dev != target:
            return
        if not _stall_recorded:
            _stall_recorded = True
            _faults.record("preempt", "timeline", f"device {dev} stalled")
        time.sleep(PREEMPT_STALL_S)
    except Exception:  # noqa: BLE001 — chaos hook must never crash capture
        pass


# stall per step-end barrier of the preempted device; large against
# CPU-mesh step walls (~ms) so the 2σ straggler gate trips decisively
PREEMPT_STALL_S = 0.05


def mark(x, phase: str, *, step, device, kind: str, edge: str,
         routine: str = "", ndev: int = 0):
    """Plant one timed barrier in a traced SPMD body and return ``x``
    unchanged.

    ``step`` and ``device`` may be traced values (the fori_loop index,
    ``r*q + c`` mesh ordinal); ``phase``/``kind``/``edge``/``routine``
    are trace-time strings.  ``edge`` is ``"b"`` (fires when the
    phase's *input* ``x`` is ready) or ``"e"`` (fires when its
    *output* is ready).  With capture off this is an identity — the
    traced program contains no callback at all."""
    if not _enabled:
        return x
    import jax
    import jax.numpy as jnp
    jax.debug.callback(
        partial(_record_cb, phase, kind, edge, routine, ndev),
        jnp.asarray(step), jnp.asarray(device), _probe(x))
    return x


class host_phase:
    """Host-track sibling of :func:`mark` for regions the host itself
    times (the superstep DAG tasks in runtime/hosttask.py): records
    begin/end events on a ``host:<thread>`` track so DAG-task overlap
    shows up in the merged timeline next to the device tracks."""

    __slots__ = ("phase", "step", "kind", "routine", "_track")

    def __init__(self, phase: str, *, step: int, kind: str = KIND_COMPUTE,
                 routine: str = ""):
        self.phase = phase
        self.step = step
        self.kind = kind
        self.routine = routine
        self._track = None

    def _emit(self, edge: str) -> None:
        ev = {"t": time.perf_counter(), "dev": self._track,
              "step": int(self.step), "phase": self.phase,
              "kind": self.kind, "edge": edge, "routine": self.routine}
        with _lock:
            _events.append(ev)

    def __enter__(self):
        if _enabled:
            self._track = f"host:{sync.current_thread_name()}"
            self._emit("b")
        return self

    def __exit__(self, *exc):
        if self._track is not None:
            self._emit("e")
        return False


# ---------------------------------------------------------------------------
# per-process export + merge
# ---------------------------------------------------------------------------

FORMAT_KEY = "slateTimeline"
FORMAT_VERSION = 1


def export_doc(meta: dict | None = None) -> dict:
    """The per-process timeline document: raw events + the clock
    anchor the merge aligns on.  ``meta`` (optional) records capture
    conditions — e.g. ``{"pipeline_depth": 2}`` — so downstream
    consumers (merged Perfetto tracks, overlap tables) can distinguish
    captures from different schedules."""
    try:
        import jax
        proc = int(jax.process_index())
    except Exception:  # noqa: BLE001
        proc = 0
    doc = {FORMAT_KEY: FORMAT_VERSION,
           "process": proc,
           "anchor_unix_s": _anchor[0],
           "anchor_perf_s": _anchor[1],
           "events": events()}
    if meta:
        doc["meta"] = dict(meta)
    return doc


def finish(path: str | None = None,
           meta: dict | None = None) -> str | None:
    """Write the per-process timeline document, feed the skew/
    straggler series into metrics, and clear the buffer.  Returns the
    written path (None when the buffer was empty)."""
    from . import overlap as _overlap
    evs = events()
    if not evs:
        reset()
        return None
    _overlap.record_metrics(evs)
    doc = export_doc(meta)
    if path is None:
        path = "timeline.json"
    with open(path, "w") as f:
        json.dump(doc, f)
    reset()
    return path


class capture:
    """``with timeline.capture() as cap: ...`` — enable, run, disable;
    ``cap.events`` holds the raw events, ``cap.path`` the written file
    when a path was given.  ``meta`` is stored in the exported document
    (capture conditions like the pipeline depth).  Skew/straggler
    metrics are recorded on exit either way."""

    def __init__(self, path: str | None = None,
                 meta: dict | None = None):
        self.path = path
        self.meta = meta
        self.events: list[dict] = []
        self._was_on = False

    def __enter__(self):
        self._was_on = _enabled
        reset()
        on()
        return self

    def __exit__(self, *exc):
        self.events = events()
        if self.path is not None and self.events:
            self.path = finish(self.path, self.meta)
        else:
            from . import overlap as _overlap
            if self.events:
                _overlap.record_metrics(self.events)
            reset()
        if not self._was_on:
            off()
        return False


def load(path: str) -> dict:
    """Load one per-process timeline document (raises ValueError on a
    file that isn't one)."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or FORMAT_KEY not in doc:
        raise ValueError(f"{path}: not a slate timeline export")
    return doc


def merge_docs(docs: list[dict]) -> list[dict]:
    """Clock-align multiple per-process documents into one event list.

    Every event's ``t`` is rebased to seconds since the EARLIEST
    absolute instant across all documents, via each document's
    (unix, perf_counter) anchor pair — the cross-process alignment a
    single-process capture gets for free.  Tracks are disambiguated
    with the source process index (``proc`` key on every event)."""
    if not docs:
        return []
    abs_starts = []
    for d in docs:
        a_unix = float(d.get("anchor_unix_s", 0.0))
        a_perf = float(d.get("anchor_perf_s", 0.0))
        for e in d.get("events") or []:
            abs_starts.append(a_unix + (float(e["t"]) - a_perf))
    if not abs_starts:
        return []
    t0 = min(abs_starts)
    merged = []
    for d in docs:
        a_unix = float(d.get("anchor_unix_s", 0.0))
        a_perf = float(d.get("anchor_perf_s", 0.0))
        proc = int(d.get("process", 0))
        for e in d.get("events") or []:
            e = dict(e)
            e["t"] = a_unix + (float(e["t"]) - a_perf) - t0
            e["proc"] = proc
            merged.append(e)
    merged.sort(key=lambda e: e["t"])
    return merged


def to_perfetto(evs: list[dict],
                depth_by_proc: dict[int, int] | None = None) -> dict:
    """Render merged (or raw single-process) events as a multi-track
    Chrome/Perfetto trace: pid = process, tid = device track, paired
    b/e barriers become complete ("X") events.  ``depth_by_proc``
    (process → scheduled pipeline depth, from each document's capture
    meta) suffixes device track names with ``[depth k]`` so traces
    from different lookahead depths stay distinguishable when
    compared side by side."""
    out: list[dict] = []
    tids: dict[tuple, int] = {}
    seen_pids: set = set()
    depth_by_proc = depth_by_proc or {}

    def tid_for(proc, dev):
        key = (proc, dev)
        if key not in tids:
            if isinstance(dev, int):
                tids[key] = dev
            else:  # host tracks above the device range
                tids[key] = 10_000 + len([k for k in tids
                                          if not isinstance(k[1], int)])
            name = (f"device {dev}" if isinstance(dev, int)
                    else str(dev))
            if isinstance(dev, int) and proc in depth_by_proc:
                name = f"{name} [depth {depth_by_proc[proc]}]"
            out.append({"ph": "M", "name": "thread_name", "pid": proc,
                        "tid": tids[key], "args": {"name": name}})
        return tids[key]

    open_stack: dict[tuple, list[dict]] = {}
    for e in sorted(evs, key=lambda e: e["t"]):
        proc = int(e.get("proc", 0))
        if proc not in seen_pids:
            seen_pids.add(proc)
            out.append({"ph": "M", "name": "process_name", "pid": proc,
                        "args": {"name": f"process {proc}"}})
        tid = tid_for(proc, e["dev"])
        key = (proc, e["dev"], e["phase"], e["step"])
        if e["edge"] == "b":
            open_stack.setdefault(key, []).append(e)
            continue
        starts = open_stack.get(key)
        if starts:
            b = starts.pop()
            out.append({"ph": "X", "name": f"{e['phase']} k={e['step']}",
                        "pid": proc, "tid": tid,
                        "ts": b["t"] * 1e6,
                        "dur": max(e["t"] - b["t"], 0.0) * 1e6,
                        "args": {"step": e["step"], "kind": e["kind"],
                                 "routine": e.get("routine", "")}})
        else:  # unmatched end: keep it visible as an instant
            out.append({"ph": "i", "s": "t",
                        "name": f"{e['phase']} k={e['step']}",
                        "pid": proc, "tid": tid, "ts": e["t"] * 1e6,
                        "args": {"kind": e["kind"]}})
    for key, starts in open_stack.items():
        for b in starts:  # unmatched begins too
            out.append({"ph": "i", "s": "t",
                        "name": f"{b['phase']} k={b['step']}",
                        "pid": int(b.get("proc", 0)),
                        "tid": tid_for(int(b.get("proc", 0)), b["dev"]),
                        "ts": b["t"] * 1e6, "args": {"kind": b["kind"]}})
    return {"traceEvents": out}


# ---------------------------------------------------------------------------
# skew / straggler series (fed on finish; overlap.py owns the math)
# ---------------------------------------------------------------------------

def record_metrics(evs: list[dict] | None = None) -> dict:
    """Compute and record the skew/straggler series for ``evs``
    (default: the live buffer).  Returns the overlap analyzer's
    summary dict — see :func:`slate_tpu.obs.overlap.record_metrics`."""
    from . import overlap as _overlap
    return _overlap.record_metrics(events() if evs is None else evs)


# ---------------------------------------------------------------------------
# CLI (registered as the `timeline` subcommand by obs/report.py)
# ---------------------------------------------------------------------------

def add_cli(sub) -> None:
    tl = sub.add_parser(
        "timeline",
        help="merge per-process timelines; overlap + straggler report")
    tl.add_argument("paths", nargs="*",
                    help="per-process timeline JSON files (finish()/"
                         "SLATE_TPU_TIMELINE exports)")
    tl.add_argument("--merge", metavar="OUT",
                    help="write the clock-aligned multi-track Perfetto "
                         "trace here")
    tl.add_argument("--overlap", action="store_true",
                    help="print per-step compute/collective/overlap "
                         "fractions")
    tl.add_argument("--stragglers", action="store_true",
                    help="print the straggler flags (devices >2σ "
                         "behind peers)")
    tl.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the machine-readable report")
    tl.add_argument("--capture-potrf", type=int, metavar="N", default=0,
                    help="first run a potrf of size N on the available "
                         "mesh under capture and report on it (the "
                         "acceptance smoke; writes timeline-p<i>.json "
                         "unless paths are given)")
    tl.add_argument("--nb", type=int, default=32,
                    help="block size for --capture-potrf (default 32)")
    tl.add_argument("--depth", type=int, default=1,
                    help="Option.PipelineDepth for --capture-potrf "
                         "(default 1; the DAG runtime schedules any "
                         "depth) — recorded in the export's meta and "
                         "on merged Perfetto track names")


def cli_run(args) -> int:
    """Body of ``python -m slate_tpu.obs timeline``."""
    import sys
    from . import overlap as _overlap
    paths = list(args.paths)
    if args.capture_potrf:
        path = _capture_potrf_smoke(args.capture_potrf, args.nb,
                                    args.depth)
        if path is None:
            print("capture produced no events", file=sys.stderr)
            return 1
        paths.append(path)
    if not paths:
        print("no timeline files given (and no --capture-potrf)",
              file=sys.stderr)
        return 2
    try:
        docs = [load(p) for p in paths]
    except (OSError, json.JSONDecodeError, ValueError) as e:
        print(f"cannot read timeline: {e}", file=sys.stderr)
        return 2
    merged = merge_docs(docs)
    report = _overlap.analyze(merged)
    if args.merge:
        depths = {int(d.get("process", 0)):
                  int((d.get("meta") or {})["pipeline_depth"])
                  for d in docs
                  if "pipeline_depth" in (d.get("meta") or {})}
        with open(args.merge, "w") as f:
            json.dump(to_perfetto(merged, depth_by_proc=depths), f)
        # keep stdout machine-readable under --json (CI pipes it)
        print(f"merged timeline ({len(merged)} events, "
              f"{len(docs)} process(es)) -> {args.merge}",
              file=sys.stderr if args.as_json else sys.stdout)
    if args.as_json:
        print(json.dumps(report, indent=1))
        return 0
    if args.overlap or not args.merge:
        print(_overlap.format_overlap_table(report))
    if args.stragglers or report.get("stragglers"):
        print(_overlap.format_stragglers(report))
    return 0


def _capture_potrf_smoke(n: int, nb: int, depth: int = 1) -> str | None:
    """Run one SPD factorization on the largest available p×q mesh
    under capture (the acceptance-criteria smoke: on the forced
    8-device CPU mesh this produces a genuinely multi-track timeline
    from one command).  ``depth`` selects the DAG runtime's lookahead
    schedule and is recorded in the export's capture meta."""
    import numpy as np
    import jax
    import slate_tpu as st
    ndev = len(jax.devices())
    p = 1
    for cand in (2, 4):  # squarish grid from what the platform offers
        if ndev % cand == 0 and ndev >= cand * cand:
            p = cand
    q = ndev // p if ndev % p == 0 else 1
    g = st.Grid(p, q) if p * q == ndev else st.Grid(1, 1)
    rng = np.random.default_rng(0)
    a = rng.standard_normal((n, n)).astype(np.float32)
    a = a @ a.T / n + n * np.eye(n, dtype=np.float32)
    A = st.HermitianMatrix.from_dense(a, nb=nb, grid=g)
    try:
        proc = int(jax.process_index())
    except Exception:  # noqa: BLE001
        proc = 0
    path = f"timeline-p{proc}.json"
    from ..types import Option
    with capture(path, meta={"pipeline_depth": depth}) as cap:
        # the smoke exists to attribute lookahead hiding, so it opts
        # into the pipelined loop (the library default is sequential)
        L, info = st.potrf(A, opts={Option.PipelineDepth: depth})
        jax.block_until_ready(L.data)
    return cap.path


def _init_from_env() -> None:
    """``SLATE_TPU_TIMELINE=path`` arms capture at import and writes
    the per-process document at exit (multi-process runs get
    ``<stem>.p<idx>.json``)."""
    import atexit
    path = os.environ.get(ENV, "")
    if not path:
        return
    on()

    def _finish():
        try:
            out = path
            try:
                import jax
                if jax.process_count() > 1:
                    stem, ext = os.path.splitext(path)
                    out = f"{stem}.p{jax.process_index()}{ext or '.json'}"
            except Exception:  # noqa: BLE001
                pass
            finish(out)
        except Exception:  # noqa: BLE001 — exit hooks must not raise
            pass

    atexit.register(_finish)


_init_from_env()
