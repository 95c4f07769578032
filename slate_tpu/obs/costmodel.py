"""slatescope cost model: what a compiled program *should* cost.

Two sources of truth are reconciled here:

* **XLA's own accounting** — ``compiled.cost_analysis()`` (flops,
  bytes accessed, transcendentals) and ``compiled.memory_analysis()``
  (argument/output/temp/code bytes), captured by
  ``cache/jitcache.py`` at compile time via :func:`capture` and
  persisted into the cache entry's ``meta.json`` so a disk-hit in a
  fresh process still knows what the executable costs without
  re-deriving anything;
* **the closed-form tables** — :mod:`.flops` for operation counts and
  :data:`MIN_BYTES_FORMULAS` here for *minimum* memory traffic (each
  operand read once, each result written once).  The closed forms are
  the model; XLA's numbers are the measurement of the lowered
  program.  :func:`reconcile` divides one by the other — a ratio far
  from 1 means XLA is moving data the algorithm doesn't require
  (layout copies, rematerialization) and is exactly the signal the
  roofline attributor feeds on.

The registry (:func:`record` / :func:`lookup`) is process-global and
keyed by routine label — the same label spans carry — so
``report.enrich_span`` can attach flops/bytes to a span whose labels
don't carry dims (the blank-attribution-row class cached runs used to
produce).  Everything in this module is host-side bookkeeping:
capture failures degrade to ``None``, never to an exception in the
compile path.
"""

from __future__ import annotations

import math
import re

from . import flops as _flops
from . import metrics as _metrics
from ..runtime import sync

# routine label -> captured cost dict (latest capture wins; a disk-hit
# restore and a fresh compile of the same routine agree by key)
_COSTS: dict[str, dict] = {}
_lock = sync.Lock(name="obs.costmodel.costs")

_DTYPE_BYTES = {
    "float32": 4, "float64": 8, "bfloat16": 2, "float16": 2,
    "complex64": 8, "complex128": 16, "int32": 4, "int64": 8,
    "int8": 1, "uint8": 1, "bool": 1,
}


def dtype_bytes(dtype) -> int:
    """Item size for a dtype label (default f32's 4 — span labels are
    strings, not dtype objects)."""
    return _DTYPE_BYTES.get(str(dtype), 4)


# ---------------------------------------------------------------------------
# closed-form minimum-traffic table (the companion of flops.FLOP_FORMULAS)
# ---------------------------------------------------------------------------
# Each formula returns ELEMENTS moved assuming every operand is read
# once and every result written once — the algorithmic floor a cache
# -resident blocked implementation approaches, per the LAWN-41 shapes
# flops.py uses.  Multiply by the itemsize for bytes.

def _b_gemm(m, n, k):
    return m * k + k * n + 2.0 * m * n          # read A,B; read+write C


def _b_potrf(n):
    return float(n) ** 2                         # triangle read + write


def _b_getrf(n, m=None):
    m = n if m is None else m
    return 2.0 * m * n


def _b_geqrf(m, n):
    return 2.0 * m * n


def _b_gelqf(m, n):
    return _b_geqrf(n, m)


def _b_trsm(m, n, side="left"):
    tri = (float(m) ** 2 if side == "left" else float(n) ** 2) / 2.0
    return tri + 2.0 * m * n


def _b_syrk(n, k):
    return n * float(k) + float(n) ** 2


def _b_solve(n, nrhs=1):
    return float(n) ** 2 + 2.0 * n * nrhs


def _b_he2hb(n, nb=None):
    return 2.0 * float(n) ** 2


def _b_hb2st(n, b):
    return 2.0 * float(n) * b


def _b_ge2tb(m, n):
    return 2.0 * m * n


def _b_heev(n):
    return 2.0 * float(n) ** 2


def _b_gesvd(m, n=None):
    n = m if n is None else n
    return 2.0 * m * n


MIN_BYTES_FORMULAS = {
    "gemm": _b_gemm,
    "potrf": _b_potrf,
    "getrf": _b_getrf,
    "geqrf": _b_geqrf,
    "gelqf": _b_gelqf,
    "trsm": _b_trsm,
    "syrk": _b_syrk,
    "herk": _b_syrk,
    "potrs": _b_solve,
    "getrs": _b_solve,
    "he2hb": _b_he2hb,
    "hb2st": _b_hb2st,
    "ge2tb": _b_ge2tb,
    "heev": _b_heev,
    "gesvd": _b_gesvd,
}


def min_bytes(routine: str, dtype=None, **dims) -> float | None:
    """Closed-form minimum bytes moved for ``routine`` at ``dims``
    (same forgiving contract as :func:`flops.flop_count`: unknown
    routine or unsatisfied dims return ``None``)."""
    fn = MIN_BYTES_FORMULAS.get(routine)
    if fn is None:
        return None
    import inspect
    accepted = inspect.signature(fn).parameters
    try:
        elems = fn(**{k: v for k, v in dims.items()
                      if v is not None and k in accepted})
    except (TypeError, ValueError):
        return None
    return float(elems) * dtype_bytes(dtype)


# ---------------------------------------------------------------------------
# XLA capture
# ---------------------------------------------------------------------------

# one optimized-HLO collective op per line; shape like f32[8,64,64]
_COLLECTIVE_RE = re.compile(
    r"=\s*(?:\(?[a-z0-9]+\[[0-9,]*\][^=]*?\)?\s*)?"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|all-to-all|collective-broadcast)"
    r"(?:-start|-done)?\(", re.ASCII)
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")

_SHAPE_DTYPE_BYTES = {
    "f32": 4, "f64": 8, "bf16": 2, "f16": 2, "c64": 8, "c128": 16,
    "s32": 4, "s64": 8, "u32": 4, "u64": 8, "s8": 1, "u8": 1,
    "pred": 1, "s16": 2, "u16": 2,
}


def collective_shapes(hlo_text: str) -> dict:
    """The one reader of collectives in optimized HLO text:
    ``{kind: [[(bytes an element, dims), ...], ...]}``, for every
    collective op of a kind the shapes of its results. XLA combines
    collectives of one kind into one op with a tuple result, in an
    order it picks: each member of the tuple is read. ``-done`` halves
    of async pairs are skipped; a ``-start`` half that lists its
    operands before its results (every kind but all-reduce) gives the
    results only.
    """
    out: dict[str, list] = {}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        head = line[:m.start(1)].partition("=")[2]
        shapes = [(_SHAPE_DTYPE_BYTES[dt],
                   tuple(int(d) for d in dims.split(",") if d))
                  for dt, dims in _SHAPE_RE.findall(head)
                  if dt in _SHAPE_DTYPE_BYTES]
        started = line[m.end(1):].startswith("-start")
        if started and kind != "all-reduce" and len(shapes) > 1:
            shapes = [s for s in shapes if s[1]]     # context scalars
            shapes = shapes[len(shapes) // 2:]
        out.setdefault(kind, []).append(shapes)
    return out


def collective_stats(hlo_text: str) -> dict:
    """Parse optimized HLO text for collective ops.

    Returns ``{kind: {"count": int, "bytes": float}}`` where bytes is
    the summed result-shape footprint of each collective — the data
    volume the op materializes per program execution, every member of
    a combined (tuple) result included (``-start`` halves of async
    pairs are counted, ``-done`` halves skipped so an overlapped
    collective isn't double-counted).
    """
    return {kind: {"count": len(ops),
                   "bytes": float(sum(size * math.prod(dims)
                                      for op in ops for size, dims in op))}
            for kind, ops in collective_shapes(hlo_text).items()}


def capture(compiled, *, hlo_text: str | None = None) -> dict | None:
    """Extract the XLA cost/memory analysis (and collective footprint)
    from a ``jax`` ``Compiled``.  Never raises — any API the platform
    lacks simply leaves its keys out; an entirely dark platform
    returns ``None``.
    """
    out: dict = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            for src, dst in (("flops", "flops"),
                             ("bytes accessed", "bytes_accessed"),
                             ("transcendentals", "transcendentals")):
                v = ca.get(src)
                if v is not None:
                    out[dst] = float(v)
    except Exception:  # noqa: BLE001 — cost capture must never crash a compile
        pass
    try:
        ma = compiled.memory_analysis()
        mem = {}
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                mem[attr.replace("_size_in_bytes", "_bytes")] = int(v)
        if mem:
            mem["peak_bytes"] = (mem.get("argument_bytes", 0)
                                 + mem.get("output_bytes", 0)
                                 + mem.get("temp_bytes", 0))
            out["memory"] = mem
    except Exception:  # noqa: BLE001
        pass
    try:
        text = hlo_text if hlo_text is not None else compiled.as_text()
        coll = collective_stats(text)
        if coll:
            out["collectives"] = coll
        out["hlo"] = hlo_fingerprint(text)
    except Exception:  # noqa: BLE001
        pass
    return out or None


def hlo_fingerprint(text: str) -> str:
    """Short content digest of an optimized-HLO dump.  Two runs with
    the same fingerprint executed the SAME machine code; the geqrf
    8.9–11.0 TF/s "compile lottery" (ROADMAP soft spots) shows up as
    different fingerprints on identical inputs — this tag makes that
    attributable in compile spans, bench rows, and roofline output."""
    import hashlib
    return hashlib.sha256(text.encode()).hexdigest()[:10]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def record(routine: str, cost: dict | None, *,
           source: str = "compile") -> None:
    """Register a captured cost under its routine label (and count the
    capture so cached-vs-fresh attribution coverage is observable)."""
    if not cost:
        return
    with _lock:
        _COSTS[routine] = dict(cost)
    _metrics.inc("costmodel.captured", routine=routine, source=source)
    for kind, s in (cost.get("collectives") or {}).items():
        _metrics.inc("comm.hlo_collectives", float(s.get("count", 0)),
                     kind=kind, routine=routine)
        _metrics.inc("comm.hlo_bytes", float(s.get("bytes", 0.0)),
                     kind=kind, routine=routine)


def lookup(routine: str) -> dict | None:
    with _lock:
        c = _COSTS.get(routine)
        return dict(c) if c else None


def lookup_prefix(routine: str) -> dict | None:
    """Cost for ``routine``, falling back to any registered label that
    extends it with a dotted suffix (driver spans say ``potrf``, the
    cache key says ``potrf.chunk_core``) — first match in sorted
    order, so the fallback is deterministic."""
    c = lookup(routine)
    if c is not None:
        return c
    with _lock:
        for name in sorted(_COSTS):
            if name.startswith(routine + "."):
                return dict(_COSTS[name])
    return None


def snapshot() -> dict:
    """Copy of the registry (embedded in ``obs.dump()`` as the
    ``costmodel`` section so the report CLI can attribute spans from a
    file, the way a live process attributes from memory)."""
    with _lock:
        return {k: dict(v) for k, v in _COSTS.items()}


def load_snapshot(costs: dict) -> None:
    """Merge a snapshot (e.g. a parsed ``costmodel`` export section)
    into the registry."""
    if not isinstance(costs, dict):
        return
    with _lock:
        for k, v in costs.items():
            if isinstance(v, dict):
                _COSTS[k] = dict(v)


def reset() -> None:
    with _lock:
        _COSTS.clear()


# ---------------------------------------------------------------------------
# reconciliation
# ---------------------------------------------------------------------------

def reconcile(routine: str, dtype=None, **dims) -> dict | None:
    """Closed-form vs XLA accounting for one routine.

    Returns ``{"model_flops", "xla_flops", "flops_ratio",
    "model_bytes", "xla_bytes", "bytes_ratio"}`` (keys present where
    both sides exist; ratio = xla / model, so >1 means the lowered
    program does more than the algorithm requires).  ``None`` when the
    routine has no captured cost.
    """
    cost = lookup_prefix(routine)
    if cost is None:
        return None
    out: dict = {"routine": routine}
    mf = _flops.flop_count(routine, **dims)
    xf = cost.get("flops")
    if mf:
        out["model_flops"] = mf
    if xf is not None:
        out["xla_flops"] = xf
    if mf and xf:
        out["flops_ratio"] = xf / mf
    mb = min_bytes(routine, dtype=dtype, **dims)
    xb = cost.get("bytes_accessed")
    if mb:
        out["model_bytes"] = mb
    if xb is not None:
        out["xla_bytes"] = xb
    if mb and xb:
        out["bytes_ratio"] = xb / mb
    return out
