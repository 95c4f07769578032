"""``cached_jit`` — the single jit entry point for every driver.

Replaces ad-hoc ``jax.jit`` in the driver/runtime layers (slatelint
SL009 enforces this for ``slate_tpu/linalg`` + ``simplified.py``) with
a three-level resolution, in the spirit of SLATE's AOT kernel binaries
and the Design-in-Tiles deployment table:

1. **in-process memo** — a dict from the full executable key to the
   loaded ``Compiled``; hits cost one signature bind + flatten.
2. **on-disk store** (:mod:`.store`) — serialized executables from a
   previous process (the warmup CLI, an earlier run). A disk hit
   deserializes in ~ms instead of recompiling in ~minutes and records
   ``cache.hit{tier=disk}`` + ``cache.compile_ms_saved``.
3. **compile** — ``jit.lower().compile()``, timed under an obs span,
   then persisted best-effort (platforms whose executables don't
   serialize simply skip step 2 forever — plain-jit behavior).

The executable key captures everything that selects machine code:
routine label, function source digest, jit options (donation,
shardings/layouts, static names), static argument reprs, per-leaf
avals (shape/dtype/weak_type) + sharding device sets, the pytree
structure string (Matrix aux data: m/n/nb/grid/op/uplo), and the
environment fingerprint (:func:`.store.fingerprint`).

Unarmed (no ``SLATE_TPU_CACHE_DIR``/``set_cache_dir``) or under
``SLATE_TPU_CACHE=0``, calls pass straight through to a plain
``jax.jit`` wrapper — identical behavior and dispatch cost to the
pre-cache tree. Tracer arguments (a cached_jit called under an outer
jit/vmap) always pass through.

Calling convention note: compiled executables take *dynamic arguments
positionally* in signature order (statics pruned). Loading therefore
reconstructs the trees instead of pickling them — ``in_tree`` from
the canonical ``((dyn...), {})`` form, ``out_tree`` via
``jax.eval_shape`` — because driver pytrees (Matrix) carry device
objects in their aux data that do not pickle.
"""

from __future__ import annotations

import functools
import hashlib
import inspect
import os
import time

import jax
from jax import tree_util as jtu

from .. import obs
from ..runtime import sync
from . import store

# key-schema version: bump to orphan every existing on-disk entry
# (k2: the slatetune table token joined the key — executables are
# bound to the tuning-table content that armed their kernel rungs;
# k3: meta.json carries the program's device_ids, which _load needs)
KEY_VERSION = "k3"


def _tune_token() -> str:
    """Tuning-table state for the key. The tune package consults the
    same store arming as this module; any change to the armed winners
    (or disarming) changes every key, so a kernel-rung choice baked
    into a serialized executable can never be replayed under a
    different tuning."""
    try:
        from .. import tune
        return tune.key_token()
    except Exception:  # noqa: BLE001 — the autotuner must never break a solve
        return "tune:err"


def _abft_token() -> str:
    """ABFT arming state for the key — non-empty ONLY inside an
    ``abft.armed_scope``, and appended to the key only then: an
    unarmed run's key tuple (and its digest → on-disk entry name) is
    bitwise identical to a tree without abft, which is the
    ``Option.Abft`` default-off byte-identity contract."""
    try:
        from ..robust import abft
        return abft.key_token()
    except Exception:  # noqa: BLE001 — verification must never break a solve
        return ""

# SLATE_TPU_SAN=1 arms the slatesan verifier on this layer: each
# compile-tier miss is traced once and verified, the verdict rides the
# entry's meta.json, and disk hits restore it (like costmodel). Unset,
# nothing below imports tools.slatesan — the compile path is untouched.
ENV_SAN = "SLATE_TPU_SAN"


def _san_enabled() -> bool:
    return os.environ.get(ENV_SAN, "") not in ("", "0")

# full executable key -> loaded Compiled (level 1)
_MEMO: dict = {}
# key -> wall stamp of the executable's last memory-tier use (hit or
# insert), the demand signal evict_cold() judges cold entries by
_MEMO_LAST_USE: dict = {}
# (fn, options) -> CachedJit, so repeated cached_jit(...) factory
# calls (e.g. per-device layout-pinned variants) reuse one underlying
# jax.jit wrapper and its trace cache
_INSTANCES: dict = {}
# one lock for _MEMO/_INSTANCES/_INFLIGHT and each wrapper's
# _my_keys/_my_digests: memo promotion was check-then-act (get → miss
# → compile → insert), so two threads racing the same cold key each
# compiled it.  The registry lock makes lookups/inserts atomic; the
# per-key _INFLIGHT gate (held ACROSS the load/compile, which must not
# run under the registry lock) makes the loser of a cold-key race wait
# for the winner's executable instead of compiling its own.  Gates are
# kept for the process lifetime — bounded by distinct executable keys.
_registry_lock = sync.RLock(name="cache.jitcache.registry")
_memo_cell = sync.shared_cell("cache.jitcache._MEMO")
_INFLIGHT: dict = {}


def _leaf_sig(x):
    aval = jax.typeof(x)
    sig = (tuple(getattr(aval, "shape", ())), str(aval.dtype),
           bool(getattr(aval, "weak_type", False)))
    sh = getattr(x, "sharding", None)
    if sh is not None:
        try:
            ids = tuple(sorted(d.id for d in sh.device_set))
        except Exception:
            ids = ()
        sig += (type(sh).__name__, ids,
                repr(getattr(sh, "spec", "")))
    return sig


def _opts_repr(static_argnums, static_argnames, jit_kwargs) -> str:
    return repr((static_argnums, static_argnames,
                 sorted((k, repr(v)) for k, v in jit_kwargs.items())))


class CachedJit:
    """One jitted function routed through the executable cache."""

    def __init__(self, fn, *, routine=None, static_argnums=None,
                 static_argnames=None, **jit_kwargs):
        functools.update_wrapper(self, fn, updated=())
        self._fn = fn
        self.routine = routine or getattr(
            fn, "__qualname__", getattr(fn, "__name__", "fn"))
        self._jit = jax.jit(fn, static_argnums=static_argnums,
                            static_argnames=static_argnames,
                            **jit_kwargs)
        self._sig = inspect.signature(fn)
        self._params = tuple(self._sig.parameters)
        names = set()
        if static_argnums is not None:
            nums = (static_argnums if isinstance(static_argnums,
                                                 (tuple, list))
                    else (static_argnums,))
            names |= {self._params[i] for i in nums}
        if static_argnames is not None:
            names |= ({static_argnames}
                      if isinstance(static_argnames, str)
                      else set(static_argnames))
        self._static_names = frozenset(names)
        kinds = [p.kind for p in self._sig.parameters.values()]
        # *args/**kwargs signatures can't be canonicalized — such
        # wrappers stay plain jit (none exist in the driver tree today)
        self._cacheable = not any(
            k in (inspect.Parameter.VAR_POSITIONAL,
                  inspect.Parameter.VAR_KEYWORD) for k in kinds)
        self._kw_only = frozenset(
            name for name, p in self._sig.parameters.items()
            if p.kind == inspect.Parameter.KEYWORD_ONLY)
        try:
            src = inspect.getsource(fn)
        except (OSError, TypeError):
            # no source on disk (REPL, -c): digest the bytecode — must
            # be process-stable, a repr() would embed the object address
            code = getattr(fn, "__code__", None)
            src = (f"{getattr(fn, '__module__', '')}."
                   f"{getattr(fn, '__qualname__', '')}:"
                   + (repr((code.co_code, code.co_consts))
                      if code is not None else type(fn).__name__))
        self._src_digest = hashlib.sha256(src.encode()).hexdigest()[:16]
        self._opts_digest = _opts_repr(static_argnums, static_argnames,
                                       jit_kwargs)
        self._my_keys: set = set()
        self._my_digests: set = set()

    # -- plain-jit conveniences the tree already relies on ----------------
    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)

    def clear_cache(self):
        """Drop this function's memo entries, the underlying jit's
        trace cache, AND the store entries this instance produced or
        served this process. Tests use this to force a retrace after
        monkeypatching trace-time constants — the key cannot see a
        patched module constant, so an armed store would otherwise
        hand the pre-patch executable straight back (and persist the
        patched one for later innocent callers)."""
        with _registry_lock:
            _memo_cell.write()
            for k in self._my_keys:
                _MEMO.pop(k, None)
                _MEMO_LAST_USE.pop(k, None)
            self._my_keys.clear()
            digests = list(self._my_digests)
            self._my_digests.clear()
        for d in digests:
            store.remove(d)
        try:
            self._jit.clear_cache()
        except Exception:
            pass

    # -- the cache path ----------------------------------------------------
    def __call__(self, *args, **kwargs):
        if not self._cacheable or store.cache_dir() is None:
            return self._jit(*args, **kwargs)
        try:
            ba = self._sig.bind(*args, **kwargs)
            ba.apply_defaults()
            bound = ba.arguments
        except TypeError:
            return self._jit(*args, **kwargs)
        # canonical calling convention: signature order, keyword-only
        # params by name, statics pruned from the dynamic split
        dyn_pos = tuple(bound[p] for p in self._params
                        if p not in self._static_names
                        and p not in self._kw_only)
        dyn_kw = {p: bound[p] for p in self._params
                  if p not in self._static_names and p in self._kw_only}
        leaves, treedef = jtu.tree_flatten((dyn_pos, dyn_kw))
        if any(isinstance(x, jax.core.Tracer) for x in leaves):
            return self._jit(*args, **kwargs)
        try:
            statics = tuple((p, repr(bound[p])) for p in self._params
                            if p in self._static_names)
            # obs.timeline.key_token(): a capture-instrumented program
            # carries extra host callbacks — it must never be satisfied
            # by an uninstrumented cached executable (or vice versa)
            key = (KEY_VERSION, self.routine, self._src_digest,
                   self._opts_digest, repr(statics), str(treedef),
                   repr([_leaf_sig(x) for x in leaves]),
                   store.fp_digest(), obs.timeline.key_token(),
                   _tune_token())
            abft_tok = _abft_token()
            if abft_tok:
                key = key + (abft_tok,)
        except Exception:
            return self._jit(*args, **kwargs)
        with _registry_lock:
            _memo_cell.read()
            compiled = _MEMO.get(key)
            if compiled is not None:
                _MEMO_LAST_USE[key] = time.time()
        if compiled is not None:
            obs.count("cache.hit", routine=self.routine, tier="memory")
            return compiled(*dyn_pos, **dyn_kw)
        digest = hashlib.sha256(
            "\x1e".join(key).encode()).hexdigest()[:32]
        with _registry_lock:
            gate = _INFLIGHT.get(key)
            if gate is None:
                gate = sync.Lock(name="cache.jitcache.inflight")
                _INFLIGHT[key] = gate
            self._my_digests.add(digest)
        with gate:
            # double-check under the gate: a racing caller that lost
            # the cold-key race finds the winner's executable here
            with _registry_lock:
                _memo_cell.read()
                compiled = _MEMO.get(key)
                if compiled is not None:
                    _MEMO_LAST_USE[key] = time.time()
            if compiled is not None:
                obs.count("cache.hit", routine=self.routine,
                          tier="memory")
                return compiled(*dyn_pos, **dyn_kw)
            compiled = self._load(digest, dyn_pos, dyn_kw, bound)
            if compiled is None:
                compiled = self._compile_and_persist(key, digest, bound)
                if compiled is None:      # lowering path unsupported
                    return self._jit(*args, **kwargs)
            with _registry_lock:
                _memo_cell.write()
                _MEMO[key] = compiled
                _MEMO_LAST_USE[key] = time.time()
                self._my_keys.add(key)
        return compiled(*dyn_pos, **dyn_kw)

    def _canonical_call_args(self, bound):
        """(args, kwargs) for the underlying jit wrapper: everything
        (statics included) in signature order, kw-only by name."""
        cargs = tuple(bound[p] for p in self._params
                      if p not in self._kw_only)
        ckw = {p: bound[p] for p in self._params if p in self._kw_only}
        return cargs, ckw

    def _dyn_only_fn(self, bound, of=None):
        """The function with statics bound, taking only dynamic args —
        used by eval_shape to reconstruct out_tree at load time, and
        (with ``of=self._jit``) by the slatesan hook so the traced
        program is the real pjit eqn carrying donated_invars."""
        fn = self._fn if of is None else of
        sd = {p: bound[p] for p in self._params
              if p in self._static_names}
        params, static, kw_only = (self._params, self._static_names,
                                   self._kw_only)

        def call(*dyn, **dyn_kw):
            it = iter(dyn)
            cargs = [sd[p] if p in static else next(it)
                     for p in params if p not in kw_only]
            ckw = {p: (sd[p] if p in static else dyn_kw[p])
                   for p in params if p in kw_only}
            return fn(*cargs, **ckw)
        return call

    def _san_report(self, bound):
        """Trace-and-verify this call under slatesan (compile-tier
        miss, or a legacy disk entry with no stored verdict). Returns
        the SanReport, or None when unarmed or on any failure —
        verification must never break a solve."""
        if not _san_enabled():
            return None
        try:
            from tools.slatesan import runtime as san_rt
            dyn_pos = tuple(bound[p] for p in self._params
                            if p not in self._static_names
                            and p not in self._kw_only)
            dyn_kw = {p: bound[p] for p in self._params
                      if p not in self._static_names
                      and p in self._kw_only}
            tier = bound.get("tier")
            if not isinstance(tier, str):
                tier = None
            return san_rt.verify_callable(
                self._dyn_only_fn(bound, of=self._jit), *dyn_pos,
                routine=self.routine, tier=tier, **dyn_kw)
        except Exception as e:
            obs.instant("san.error", routine=self.routine,
                        error=repr(e)[:120])
            return None

    def _load(self, digest, dyn_pos, dyn_kw, bound):
        got = store.load(digest, routine=self.routine)
        if got is None:
            return None
        payload, meta = got
        t0 = time.perf_counter()  # slatelint: disable=SL008 -- host-only deserialize wall time, reported via obs.record_span
        try:
            store.ensure_custom_calls_registered()
            from jax.experimental import serialize_executable as se
            in_tree = jtu.tree_structure((dyn_pos, dyn_kw))
            out_tree = jtu.tree_structure(
                jax.eval_shape(self._dyn_only_fn(bound),
                               *dyn_pos, **dyn_kw))
            # the devices the program was compiled for, in its own
            # order: left out, jax loads it onto ALL local devices and
            # a Grid(1,1) program dies on a multi-device host
            by_id = {d.id: d for d in jax.devices()}
            compiled = se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i]
                                   for i in meta["device_ids"]])
        except Exception as e:
            obs.count("cache.corrupt", routine=self.routine)
            store.quarantine_entry(
                digest, f"deserialize: {e!r}", routine=self.routine)
            return None
        ms = (time.perf_counter() - t0) * 1e3  # slatelint: disable=SL008 -- host-only deserialize wall time
        obs.count("cache.hit", routine=self.routine, tier="disk")
        # restore the compile-time cost analysis persisted in meta.json
        # so disk-hit spans still carry flops/bytes attribution
        obs.costmodel.record(self.routine, meta.get("cost_analysis"),
                             source="disk")
        if _san_enabled():
            # restore the persisted verdict without re-tracing; a
            # pre-slatesan entry (no verdict in meta) gets one fresh
            # trace verify, same as a compile-tier miss would
            san = meta.get("san")
            if san is not None:
                try:
                    from tools.slatesan import runtime as san_rt
                    san_rt.restore(self.routine, san)
                except Exception as e:
                    obs.instant("san.error", routine=self.routine,
                                error=repr(e)[:120])
            else:
                self._san_report(bound)
        obs.observe("cache.deserialize_ms", ms, routine=self.routine)
        obs.count("cache.compile_ms_saved",
                  float(meta.get("compile_ms", 0.0)),
                  routine=self.routine)
        obs.record_span("cache.deserialize", ms / 1e3,
                        routine=self.routine)
        return compiled

    def _compile_and_persist(self, key, digest, bound):
        obs.count("cache.miss", routine=self.routine)
        cargs, ckw = self._canonical_call_args(bound)
        t0 = time.perf_counter()  # slatelint: disable=SL008 -- host-only compile wall time (no device work in the window)
        try:
            with obs.span("cache.compile", routine=self.routine) as sp:
                compiled = self._jit.lower(*cargs, **ckw).compile()
                cost = obs.costmodel.capture(compiled)
                # stamp the span with the optimized-HLO fingerprint:
                # distinct compiles of the same key (the "32k compile
                # lottery") become distinguishable in the trace
                if cost and cost.get("hlo") and hasattr(sp, "labels"):
                    sp.labels["hlo"] = cost["hlo"]
        except Exception:
            # e.g. an option the AOT path can't lower — plain jit owns it
            obs.instant("cache.lower_unsupported", routine=self.routine)
            return None
        ms = (time.perf_counter() - t0) * 1e3  # slatelint: disable=SL008 -- host-only compile wall time
        obs.observe("cache.compile_ms", ms, routine=self.routine)
        obs.costmodel.record(self.routine, cost)
        san = self._san_report(bound)
        try:
            from jax.experimental import serialize_executable as se
            payload, _, _ = se.serialize(compiled)
            meta = {"routine": self.routine, "compile_ms": ms,
                    "key": list(key),
                    "device_ids": [
                        d.id for d in compiled.runtime_executable()
                        .local_devices()]}
            if cost:
                meta["cost_analysis"] = cost
            if san is not None:
                meta["san"] = san.to_dict()
            store.save(digest, payload, meta)
        except Exception as e:
            # AOT serialization unsupported here: still use the
            # compiled program in-process (== plain jit)
            obs.count("cache.serialize_fail", routine=self.routine)
            obs.instant("cache.serialize_unsupported",
                        routine=self.routine, error=repr(e)[:120])
        return compiled


def cached_jit(fn=None, *, routine=None, static_argnums=None,
               static_argnames=None, **jit_kwargs):
    """Drop-in for ``jax.jit`` / ``partial(jax.jit, ...)`` that routes
    through the executable cache. Instances are memoized on
    (fn, options), so calling this per-shape or per-device (as the
    getrf layout-pinned group path does) reuses wrappers."""
    if fn is None:
        return functools.partial(
            cached_jit, routine=routine, static_argnums=static_argnums,
            static_argnames=static_argnames, **jit_kwargs)
    inst_key = (fn, routine,
                _opts_repr(static_argnums, static_argnames, jit_kwargs))
    with _registry_lock:
        inst = _INSTANCES.get(inst_key)
        if inst is None:
            inst = CachedJit(fn, routine=routine,
                             static_argnums=static_argnums,
                             static_argnames=static_argnames,
                             **jit_kwargs)
            _INSTANCES[inst_key] = inst
    return inst


def clear_in_process(routine: str | None = None) -> None:
    """Drop in-process memoized executables and wrapper trace caches
    (the on-disk store is untouched). With ``routine``, only wrappers
    whose routine label matches (exactly or as a dotted prefix) are
    cleared — the replacement for the old narrow
    ``getrf._group_jit_cache.clear()`` test hook. A full clear
    mid-suite forces every driver program to retrace, which is exactly
    the compile tax this layer exists to avoid — scope it."""
    if routine is not None:
        with _registry_lock:
            insts = list(_INSTANCES.values())
        for inst in insts:
            if (inst.routine == routine
                    or inst.routine.startswith(routine + ".")):
                inst.clear_cache()
        return
    with _registry_lock:
        insts = list(_INSTANCES.values())
        _INSTANCES.clear()
        _memo_cell.write()
        _MEMO.clear()
        _MEMO_LAST_USE.clear()
        _INFLIGHT.clear()
    for inst in insts:
        try:
            inst._jit.clear_cache()
        except Exception:
            pass


def evict_cold(routine_prefix: str | None = None,
               min_idle_s: float = 0.0, now: float | None = None) -> int:
    """Drop memory-tier executables whose last use is at least
    ``min_idle_s`` ago — the demand-driven eviction hook the slateflow
    scheduler calls when ``hbm.watch`` reports the budget exceeded.
    ONLY the in-process memo is dropped (level 1): the on-disk store
    keeps the executable, so a re-request pays a ~ms deserialize, not
    a recompile.  ``routine_prefix`` scopes eviction to routines
    matching exactly or as a dotted prefix (``"serve."`` evicts only
    serving executables, never the resident factorization drivers).
    Returns the number evicted; each lands as a
    ``cache.evict{routine, tier="memory"}`` counter."""
    now = time.time() if now is None else now
    evicted: list[str] = []
    with _registry_lock:
        for key in list(_MEMO):
            routine = key[1] if len(key) > 1 else ""
            if routine_prefix is not None and not (
                    routine == routine_prefix
                    or str(routine).startswith(routine_prefix)):
                continue
            if now - _MEMO_LAST_USE.get(key, 0.0) < min_idle_s:
                continue
            _memo_cell.write()
            _MEMO.pop(key, None)
            _MEMO_LAST_USE.pop(key, None)
            evicted.append(str(routine))
    for routine in evicted:
        obs.count("cache.evict", routine=routine, tier="memory")
    return len(evicted)
