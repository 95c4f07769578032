#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that slate_tpu still starts on the chip.

Drives the library's main path once, through the entry points a user
calls, on the attached TPU:

* default (one chip): ``slate.posv`` and ``slate.gesv`` at n=16384,
  nb=1024, f32 on ``Grid(1, 1)``, then 8 served solves through
  ``serve.make_scheduler("flow")``;
* ``--chips 4``: only ``slate.posv``/``slate.gesv`` on ``Grid(2, 2)``
  over four chips, with proofs of sharding and collectives.

Every solve is judged by the norm-wise backward error
``‖A·X − B‖∞ / (‖A‖∞‖X‖∞ + ‖B‖∞) ≤ C_BOUND·n·ε`` against a plain
``jnp.matmul(precision="highest")`` recomputation (and, on one chip,
also through the public ``slate.gemm`` + ``slate.norm``).

One process, no children, no ``try/except`` around a phase: any
exception or failed check exits non-zero.  One JSON line per step; the
last line of stdout is ``{"ok": true, "device": {...}}`` only when every
phase passed on a TPU of the required chip count.  Without a TPU the
script stops at the device check (exit 1, nothing on stdout).
``--rehearse-on-cpu`` (with a tiny ``--n/--nb``) runs the phases on
whatever backend there is and then prints ``{"ok": false, ...}`` and
exits 1, so a CPU run can never be mistaken for a pass.  Walls are for
the record only; nothing about speed is asserted.
"""

from __future__ import annotations

import argparse
import collections
import json
import math
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import slate_tpu as slate
from slate_tpu import obs, serve, tune
from slate_tpu.cache import place_jax_compile_cache
from slate_tpu.linalg import getrf, potrf
from slate_tpu.types import superstep_chunk

EPS = 2.0 ** -24          # f32 unit roundoff
C_BOUND = 1.0             # backward-error bound = C_BOUND · n · EPS
BOUND_DEF = "1*n*eps, eps=2^-24"
NRHS = 8


def emit(**step) -> None:
    print(json.dumps(step), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED {what}")


def timed(fn):
    """(result, wall seconds) of ``fn()`` with the device drained."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def backward_error_plain(Ad, Xd, Bd) -> float:
    """The plain reference: jnp only, nothing of the code under test."""
    def ninf(M):
        return jnp.linalg.norm(M, ord=jnp.inf)
    R = jnp.matmul(Ad, Xd, precision="highest") - Bd
    return float(ninf(R) / (ninf(Ad) * ninf(Xd) + ninf(Bd)))


def backward_error_slate(Ag, X, B) -> float:
    """The same quantity through the public slate.gemm + slate.norm."""
    ninf = lambda M: slate.norm(slate.Norm.Inf, M)      # noqa: E731
    R = slate.gemm(1.0, Ag, X, -1.0, B)
    return float(ninf(R) / (ninf(Ag) * ninf(X) + ninf(B)))


def general_view(A):
    """A Hermitian operand as the general Matrix over the same tiles."""
    return slate.Matrix(data=A.data, m=A.m, n=A.n, nb=A.nb, grid=A.grid)


def dense_of(A):
    """Gathered dense operand; a Hermitian one is rebuilt from the
    triangle its ``uplo`` contract guarantees (lower)."""
    d = A.to_dense()
    if isinstance(A, slate.HermitianMatrix):
        d = jnp.tril(d) + jnp.tril(d, -1).T
    return d


def chunk_phase(span: str) -> list[str]:
    """Which driver path ran: the ``phase`` labels of the named span."""
    return sorted({s["labels"].get("phase", "") for s in
                   obs.metrics.snapshot()["spans"] if s["name"] == span})


def solve_twice(routine: str, A, B):
    """``slate.posv``/``slate.gesv`` called twice: (outputs, info, first
    wall — compile included —, second wall)."""
    solve = slate.posv if routine == "posv" else slate.gesv
    out, first_s = timed(lambda: solve(A, B))
    _, second_s = timed(lambda: solve(A, B))
    info = int(out[-1])
    check(info == 0, f"{routine}: info == {info}")
    return out, info, first_s, second_s


def both_solves(args, g, step) -> None:
    """``step("posv", A, B)`` then ``step("gesv", A, B)`` on operands
    made on the device(s) of ``g`` from the seed."""
    B = slate.random_matrix(args.n, NRHS, args.nb, g, jnp.float32,
                            seed=args.seed + 1)
    step("posv", slate.random_spd(args.n, nb=args.nb, grid=g,
                                  dtype=jnp.float32, seed=args.seed), B)
    step("gesv", slate.random_matrix(args.n, args.n, args.nb, g,
                                     jnp.float32, seed=args.seed + 2), B)


def collectives_in(text: str) -> dict:
    return {op: len(re.findall(rf"\b{op}(?:-start)?\(", text))
            for op in ("all-gather", "all-reduce")}


# --------------------------------------------------------------------------
# one chip: posv, gesv, served solves
# --------------------------------------------------------------------------

def step_solve(routine: str, A, B, on_tpu: bool) -> None:
    """One public factor-and-solve, twice, with both residual checks."""
    n, nb = A.n, A.nb
    out, info, first_s, second_s = solve_twice(routine, A, B)
    X = out[0]
    Ag = general_view(A) if routine == "posv" else A
    be_slate = backward_error_slate(Ag, X, B)
    be_plain = backward_error_plain(dense_of(A), X.to_dense(),
                                    B.to_dense())
    bound = C_BOUND * n * EPS
    line = dict(step=routine, n=n, nb=nb, nrhs=NRHS, grid="1x1",
                first_call_s=first_s, second_call_s=second_s, info=info,
                backward_error_slate=be_slate,
                backward_error_plain=be_plain, bound=bound,
                bound_def=BOUND_DEF)
    if routine == "posv":
        line["path"] = "potrf:" + ",".join(chunk_phase("potrf.chunk"))
    else:
        line.update(lu_path(A, on_tpu))
    emit(**line)
    check(math.isfinite(be_slate) and be_slate <= bound,
          f"{routine}: slate.gemm/norm backward error {be_slate} > {bound}")
    check(math.isfinite(be_plain) and be_plain <= bound,
          f"{routine}: plain backward error {be_plain} > {bound}")
    check(abs(be_slate - be_plain) <= 0.1 * bound,
          f"{routine}: the two checks disagree: {be_slate} vs {be_plain}")


def lu_path(A, on_tpu: bool) -> dict:
    """Which LU ran.  On the chip it must be the no-row-movement fast
    path on the COMPILED Pallas panel — not the dense substitute, not
    interpret mode."""
    Am = A.materialize()
    mode = getrf._fast_path_mode(Am, "partial")
    if on_tpu:
        check(mode == "tpu", f"gesv: _fast_path_mode answered {mode!r}, "
              "the LU fell off the Pallas fast path")
    if mode is None:
        return {"path": "getrf:dense (no fast path at this size/backend)"}
    lowered = getrf._getrf_fast_jit.lower(
        Am, interpret=(mode == "interpret"),
        want_ipiv=False, fold=getrf._fold_now())
    kernels = lowered.as_text().count("tpu_custom_call")
    if on_tpu:
        check(kernels > 0, "gesv: no tpu_custom_call in the lowered "
              "getrf.fast program")
    return {"path": f"getrf:fast_path/{mode}",
            "tpu_custom_calls_lowered": kernels}


def served_requests(seed: int, small: bool) -> list:
    """8 requests, two tenants, posv and gesv; orders drawn from the
    seed, off every tile multiple, stratified over the buckets."""
    ranges = ([(40, 250)] if small
              else [(300, 511), (513, 1023), (513, 1023), (1025, 2000)])
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(8):
        lo, hi = ranges[i % len(ranges)]
        n = int(rng.integers(lo, hi + 1))
        while n % 64 == 0:
            n = int(rng.integers(lo, hi + 1))
        a = rng.standard_normal((n, n)).astype(np.float32)
        routine = "posv" if i < 4 else "gesv"
        if routine == "posv":
            a = a @ a.T / n + np.eye(n, dtype=np.float32)
        b = rng.standard_normal((n, 2)).astype(np.float32)
        reqs.append(serve.SolveRequest(
            a=a, b=b, routine=routine, tag=i,
            tenant=("tenant-a", "tenant-b")[i % 2]))
    return reqs


def step_served(seed: int, small: bool) -> None:
    reqs = served_requests(seed, small)
    sched = serve.make_scheduler("flow", auto_start=False)
    resolved = collections.Counter()
    sched.on_complete(lambda res: resolved.update([res.rid]))
    t0 = time.perf_counter()
    tickets = [sched.submit(r) for r in reqs]
    sched.start()
    results = [t.result(timeout=900) for t in tickets]
    wall_s = time.perf_counter() - t0
    sched.stop()
    for req, res in zip(reqs, results):
        n = req.a.shape[0]
        check(not res.shed, f"served[{req.tag}]: shed ({res.reason})")
        check(res.health is not None and res.health.ok,
              f"served[{req.tag}]: health {res.health}")
        check(resolved[req.rid] == 1, f"served[{req.tag}]: resolved "
              f"{resolved[req.rid]} times")
        a, b, x = (np.asarray(v, np.float64)
                   for v in (req.a, req.b, res.x))
        ninf = lambda M: np.linalg.norm(M, ord=np.inf)  # noqa: E731
        be = ninf(a @ x - b) / (ninf(a) * ninf(x) + ninf(b))
        x_ref = np.asarray(jnp.linalg.solve(jnp.asarray(req.a),
                                            jnp.asarray(req.b)),
                           np.float64)
        fwd = ninf(x - x_ref) / ninf(x_ref)
        cond = float(np.linalg.cond(a, np.inf))
        bound, fwd_bound = C_BOUND * n * EPS, 2 * n * EPS * cond
        emit(step="served", tag=req.tag, routine=req.routine,
             tenant=req.tenant, n=n, bucket=res.bucket, rung=res.rung,
             latency_s=res.wall_s, backward_error=be, bound=bound,
             bound_def=BOUND_DEF, err_vs_jnp_solve=fwd,
             err_bound=fwd_bound, err_bound_def="2*n*eps*cond_inf",
             cond_inf=cond)
        check(be <= bound, f"served[{req.tag}]: backward error {be}")
        check(fwd <= fwd_bound, f"served[{req.tag}]: {fwd} from "
              f"jnp.linalg.solve, bound {fwd_bound}")
    warm_err = sum(v for lk, v in
                   obs.metrics.counters_named("serve.warmup_run").items()
                   if ("outcome", "error") in lk)
    shed = obs.count_total("serve.shed")
    emit(step="served_summary", requests=len(reqs), wall_s=wall_s,
         resolved_once=sum(1 for c in resolved.values() if c == 1),
         shed=shed, warmup_errors=warm_err)
    check(len(resolved) == len(reqs) and shed == 0 and warm_err == 0,
          "served: a request was shed, lost, or a warmup failed")


def phase_one_chip(args, devices, on_tpu: bool) -> None:
    g = slate.Grid(1, 1, devices=devices[:1])
    both_solves(args, g, lambda routine, A, B:
                step_solve(routine, A, B, on_tpu))
    step_served(args.seed, small=args.n < 4096)


# --------------------------------------------------------------------------
# four chips: posv and gesv on a 2×2 grid
# --------------------------------------------------------------------------

def spread(name: str, arr) -> dict:
    """Proof that ``arr`` is spread evenly over the four devices."""
    shard_bytes = [s.data.nbytes for s in arr.addressable_shards]
    check(len(arr.sharding.device_set) == 4
          and shard_bytes == [arr.nbytes // 4] * 4,
          f"{name}: shards {shard_bytes} of {arr.nbytes} bytes on "
          f"{len(arr.sharding.device_set)} devices")
    return {"devices": 4, "shard_bytes": shard_bytes[0],
            "bytes": arr.nbytes}


def chunk_text(routine: str, A) -> str:
    """Compiled text of the first super-step chunk program, lowered
    exactly as the driver calls it."""
    tier, depth = tune.driver_config(routine, A.n, None)
    check(depth == 0, "the smoke covers the default sequential chunks")
    kt = min(A.mt, A.nt)
    klen = min(superstep_chunk(kt, 2, None), kt)
    info0 = jnp.zeros((), jnp.int32)
    if routine == "potrf":
        low = potrf._potrf_chunk_jit.lower(A, info0, 0, klen, tier=tier)
    else:
        piv0 = jnp.zeros((kt, A.nb), jnp.int32)
        low = getrf._getrf_chunk_jit.lower(A, piv0, info0, 0, klen,
                                           tier=tier)
    return low.compile().as_text()


def step_solve_2x2(routine: str, A, B) -> None:
    n, nb = A.n, A.nb
    out, info, first_s, second_s = solve_twice(routine, A, B)
    X, F = out[0], out[1]
    fact = "potrf" if routine == "posv" else "getrf"
    phases = chunk_phase(fact + ".chunk")
    check(phases == ["spmd_chunk"], f"{routine}: driver took {phases}, "
          "not the chunked super-steps")
    placement = {name: spread(f"{routine}.{name}", M.data)
                 for name, M in (("A", A), ("factor", F), ("X", X))}
    text = chunk_text(fact, A.materialize())
    coll = collectives_in(text)
    check(coll["all-gather"] + coll["all-reduce"] > 0,
          f"{routine}: no collective in the compiled chunk program")
    be = backward_error_plain(dense_of(A), X.to_dense(), B.to_dense())
    bound = C_BOUND * n * EPS
    emit(step=routine, n=n, nb=nb, nrhs=NRHS, grid="2x2",
         first_call_s=first_s, second_call_s=second_s, info=info,
         path=f"{fact}:spmd_chunk", placement=placement,
         chunk_collectives=coll,
         chunk_tpu_custom_calls=text.count("tpu_custom_call"),
         backward_error_plain=be, bound=bound, bound_def=BOUND_DEF)
    check(math.isfinite(be) and be <= bound,
          f"{routine}: plain backward error {be} > {bound}")


def phase_four_chips(args, devices, on_tpu: bool) -> None:
    g = slate.Grid(2, 2, devices=devices[:4])
    emit(step="grid", grid="2x2", mesh=[
        {"mesh_rc": [r, c], "id": d.id,
         "coords": list(getattr(d, "coords", ()) or ()),
         "core_on_chip": getattr(d, "core_on_chip", None)}
        for r in range(2) for c in range(2)
        for d in [g.mesh.devices[r, c]]])
    both_solves(args, g, step_solve_2x2)
    stats = [d.memory_stats() for d in g.devices]
    peaks = [s["peak_bytes_in_use"] if s else None for s in stats]
    emit(step="memory", peak_bytes_in_use={
        str(d.id): p for d, p in zip(g.devices, peaks)})
    if on_tpu:      # same order on every device: nothing parked on one
        check(min(peaks) * 4 >= max(peaks),
              f"peak bytes uneven across devices: {peaks}")


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the 2x2 phase on four chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=16384,
                    help="rehearsal only; the chip run uses the default")
    ap.add_argument("--nb", type=int, default=1024,
                    help="rehearsal only; the chip run uses the default")
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="run the phases without a TPU, then FAIL: "
                         'prints {"ok": false, ...} and exits 1')
    args = ap.parse_args(argv)

    jax_cache = place_jax_compile_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_tpu = device["platform"] == "tpu"
    right = on_tpu and device["count"] == args.chips
    if not right and not args.rehearse_on_cpu:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), jax found "
              f"{device}", file=sys.stderr)
        return 1
    check(len(devices) >= args.chips,
          f"rehearsal needs {args.chips} devices, found {len(devices)}")

    obs.metrics_on()
    emit(step="device", **device, chips_wanted=args.chips,
         jax=jax.__version__, jax_cache_dir=jax_cache, seed=args.seed)
    t0 = time.perf_counter()
    phase = phase_four_chips if args.chips == 4 else phase_one_chip
    phase(args, devices, on_tpu)
    corrupt = obs.count_total("cache.corrupt")
    compiles = [h for h in obs.metrics.snapshot()["histograms"]
                if h["name"] == "jax.event_duration_s"
                and h["labels"].get("event", "").endswith(
                    "backend_compile_duration")]
    emit(step="done", wall_s=time.perf_counter() - t0,
         cache_corrupt=corrupt,
         backend_compiles=sum(h["count"] for h in compiles),
         backend_compile_s=sum(h["sum"] for h in compiles),
         jax_cache_hits=obs.metrics.counter_value(
             "jax.events", event="/jax/compilation_cache/cache_hits"),
         jax_cache_misses=obs.metrics.counter_value(
             "jax.events", event="/jax/compilation_cache/cache_misses"))
    check(corrupt == 0, f"cache.corrupt == {corrupt}")
    print(json.dumps({"ok": right, "device": device}), flush=True)
    return 0 if right else 1


if __name__ == "__main__":
    sys.exit(main())
