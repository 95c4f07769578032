"""LU: getrf (partial pivoting) / getrf_nopiv / getrf_tntpiv / getrs /
gesv (+ band gbtrf/gbtrs/gbsv).

Reference: src/getrf.cc:23-300 (panel on host + spin-barrier threads,
internal_getrf.cc:21-125, pivot exchange over a panel sub-communicator,
row swaps via MPI_Sendrecv in internal_swap.cc), src/getrf_nopiv.cc,
src/getrf_tntpiv.cc (CALU tournament), src/getrs.cc, src/gesv.cc.

TPU redesign — one jitted ``shard_map`` program per driver:

* **Panel**: under the row cap of one ``lu`` the tile column is
  all-gathered down mesh rows (internal_getrf.cc:56-67's panel
  sub-communicator) and *every chip factors it redundantly*
  (internal/tile_kernels.panel_lu_factor): no ThreadBarrier, no
  cross-rank argmax/bcast per column. Over the cap it is factored
  where its rows are stored (``_panel_stored_rows``): CALU's first
  round on each chip's own rows, only nb winner rows a chip cross p.

* **Row swaps**: LAPACK-style sequential swaps touch at most 2·nb rows
  per panel. Those candidate rows are gathered with a masked ``psum``
  down mesh rows, the swap sequence is resolved into a permutation on
  a content-index vector, and each chip rewrites only the local rows
  that changed — the TPU analog of internal_swap.cc:489-670's
  device-side swaps + MPI_Sendrecv, with latency O(1) collectives per
  panel instead of O(nb) exchanges.

* **Trailing update**: batched triangular solve on the U block-row +
  one einsum over local trailing tiles, exactly like potrf.

``getrf_tntpiv`` (CALU): the same driver — under the cap the
replicated panel *is* a degenerate tournament (every chip holds all
candidate rows already), so the plain partial-pivot panel gives
CALU's communication profile; over it the tournament runs for real,
on a grid without ever assembling the panel.

Pivots are returned as an int32 array ``piv[kt, nb]`` of global row
indices (LAPACK ipiv semantics, 0-based): at panel k, step j, row
``k·nb+j`` was swapped with ``piv[k, j]``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..cache.jitcache import cached_jit
from ..grid import AXIS_P, AXIS_Q
from ..matrix import Matrix, cdiv
from ..types import (Op, Uplo, Diag, Side, MethodLU, Option, get_option,
                     superstep_chunk)
from ..errors import slate_error_if
from ..internal import comm, masks
from ..internal.tile_kernels import panel_lu_factor, panel_lu_nopiv
from ..internal.masks import tile_diag_pad_identity
from ..internal.precision import resolve_tier, trailing_dot_kwargs
from .. import obs
from ..obs import timeline as tl
from ..runtime import dag
from ..utils import trace
from . import _superstep


# ---------------------------------------------------------------------------
# getrf — partial pivoting
# ---------------------------------------------------------------------------

def _getrf_native(A: Matrix, opts=None, overwrite_a: bool = False,
                  health: bool = False, checkpoint=None, _resume=None):
    """:func:`getrf` with the pivots in the form the factor produced
    them (ROADMAP D2a): ``(LU, piv, info)`` where ``piv`` is a
    :class:`PivotOrder` from the one-chip fast path (pivoting by index
    never makes a swap list) and the [kt, nb] int32 LAPACK pivots from
    every other program.  :func:`getrs` takes either, so a caller that
    only solves with the factors (``linalg/mixed.py``'s refinement,
    ``gecondest`` on the health path) never pays the host conversion
    nor, in every solve, the replay of its swap list (``_sim_perm``):
    an order is applied as one gather (``_apply_order_jit``).

    The public :func:`getrf`, further down beside
    :func:`pivot_order_to_ipiv`, is this function followed by that
    conversion, for the caller who asks for LAPACK pivots; its
    docstring describes the arguments, which are passed through as
    they are.

    Kept at this place and at this length on purpose: the fast core's
    persistent-cache key holds the line numbers of the kernel calls
    below (PERF.md section 7, fault 5), so a line added or taken away
    above them costs every checkout a cold compile of the 16k LU
    (two minutes on a v5e).  ``_gesv`` still chooses the fast path a
    second time by itself (fault 1, D2a's first half).
    """
    from ..robust import faults as _faults
    with trace.block("getrf", routine="getrf", m=A.m, n=A.n, nb=A.nb,
                     mt=A.mt, pad_rows=A.mt * A.nb - A.m) as top:
        # what the host does before the first launch, with the device
        # idle unless a caller queued work ahead
        with trace.block("getrf.prepare"):
            A = _faults.maybe_corrupt("getrf", A)
            A = A.materialize()
            g = A.grid
            kt = min(A.mt, A.nt)
            lcm_pq = g.p * g.q // math.gcd(g.p, g.q)
            from .. import tune
            tier, depth = tune.driver_config("getrf", A.n, opts)
            chunked = g.size > 1 and kt >= 2 * lcm_pq
            guard = _superstep.arm("getrf", A, opts, checkpoint, chunked)
        top.label(precision=tier, **_panel_labels(A, chunked, depth))
        Anorm = _superstep.norm_one(A, opts) if health else None
        if chunked:
            # chunked super-steps (same scheme as potrf): trailing
            # updates on a statically shrinking window; swaps still
            # span the full row (back-pivoting the stored L).
            # Option.Lookahead / Option.ChunkSize tune the granularity;
            # Option.PipelineDepth picks the software-pipelined chunk
            # body (panel k+1 gather in flight under step-k trailing
            # gemm) vs the strictly sequential one.
            S = superstep_chunk(kt, lcm_pq, opts)
            obs.count("getrf.path", 1, phase="spmd_chunk")
            piv0 = (jnp.arange(kt, dtype=jnp.int32)[:, None] * A.nb
                    + jnp.arange(A.nb, dtype=jnp.int32)[None, :])

            def step(data, carried, k0, klen, donate):
                Ak = A._replace(data=data)
                if depth > 0:
                    fn = (_getrf_pipe_chunk_jit_overwrite if donate
                          else _getrf_pipe_chunk_jit)
                    return fn(Ak, *carried, k0, klen, depth=depth,
                              tier=tier)
                fn = (_getrf_chunk_jit_overwrite if donate
                      else _getrf_chunk_jit)
                return fn(Ak, *carried, k0, klen, tier=tier)

            # a resumed run reproduces the uninterrupted result
            # bitwise, pivots included
            data, piv, info = _superstep.run_chunks(
                guard, A, step, (piv0, jnp.zeros((), jnp.int32)),
                ("piv", "info"), kt, S, overwrite_a, _resume)
        else:
            fm = (_fast_path_mode(A, "partial")
                  if (g.size == 1 and kt <= 64) else None)
            obs.count("getrf.path", 1, phase=(
                "one_program" if fm is None else "fast_path"))

            def launch(donate):
                if fm is not None:
                    fj = (_getrf_fast_jit_overwrite if donate
                          else _getrf_fast_jit)
                    with trace.block("getrf.chunk", phase="fast_path",
                                     k0=0, klen=kt):
                        data, order, info = fj(
                            A, interpret=(fm == "interpret"),
                            want_ipiv=False, fold=_fold_now(),
                            tier=tier)
                    # the order as the factor made it; LAPACK ipiv
                    # only in getrf(), for the caller who asks
                    return data, PivotOrder(order), info
                jit_fn = (_getrf_jit_overwrite if donate
                          else _getrf_jit)
                with trace.block("getrf.chunk", phase="one_program",
                                 k0=0, klen=kt):
                    return jit_fn(A, piv_mode="partial", tier=tier,
                                  depth=depth)

            data, piv, info = _superstep.run_one_program(
                guard, A, launch, kt, overwrite_a)
    LU = A._replace(data=data)
    if health:
        # info counts zero pivots (no single bad-tile coordinate);
        # rcond via gecondest
        from ..types import Norm
        from .condest import gecondest
        return LU, piv, _superstep.health(
            "getrf", info, Anorm, opts, "count",
            lambda anorm: gecondest(Norm.One, LU, piv, anorm, opts))
    return LU, piv, info


def getrf_resume(A: Matrix, opts=None, overwrite_a: bool = False,
                 health: bool = False, checkpoint=None):
    """Resume a checkpointed getrf after a preempt (robust.ckpt).

    Loads the latest valid checkpoint for the (A, opts) job —
    validating fingerprint, payload checksum, and step hash — and
    re-enters the step loop at the saved chunk boundary, producing
    results bitwise equal to an uninterrupted run, pivots included,
    on both the sequential and PipelineDepth paths.  When no valid
    checkpoint exists (never saved, corrupt → quarantined, stale
    fingerprint, different options) the call demotes to a from-scratch
    :func:`getrf` and the demotion lands in
    ``robust.ladder.demotion_log()``."""
    return _superstep.resume("getrf", getrf, A, opts,
                             overwrite_a=overwrite_a, health=health,
                             checkpoint=checkpoint)


def getrf_nopiv(A: Matrix, opts=None):
    """LU without pivoting (reference src/getrf_nopiv.cc)."""
    A = A.materialize()
    tier = resolve_tier(opts)
    with trace.block("getrf_nopiv", precision=tier):
        data, piv, info = _getrf_jit(A, piv_mode="none", tier=tier)
    return A._replace(data=data), info


def getrf_tntpiv(A: Matrix, opts=None):
    """CALU tournament-pivot LU (reference src/getrf_tntpiv.cc). The
    replicated panel is a collapsed tournament (all candidate rows are
    already on every chip); panels taller than the single-shot row cap
    run the real one: on a grid where the rows are stored, winners
    only crossing p (_panel_stored_rows), else _panel_lu_tournament."""
    return getrf(A, opts)


from ..internal.tile_kernels import LU_PANEL_MAX_ROWS as _LU_PANEL_MAX_ROWS


# ---------------------------------------------------------------------------
# single-device FAST path: pivoting-by-index with a Pallas panel kernel
# (reference internal_getrf.cc:21-125 / Tile_getrf.hh:161-300 — see
# internal/panel_plu.py for the kernel redesign rationale)
# ---------------------------------------------------------------------------

_FAST_W = 128            # subpanel width (= panel_plu.W)
_FAST_GROUP = 4          # panels per compaction group
# Largest n whose compaction may use the one-shot full-window
# ``jnp.take`` (a second window-sized temp, measured 2× faster than
# the chunked permute at 16k). Above it the column-chunked in-place
# form caps the temp at hw·_COMPACT_CB — the peak-memory property
# that admits the donated 45k-64k dense class into 16 GB HBM
# (VERDICT r3 #3). 24576 (not 32768) because BOTH the 2.4 GB window
# temp AND the donated factor must coexist with XLA workspace at the
# moment the gather runs: 32768² f32 is 4.3 GB of extra peak — the
# "32k memory cliff"; 24576² is 2.3 GB and measured safe.
# tests/test_getrf.py::test_fast_path_compaction_chunked covers the
# chunked leg so a future bump cannot silently reintroduce the
# window-sized temp at large n.
_COMPACT_TAKE_MAX_N = 24576
_COMPACT_CB = 2048       # chunked-compaction column-block width


def _fast_path_mode(A, piv_mode) -> str | None:
    """'tpu' / 'interpret' when the no-row-movement fast path applies.

    Requirements: partial pivoting, single device, f32, square with
    zero padding (m == n == kt·nb), nb a lane-tile multiple. Auto-on
    for TPU at n ≥ 8192, where it measures ~1.4× the dense path
    (9.4 vs 6.9 TF/s at n=16k); SLATE_LU_FAST=1 forces it anywhere
    (on CPU via Pallas interpret mode —
    tests/test_getrf.py::test_getrf_fast_path covers it that way),
    =0 disables.
    """
    import os
    flag = os.environ.get("SLATE_LU_FAST", "")
    if flag == "0":
        return None
    kt = min(A.mt, A.nt)
    mtl, ntl = A.data.shape[2], A.data.shape[3]
    exact = (piv_mode == "partial" and A.m == A.n
             and A.m == kt * A.nb and mtl * A.nb == A.m
             and ntl * A.nb == A.n and A.nb % _FAST_W == 0)
    if not exact or A.dtype not in (jnp.float32, jnp.dtype(jnp.float32)):
        return None
    on_tpu = A.grid.devices[0].platform == "tpu"
    if flag == "1":
        return "tpu" if on_tpu else "interpret"
    # upper cutoff: THIS tiled entry holds the input tiles, the dense
    # working array (one pass from them) and the output tiles (one pass
    # back) at once, ≈ 3× the matrix, so it is memory-safe only to ~32k
    # f32 on 16 GB HBM. The 45k class goes through getrf_dense_inplace:
    # the donated dense entry with column-chunked in-place compaction
    # (matrix 8.1 GB + ~1 GB temporaries; BASELINE.md round 4).
    return "tpu" if (on_tpu and 8192 <= A.n <= 32768) else None


def _getrf_fast_group_core(a, content, info, g0, gsz, nb,
                           interpret: bool, fold: bool = True,
                           tier=None):
    """One compaction group of the no-row-movement LU on a DENSE
    [n, n] array: ``gsz`` statically-unrolled panels + the group's
    in-place column-chunked compaction. Returns
    (a, content, o_g [gsz·nb] original row per elimination step,
    info). Shared by the tiled fast path (one fused program) and the
    donated per-group programs of :func:`getrf_dense_inplace`."""
    from ..internal.panel_plu import plu_panel
    n = a.shape[0]
    sb = nb // _FAST_W
    W = _FAST_W
    # (parameter layout is pinned row-major by _getrf_fast_group_jit —
    # without it XLA's layout assignment picks the transposed {0,1}
    # layout for the [n, n] parameter, inserting a matrix-sized
    # conversion copy and defeating donation: 19.6 GB peak at n=45056)
    # the whole body indexes `a` with ABSOLUTE coordinates — an
    # extracted trailing-window value (`aw = a[done:, done:]`) is a
    # materialized window-sized temp in every group past the first
    # (6.25 GB at n=45056), on top of the array itself
    done = g0 * nb
    hw = n - done
    gnb = gsz * nb
    ge = done + gnb                                  # group column end
    iota_hw = jnp.arange(hw, dtype=jnp.int32)
    act = jnp.ones(hw, a.dtype)
    upend = jnp.zeros((gnb, gnb), a.dtype)           # group-column U
    ordg = jnp.zeros(gnb, jnp.int32)

    # ---- group panel factorization: right-looking WITHIN the group --
    # (trailing right of the group is deferred to ONE exact-height
    # gemm after compaction — the per-panel full-width updates paid
    # ~(kk+1)·nb rows of zero-multiplier masked-height waste per panel
    # plus skinny-matmul inefficiency: ~124 ms of the 267 ms profile
    # at n=16384, ~21 ms of it pure waste; see BASELINE.md round 4)
    from ..internal.panel_plu import (H_MAX, fold_panel,
                                      plu_call_folded_block,
                                      unfold_panel)
    folded = fold and hw % 1024 == 0 and hw <= H_MAX
    Lf = hw // 8
    for kk in range(gsz):
        d_lo, d_hi = done + kk * nb, done + (kk + 1) * nb
        with jax.named_scope("panel"):
            ubuf = jnp.zeros((nb, nb), a.dtype)
            ordp = jnp.zeros(nb, jnp.int32)
            if folded:
                # ONE panel fold; the kernel addresses subpanel s of the
                # whole folded buffer by scalar-prefetched block index and
                # factors it IN PLACE (aliased) — no per-subpanel slice /
                # dynamic-update-slice traffic, and the intra-panel algebra
                # stays in folded coordinates (row i ↔ (i // Lf, i % Lf))
                pcf = fold_panel(a[done:, d_lo:d_hi], interpret)
                actf = act.reshape(8, Lf)
                for s in range(sb):
                    c0 = s * W
                    pcf, actf, piv_l, inf = plu_call_folded_block(
                        pcf, actf, s, interpret)
                    subf = pcf[:, c0:c0 + W, :]
                    piv_l = piv_l[0]
                    info = info + inf[0, 0].astype(jnp.int32)
                    ordp = ordp.at[c0:c0 + W].set(piv_l)
                    rem = nb - (s + 1) * W
                    if rem > 0:
                        # pivot-row extraction as one-hot MXU contractions
                        # (advanced indexing on the folded axes lowers to
                        # a while-loop gather — ~37 ms at n=16384)
                        fold_iota = (jnp.arange(8, dtype=jnp.int32)[:, None]
                                     * Lf
                                     + jnp.arange(Lf, dtype=jnp.int32)[None])
                        oh = (fold_iota[None] == piv_l[:, None, None]
                              ).astype(a.dtype)          # [W, 8, Lf]
                        lu11 = jnp.einsum("jsl,swl->jw", oh, subf)
                        brows = jnp.einsum("jsl,srl->jr", oh,
                                           pcf[:, c0 + W:, :])  # [W, rem]
                        u = lax.linalg.triangular_solve(
                            lu11, brows, left_side=True, lower=True,
                            unit_diagonal=True)
                        ubuf = ubuf.at[c0:c0 + W, c0 + W:].set(u)
                        lsubf = jnp.where(actf[:, None, :] > 0, subf,
                                          jnp.zeros_like(subf))
                        pcf = pcf.at[:, c0 + W:, :].add(
                            -jnp.einsum("swl,wr->srl", lsubf, u))
                act = actf.reshape(hw)
                pcols = unfold_panel(pcf, interpret)
            else:
                pcols = a[done:, d_lo:d_hi]              # [hw, nb]
                for s in range(sb):
                    c0 = s * W
                    sub = pcols[:, c0:c0 + W]
                    subf, piv_l, act, inf = plu_panel(sub, act, interpret,
                                                      fold=fold)
                    pcols = pcols.at[:, c0:c0 + W].set(subf)
                    ordp = ordp.at[c0:c0 + W].set(piv_l)
                    info = info + inf
                    rem = nb - (s + 1) * W
                    if rem > 0:
                        lu11 = jnp.take(subf, piv_l, axis=0)
                        brows = jnp.take(pcols[:, c0 + W:], piv_l,
                                         axis=0)         # [W, rem]
                        u = lax.linalg.triangular_solve(
                            lu11, brows, left_side=True, lower=True,
                            unit_diagonal=True)
                        ubuf = ubuf.at[c0:c0 + W, c0 + W:].set(u)
                        lsub = jnp.where((act > 0)[:, None], subf,
                                         jnp.zeros_like(subf))
                        pcols = pcols.at[:, c0 + W:].add(-(lsub @ u))
            ordg = ordg.at[d_lo - done:d_hi - done].set(ordp)
            upend = upend.at[d_lo - done:d_hi - done,
                             d_lo - done:d_hi - done].set(ubuf)
            a = a.at[done:, d_lo:d_hi].set(pcols)
        # trailing on the group's OWN remaining columns only
        if d_hi < ge:
            with jax.named_scope("trailing"):
                lu11n = jnp.take(pcols, ordp, axis=0)
                bright = jnp.take(a[done:, d_hi:ge], ordp, axis=0)
                un = lax.linalg.triangular_solve(
                    jnp.tril(lu11n, -1)
                    + jnp.eye(nb, dtype=a.dtype), bright,
                    left_side=True, lower=True, unit_diagonal=True)
                lk = jnp.where((act > 0)[:, None], pcols,
                               jnp.zeros_like(pcols))
                a = a.at[done:, d_hi:ge].add(
                    -jnp.matmul(lk, un, **trailing_dot_kwargs(tier, a.dtype)))
                upend = upend.at[d_lo - done:d_hi - done,
                                 d_hi - done:].set(un)

    with jax.named_scope("pivot_gather"):
        o_g = jnp.take(content[done:], ordg)
        # ---- compaction: finished rows to LAPACK order + U overlay ------
        rank = jnp.zeros(hw, jnp.int32).at[ordg].set(
            jnp.arange(gnb, dtype=jnp.int32))
        key = jnp.where(act > 0, gnb + iota_hw, rank)
        perm = jnp.argsort(key)
        # an argsort's indices are distinct and in [0, hw): said to the
        # gather, or it NaN-fills by a bounds check in a pass of its own
        inb = dict(mode="promise_in_bounds", unique_indices=True)
        if n <= _COMPACT_TAKE_MAX_N:
            # one whole-window gather: 2× faster than chunks at 16k
            a = a.at[done:].set(a.at[done + perm].get(**inb))
        else:
            # column-chunked permute (window + stored-L back-pivot): each
            # [hw, CB] block gathers and writes back in place, so the peak
            # temporary is hw·CB instead of a second matrix-sized window —
            # this is what admits the 45k-64k f32 class (VERDICT r3 #3)
            CB = _COMPACT_CB
            for c0 in range(0, n, CB):
                cw = min(CB, n - c0)
                a = a.at[done:, c0:c0 + cw].set(
                    a[done:, c0:c0 + cw].at[perm].get(**inb))
        content = content.at[done:].set(content[done:].at[perm].get(**inb))
        i_g = jnp.arange(gnb, dtype=jnp.int32)
        sub_end = (i_g // W + 1) * W                     # group cols
        colmask = i_g[None, :] >= sub_end[:, None]
        a = a.at[done:ge, done:ge].set(
            jnp.where(colmask, upend, a[done:ge, done:ge]))

    # ---- deferred cross-group trailing (exact shapes) ---------------
    # U block rows by blocked forward substitution on the compacted
    # pivot rows (stale right of ge by exactly this group's panels),
    # then ONE [hw-gnb, gnb] x [gnb, n-ge] gemm — no masked-height
    # waste, full-MXU-efficiency shapes
    if ge < n:
        with jax.named_scope("trailing"):
            ug = []
            for kk in range(gsz):
                r0 = done + kk * nb
                acc = a[r0:r0 + nb, ge:]
                for p in range(kk):
                    acc = acc - (a[r0:r0 + nb,
                                   done + p * nb:done + (p + 1) * nb]
                                 @ ug[p])
                lkk = a[r0:r0 + nb, done + kk * nb:done + (kk + 1) * nb]
                ug.append(lax.linalg.triangular_solve(
                    jnp.tril(lkk, -1) + jnp.eye(nb, dtype=a.dtype), acc,
                    left_side=True, lower=True, unit_diagonal=True))
            ugs = jnp.concatenate(ug, axis=0)            # [gnb, n-ge]
            a = a.at[ge:, ge:].add(
                -jnp.matmul(a[ge:, done:ge], ugs,
                            **trailing_dot_kwargs(tier, a.dtype)))
            a = a.at[done:ge, ge:].set(ugs)
    return a, content, o_g, info


def _fast_group_program(dev):
    """The per-group donated program for ``dev`` with PINNED row-major
    layouts: XLA's layout assignment otherwise gives the [n, n]
    parameter the transposed {0,1} layout (preferred by the row-gather
    compaction), which inserts a matrix-sized layout-conversion copy
    AND defeats donation — measured 19.6 GB peak at n=45056 vs ~9 GB
    pinned.

    ``cached_jit`` memoizes on (fn, options) and the layout Formats
    carry the device — so each device gets exactly one wrapper, and the
    compiled group programs participate in the on-disk executable
    store like every other driver program."""
    from jax.experimental.layout import Format, Layout
    sh = jax.sharding.SingleDeviceSharding(dev)
    f2 = Format(Layout((0, 1)), sh)
    f1 = Format(Layout((0,)), sh)
    f0 = Format(Layout(()), sh)
    return cached_jit(_getrf_fast_group_core,
                      routine="getrf.fast_group",
                      donate_argnums=(0, 1),
                      static_argnums=(3, 4, 5, 6, 7, 8),
                      in_shardings=(f2, f1, f0),
                      out_shardings=(f2, f1, f1, f0))


def _getrf_fast_group_jit(a, content, info, g0, gsz, nb, interpret,
                          fold, tier=None):
    return _fast_group_program(next(iter(a.devices())))(
        a, content, info, g0, gsz, nb, interpret, fold, tier)


def getrf_dense_inplace(a, nb: int = 1024, opts=None):
    """Partial-pivot LU of a dense LAPACK-layout f32 array IN PLACE
    (donated buffer): the 45k-class single-chip entry. The tiled fast
    path must convert storage (tiles ⇄ dense is a layout permutation —
    a full transient copy, which at an 8 GB matrix exceeds HBM); this
    entry skips the Matrix container entirely: the factorization runs
    as one donated jit program per compaction group and peak memory ≈
    the array + one [hw, 4096] permute block + the group U buffer.
    n must be a multiple of nb. Returns (LU_dense, piv [kt, nb]
    LAPACK ipiv — derived on host from the elimination order, off the
    device programs — and info). Reference analog: slate::getrf's
    in-place semantics on fromLAPACK-style storage (src/getrf.cc)."""
    slate_error_if(a.ndim != 2 or a.shape[0] != a.shape[1],
                   "getrf_dense_inplace needs a square 2-D array")
    slate_error_if(not isinstance(a, jax.Array)
                   or a.dtype != jnp.float32,
                   "getrf_dense_inplace needs an f32 jax array "
                   "(donated device buffer)")
    n = a.shape[0]
    slate_error_if(n % nb != 0,
                   "getrf_dense_inplace: n must be a multiple of nb")
    slate_error_if(nb % _FAST_W != 0,
                   f"getrf_dense_inplace: nb must be a multiple of "
                   f"{_FAST_W}")
    kt = n // nb
    content = jnp.arange(n, dtype=jnp.int32)
    info = jnp.zeros((), jnp.int32)
    tier = resolve_tier(opts)
    o_parts = []
    with trace.block("getrf_dense_inplace", routine="getrf",
                     m=n, n=n, nb=nb, precision=tier):
        for g0 in range(0, kt, _FAST_GROUP):
            gsz = min(_FAST_GROUP, kt - g0)
            with trace.block("getrf.dense_group", phase="dense_group",
                             k0=g0, gcount=gsz):
                a, content, o_g, info = _getrf_fast_group_jit(
                    a, content, info, g0=g0, gsz=gsz, nb=nb,
                    interpret=False, fold=_fold_now(), tier=tier)
            o_parts.append(o_g)
    order = jnp.concatenate(o_parts).reshape(kt, nb)
    return a, pivot_order_to_ipiv(order), info


def _getrf_fast_core(A, interpret: bool, want_ipiv: bool = True,
                     fold: bool = True, tier=None):
    """No-row-movement blocked LU (single device, square, f32).

    Pivoting by index: subpanels are factored in place by the Pallas
    kernel (internal/panel_plu.py) with an active-row mask instead of
    row swaps; U block-rows are built from one nb-row gather + one
    unit-lower solve per panel and parked in a per-group buffer; every
    ``_FAST_GROUP`` panels one row gather compacts the finished rows
    into LAPACK order and overlays the parked U. Panels are statically
    unrolled per group (the fori formulation profiled at ~40% extra MXU
    flops in masked full-width trailing plus ~70 ms of unfused slice
    copies). This replaces XLA `lu`'s ~6 µs/column latency floor and the
    ~10.6 ms/panel swap gathers of the dense path (BASELINE.md).
    """
    from ..matrix import bc_from_tiles
    nb, n, kt = A.nb, A.n, A.n // A.nb
    # dense [n, n] working array, ONE pass from the stored tiles and one
    # back: with a sublane group's 8 rows an axis of their own each copy
    # moves whole [8, nb] runs; as [kt, nb, kt, nb] XLA copies twice
    a = (A.data[0, 0].reshape(kt, kt, nb // 8, 8, nb)
         .transpose(0, 2, 3, 1, 4).reshape(n, n))
    content, info = jnp.arange(n, dtype=jnp.int32), jnp.zeros((), jnp.int32)
    o_parts = []         # original row id per elimination step
    for g0 in range(0, kt, _FAST_GROUP):
        gsz = min(_FAST_GROUP, kt - g0)
        a, content, o_g, info = _getrf_fast_group_core(
            a, content, info, g0, gsz, nb, interpret, fold, tier)
        o_parts.append(o_g)

    # ---- pivots -----------------------------------------------------
    o_all = jnp.concatenate(o_parts)                     # [n]
    if want_ipiv:
        # LAPACK ipiv via an O(n) sequential swap simulation ON DEVICE
        # — kept for jit-composable callers; the public getrf/gesv path
        # passes want_ipiv=False and converts the elimination order on
        # the host instead (runtime.order_to_ipiv, VERDICT r3 #2: n
        # dispatch-serial fori steps do not belong in the factor
        # program)
        def sim(j, carry):
            lcontent, llocof, ipiv = carry
            o = o_all[j]
            loc = llocof[o]
            ipiv = ipiv.at[j].set(loc)
            cj = lcontent[j]
            lcontent = lcontent.at[j].set(o).at[loc].set(cj)
            llocof = llocof.at[o].set(j).at[cj].set(loc)
            return lcontent, llocof, ipiv

        ids = jnp.arange(n, dtype=jnp.int32)
        _, _, ipiv = lax.fori_loop(0, n, sim,
                                   (ids, ids, jnp.zeros(n, jnp.int32)))
        piv = ipiv.reshape(kt, nb)
    else:
        # elimination order: piv[k, j] = ORIGINAL row eliminated at
        # step k·nb+j (wrap in PivotOrder before handing to getrs)
        piv = o_all.reshape(kt, nb)
    tiles = (a.reshape(kt, nb // 8, 8, kt, nb)
             .transpose(0, 3, 1, 2, 4).reshape(kt, kt, nb, nb))
    return bc_from_tiles(tiles, 1, 1), piv, info


_getrf_fast_jit = cached_jit(
    _getrf_fast_core, routine="getrf.fast",
    static_argnames=("interpret", "want_ipiv", "fold", "tier"))
_getrf_fast_jit_overwrite = cached_jit(
    _getrf_fast_core, routine="getrf.fast.overwrite", donate_argnums=0,
    static_argnames=("interpret", "want_ipiv", "fold", "tier"))


def _fold_now() -> bool:
    """SLATE_LU_FOLD read at CALL time and passed as a static jit arg
    — a trace-time env read would be silently baked into the cached
    executable (review r4)."""
    from ..internal.panel_plu import _fold_enabled
    return _fold_enabled()


def _panel_max_rows(platform: str) -> int | None:
    """Rows one single-shot ``lax.linalg.lu`` panel may have on
    ``platform``: a TPU's scoped vmem caps it (tile_kernels.
    LU_PANEL_MAX_ROWS), nothing else does. A taller panel takes the
    tournament (CALU) form of ``panel_lu_factor``."""
    return _LU_PANEL_MAX_ROWS if platform == "tpu" else None


def _panel_form(M: int, cap: int | None, depth: int = 0) -> str:
    """``stored``: the panel is over the row cap, so it takes the
    tournament, and the sequential chunk core runs it on the rows each
    device stores (``_panel_stored_rows``); ``gathered``: the [M, nb]
    panel is assembled on every device (under the cap, or the
    pipelined core, whose ring buffer holds it)."""
    return ("stored" if cap is not None and M > cap and depth == 0
            else "gathered")


def _panel_labels(A, chunked: bool, depth: int) -> dict:
    """Labels of a chunked factorization's ``getrf`` span, none
    otherwise: which pivoting the row cap chose for the panel's height,
    that height, where the panel is factored (``_panel_form``) and the
    bytes of panel rows each device receives over p in the
    factorization: every step p·nb winner rows on the stored form, the
    whole [M, nb] panel on the gathered one; the bytes also go to the
    ``getrf.panel_gather_bytes`` counter."""
    if not chunked:
        return {}
    g = A.grid
    M = A.data.shape[2] * g.p * A.nb
    cap = _panel_max_rows(g.devices[0].platform)
    form = _panel_form(M, cap, depth)
    received = (min(A.mt, A.nt) * (g.p * A.nb if form == "stored" else M)
                * A.nb * jnp.dtype(A.dtype).itemsize)
    obs.count("getrf.panel_gather_bytes", received)
    return {"pivoting": ("tournament" if cap is not None and M > cap
                         else "partial"),
            "panel_rows": M, "panel_form": form,
            "panel_gather_bytes": received}


class PivotOrder(NamedTuple):
    """Pivots as an ELIMINATION ORDER instead of a LAPACK swap list:
    ``order[k, j]`` = original row eliminated at step k·nb+j. The LU
    fast path's native output (pivoting by index never materializes
    swaps), accepted by :func:`getrs` — applying P·B is then ONE
    gather, with no O(n) sequential swap simulation on either side.
    Convert with :func:`pivot_order_to_ipiv` when LAPACK ipiv is
    required (compat APIs)."""
    order: jax.Array        # [kt, nb] int32


def pivot_order_to_ipiv(order) -> jnp.ndarray:
    """Elimination order → LAPACK ipiv [kt, nb] (host O(n) chain
    conversion — runtime.order_to_ipiv; same values as the device swap
    simulation)."""
    from .. import runtime as _rt
    import numpy as _np
    arr = order.order if isinstance(order, PivotOrder) else order
    kt, nb = arr.shape
    # the one blocking device→host read of the gesv path
    ipiv = obs.sync_read("gesv.order_to_ipiv",
                         lambda o: _rt.order_to_ipiv(_np.asarray(o)), arr)
    return jnp.asarray(ipiv, jnp.int32).reshape(kt, nb)


def getrf(A: Matrix, opts=None, overwrite_a: bool = False,
          health: bool = False, checkpoint=None, _resume=None):
    """LU with partial pivoting: P·A = L·U (reference src/getrf.cc).

    Returns ``(LU, piv, info)``: LU holds unit-lower L below the
    diagonal and U on/above (LAPACK layout); piv is [kt, nb] int32
    global-row pivots; info = number of zero pivots (0 ⇒ nonsingular).

    ``overwrite_a=True`` donates A's device buffer to the factors
    (reference in-place semantics); A must not be used afterwards.

    ``health=True`` swaps the info scalar for a
    :class:`~slate_tpu.robust.guards.HealthReport` — same info value
    plus an rcond estimate via ``gecondest`` (host-synced; opt-in).

    ``checkpoint`` controls factorization-state checkpointing on the
    chunked multi-device path (robust.ckpt, docs/robustness.md
    "Checkpoint & resume"): ``None``/``True`` follow the
    ``SLATE_TPU_CKPT_DIR`` arming (off-by-default passthrough),
    ``False`` disables for this call, an int sets the save stride in
    chunks.  Saves offload asynchronously and never block the next
    trailing update; :func:`getrf_resume` picks a killed run back up
    bitwise-identically.  ``_resume`` is the internal restart state
    (use :func:`getrf_resume`).

    The one-chip fast path factors by index and has no swap list: its
    elimination order is turned into LAPACK pivots here, on the host
    (one blocking read).  A caller that only hands the pivots back to
    :func:`getrs` takes :func:`_getrf_native` and skips that.
    """
    LU, piv, info = _getrf_native(A, opts, overwrite_a, health,
                                  checkpoint, _resume)
    if isinstance(piv, PivotOrder):
        piv = pivot_order_to_ipiv(piv)
    return LU, piv, info


def _getrf_dense_1dev(A, piv_mode, tier=None):
    """Single-device fast path: exact-shape unrolled blocked LU on the
    dense (padded) matrix. Panels are true [rem, nb] slices handed to
    XLA's native pivoted LU; row swaps are one gather per panel. The
    SPMD path's uniform full-height panels + candidate-row psum swaps
    exist only to keep every mesh step identical — with one device the
    exact shapes are ~3x faster (v5e, n=8192). Same pivot/info
    semantics (piv[k, j] = global row swapped with row k·nb+j)."""
    from ..matrix import tiles_to_dense, dense_to_tiles, bc_from_tiles
    from ..internal.tile_kernels import lu_nopiv_block, _factor_dtype
    nb = A.nb
    m, n = A.m, A.n
    kt = min(A.mt, A.nt)
    mtl, ntl = A.data.shape[2], A.data.shape[3]
    Mp, Np = mtl * nb, ntl * nb
    fd = _factor_dtype(A.dtype)

    a = tiles_to_dense(A.data[0, 0], Mp, Np)
    info = jnp.zeros((), jnp.int32)
    pk = trailing_dot_kwargs(tier, A.dtype)
    pivs = []
    if piv_mode == "partial":
        # Panels are sliced to their REAL rows/columns (static shapes —
        # the luxury of the unrolled path), so padding never enters the
        # pivot search. The SPMD path must instead scrub+identity-pad
        # uniform full tiles every step (masks.tile_diag_pad_identity).
        cap = _panel_max_rows(A.grid.devices[0].platform)
        for k in range(kt):
            r0 = k * nb
            w = min(nb, n - r0)          # real panel width
            h = m - r0                   # real panel height
            kw = min(h, w)               # pivots this panel
            pan = a[r0:m, r0:r0 + w]
            if cap is not None and h > cap:
                # taller than XLA's single-shot lu row cap: chunked
                # CALU tournament panel (same kernel the SPMD path
                # uses), pivots resolved to a permutation locally.
                lu, piv_l, _ = panel_lu_factor(pan, 0, h, max_rows=cap)
                perm0 = jnp.arange(h, dtype=jnp.int32)

                def _sim(j, prm, piv_l=piv_l):
                    b = piv_l[j]
                    pa, pb = prm[j], prm[b]
                    return prm.at[j].set(pb).at[b].set(pa)

                perm = lax.fori_loop(0, kw, _sim, perm0)
            else:
                lu, piv_l, perm = lax.linalg.lu(pan.astype(fd))
            lu = lu.astype(a.dtype)
            a = a.at[r0:m, r0:r0 + w].set(lu)
            if r0 > 0:   # swap rows in the already-factored left part
                a = a.at[r0:m, :r0].set(jnp.take(a[r0:m, :r0], perm,
                                                 axis=0))
            piv_k = piv_l[:kw].astype(jnp.int32) + jnp.int32(r0)
            if kw < nb:  # padded pivot slots self-swap
                piv_k = jnp.concatenate(
                    [piv_k, r0 + jnp.arange(kw, nb, dtype=jnp.int32)])
            pivs.append(piv_k)
            dg = jnp.diagonal(lu)[:kw]
            info = info + jnp.sum(dg == 0).astype(jnp.int32)
            if r0 + w < n:
                right = jnp.take(a[r0:m, r0 + w:n], perm, axis=0)
                urow = lax.linalg.triangular_solve(
                    jnp.tril(lu[:kw, :kw], -1)
                    + jnp.eye(kw, dtype=a.dtype),
                    right[:kw], left_side=True, lower=True,
                    unit_diagonal=True)
                a = a.at[r0:r0 + kw, r0 + w:n].set(urow)
                if r0 + kw < m:
                    trail = right[kw:] - jnp.matmul(lu[kw:, :kw], urow,
                                                    **pk)
                    a = a.at[r0 + kw:m, r0 + w:n].set(trail)
    else:
        if kt * nb > min(m, n):
            # no pivoting → a padded-diagonal identity can't migrate;
            # same trick as the SPMD path (masks.tile_diag_pad_identity)
            pad = jnp.arange(min(m, n), min(kt * nb, Mp, Np))
            a = a.at[pad, pad].set(1.0)
        for k in range(kt):
            r0 = k * nb
            blk, info_k = lu_nopiv_block(a[r0:r0 + nb, r0:r0 + nb])
            info = info + info_k
            u11 = jnp.triu(blk)
            safe_u = u11 + jnp.diag(jnp.where(
                jnp.diagonal(u11) == 0, jnp.ones(nb, u11.dtype),
                jnp.zeros(nb, u11.dtype)))
            a = a.at[r0:r0 + nb, r0:r0 + nb].set(blk)
            pivs.append(r0 + jnp.arange(nb, dtype=jnp.int32))
            if r0 + nb < Mp:
                l21 = lax.linalg.triangular_solve(
                    safe_u, a[r0 + nb:, r0:r0 + nb], left_side=False,
                    lower=False)
                a = a.at[r0 + nb:, r0:r0 + nb].set(l21)
            if r0 + nb < Np:
                urow = lax.linalg.triangular_solve(
                    jnp.tril(blk, -1) + jnp.eye(nb, dtype=a.dtype),
                    a[r0:r0 + nb, r0 + nb:], left_side=True, lower=True,
                    unit_diagonal=True)
                a = a.at[r0:r0 + nb, r0 + nb:].set(urow)
                if r0 + nb < Mp:
                    trail = a[r0 + nb:, r0 + nb:] - jnp.matmul(
                        a[r0 + nb:, r0:r0 + nb], urow, **pk)
                    a = a.at[r0 + nb:, r0 + nb:].set(trail)
    piv = jnp.stack(pivs) if pivs else jnp.zeros((0, nb), jnp.int32)
    tiles = dense_to_tiles(a, nb, mtl, ntl)
    return bc_from_tiles(tiles, 1, 1), piv, info


def _getrf_core(A, piv_mode, tier=None, depth=0):
    g = A.grid
    p, q, nb = g.p, g.q, A.nb
    m, n = A.m, A.n
    mt, nt = A.mt, A.nt
    kt = min(mt, nt)
    mtl, ntl = A.data.shape[2], A.data.shape[3]
    mt_p = mtl * p
    M = mt_p * nb                     # padded global rows
    pk = trailing_dot_kwargs(tier, A.dtype)

    # Dense-path gate: the unrolled program loses to the uniform
    # fori_loop past ~64 block columns (same trade as potrf). Panels
    # taller than XLA's single-shot lu row cap run the chunked CALU
    # tournament inside the dense path (measured 2.4x over the SPMD
    # path at n=16k on one chip).
    if g.size == 1 and kt <= 64:
        return _getrf_dense_1dev(A, piv_mode, tier)
    if piv_mode == "partial":
        # the uniform SPMD program is the k0=0, klen=kt chunk
        piv0 = (jnp.arange(kt, dtype=jnp.int32)[:, None] * nb
                + jnp.arange(nb, dtype=jnp.int32)[None, :])
        if g.size > 1 and depth > 0:
            # software-pipelined lookahead loop (Option.PipelineDepth)
            data, piv, info = _getrf_pipe_chunk_core(
                A, piv0, jnp.zeros((), jnp.int32), 0, kt, depth=depth,
                tier=tier)
            return data, piv, info
        data, piv, info = _getrf_chunk_core(
            A, piv0, jnp.zeros((), jnp.int32), 0, kt, tier=tier)
        return data, piv, info

    def body(a):
        a = a[0, 0]
        r, c = comm.coords()
        gi = masks.local_tile_rows(mtl, p)     # [mtl]
        gj = masks.local_tile_cols(ntl, q)     # [ntl]
        # global row index of each local (tile-slot, in-tile-row):
        t_local = (gi[:, None] * nb + jnp.arange(nb)[None, :])  # [mtl, nb]

        def step(k, carry):
            a, pivots, info = carry

            # ---- panel: gather column k, factor redundantly --------
            pcol = lax.dynamic_index_in_dim(a, k // q, axis=1,
                                            keepdims=False)  # [mtl,nb,nb]
            # identity on the padded diagonal so padding self-pivots
            diag_slot = k // p
            fixed = tile_diag_pad_identity(
                lax.dynamic_index_in_dim(pcol, diag_slot, axis=0,
                                         keepdims=False), k, m, nb, n)
            pcol = jnp.where(
                (gi == k)[:, None, None],
                lax.dynamic_update_index_in_dim(pcol, fixed, diag_slot,
                                                axis=0), pcol)
            full = comm.allgather_panel_rows(pcol, p, k % q)  # [mt_p,nb,nb]
            panel2d = full.reshape(M, nb)

            # only the no-pivot mode reaches this body (partial
            # pivoting delegates to _getrf_chunk_jit above)
            panel2d, info_k = panel_lu_nopiv(panel2d, k * nb, m)
            piv_k = k * nb + jnp.arange(nb, dtype=jnp.int32)
            info = info + info_k
            pivots = pivots.at[k].set(piv_k)
            ptiles = panel2d.reshape(mt_p, nb, nb)

            # ---- write the factored panel back (owner column) ------
            newcol = jnp.take(ptiles, gi, axis=0)        # [mtl, nb, nb]
            a = jnp.where(
                c == k % q,
                lax.dynamic_update_index_in_dim(a, newcol, k // q, axis=1),
                a)

            # ---- U block-row: unit-lower solve on owner mesh row ---
            lkk = lax.dynamic_slice(panel2d, (k * nb, 0), (nb, nb))
            arow = lax.dynamic_index_in_dim(a, k // p, axis=0,
                                            keepdims=False)  # [ntl,nb,nb]
            solved = lax.linalg.triangular_solve(
                jnp.broadcast_to(lkk, (ntl, nb, nb)), arow,
                left_side=True, lower=True, unit_diagonal=True)
            right = (gj > k) & (gj < nt)
            urow = jnp.where(right[:, None, None], solved, arow)
            a = jnp.where(
                r == k % p,
                lax.dynamic_update_index_in_dim(a, urow, k // p, axis=0),
                a)
            urow_b = comm.bcast_from_row(
                jnp.where(right[:, None, None], urow, jnp.zeros_like(urow)),
                k % p)

            # ---- trailing gemm: A(i,j) −= L(i,k)·U(k,j) ------------
            lrows = jnp.take(ptiles, gi, axis=0)
            below = (gi > k) & (gi < mt)
            lrows = jnp.where(below[:, None, None], lrows,
                              jnp.zeros_like(lrows))
            upd = jnp.einsum("aik,bkj->abij", lrows, urow_b, **pk)
            return a - upd, pivots, info

        pivots0 = jnp.zeros((kt, nb), jnp.int32)
        a, pivots, info = lax.fori_loop(
            0, kt, step, (a, pivots0, jnp.zeros((), jnp.int32)))
        return a[None, None], pivots, info

    data, piv, info = jax.shard_map(
        body, mesh=g.mesh, in_specs=(P(AXIS_P, AXIS_Q),),
        out_specs=(P(AXIS_P, AXIS_Q), P(), P()), check_vma=False)(A.data)
    return data, piv, info


_getrf_jit = cached_jit(_getrf_core, routine="getrf",
                        static_argnames=("piv_mode", "tier", "depth"))
# in-place variant (donated A buffer) — see getrf(overwrite_a=True)
_getrf_jit_overwrite = cached_jit(_getrf_core, routine="getrf.overwrite",
                                  donate_argnums=0,
                                  static_argnames=("piv_mode", "tier",
                                                   "depth"))


def _getrf_chunk_core(A, pivots0, info0, k0, klen, win_hi=None,
                      swap_min=0, tier=None):
    """One SPMD chunk of partial-pivot LU: block columns [k0, k0+klen),
    trailing trsm/gemm restricted to the static window
    [k0//p:, k0//q : cdiv(win_hi, q)]. With the defaults
    (win_hi=None ⇒ nt, swap_min=0) row swaps span the full local
    stacks (the stored L is back-pivoted, reference getrf.cc); the
    superstep DAG instead passes win_hi=k0+klen, swap_min=k0 so the
    factor task touches ONLY its own chunk columns and the tailLA /
    tailRest / backpivot tasks own the rest (runtime/hosttask.py
    getrf_superstep_dag). ``k0`` must be a multiple of lcm(p, q).

    The panel's static height M chooses its form (``_panel_form``).
    Under the row cap, ``gathered``: column k crosses q and is
    all-gathered over p (scope ``panel_bcast``), every device factors
    the [M, nb] panel (``panel``). Over it, ``stored``: column k
    crosses q, the tournament's first round runs on each device's own
    rows, and ``panel_bcast`` carries over p only the p·nb winner rows,
    their ids and the diagonal tile (``_panel_stored_rows``); ``panel``
    is that round, the last one, and the local rows' L21."""
    g = A.grid
    p, q, nb = g.p, g.q, A.nb
    m, n = A.m, A.n
    mt, nt = A.mt, A.nt
    mtl, ntl = A.data.shape[2], A.data.shape[3]
    mt_p = mtl * p
    M = mt_p * nb
    panel_max_rows = _panel_max_rows(g.devices[0].platform)
    stored = _panel_form(M, panel_max_rows) == "stored"
    windowed = win_hi is not None
    whi = nt if win_hi is None else win_hi
    r0s, c0s = k0 // p, k0 // q
    c1s = ntl if win_hi is None else cdiv(win_hi, q)
    nsub = c1s - c0s
    pk = trailing_dot_kwargs(tier, A.dtype)

    def body(a, pivots0, info0):
        a = a[0, 0]
        r, c = comm.coords()
        gi = masks.local_tile_rows(mtl, p)
        gj = masks.local_tile_cols(ntl, q)
        gis, gjs = gi[r0s:], gj[c0s:c1s]
        t_local = (gi[:, None] * nb + jnp.arange(nb)[None, :])

        # slatetimeline device track (see linalg/potrf.py): barriers
        # fence the panel gather, the U-row bcast, and the trailing
        # gemm; absent from the traced program unless capture is on
        dev = r * q + c
        ndev = p * q

        def step(k, carry):
            a, pivots, info = carry
            a = tl.mark(a, "step", step=k, device=dev,
                        kind=tl.KIND_STEP, edge="b", routine="getrf",
                        ndev=ndev)
            # ---- panel: column k, gathered or where it is stored ---
            # (named scopes as in _potrf_chunk_core: benchmarks/
            # span_report.py reads the device trace by them)
            with jax.named_scope("panel_bcast"):
                pcol = lax.dynamic_index_in_dim(a, k // q, axis=1,
                                                keepdims=False)
                diag_slot = k // p
                fixed = tile_diag_pad_identity(
                    lax.dynamic_index_in_dim(pcol, diag_slot, axis=0,
                                             keepdims=False), k, m, nb, n)
                pcol = jnp.where(
                    (gi == k)[:, None, None],
                    lax.dynamic_update_index_in_dim(pcol, fixed, diag_slot,
                                                    axis=0), pcol)
            pcol = tl.mark(pcol, "panel_bcast", step=k, device=dev,
                           kind=tl.KIND_COLLECTIVE, edge="b",
                           routine="getrf", ndev=ndev)
            if stored:
                # column k crosses q, then the tournament runs on the
                # rows each device stores: only winners cross p
                with jax.named_scope("panel_bcast"):
                    pcol = comm.bcast_from_col(pcol, k % q)
                pcol = tl.mark(pcol, "panel_bcast", step=k, device=dev,
                               kind=tl.KIND_COLLECTIVE, edge="e",
                               routine="getrf", ndev=ndev)
                # lkk: the factored diagonal block, on every device;
                # the local slots are the L rows `trailing` multiplies
                newcol, lkk, piv_k, info_k = _panel_stored_rows(
                    pcol, k, t_local, m, p, panel_max_rows)
                lrows = newcol[r0s:]
            else:
                # the gathered panel, op for op as it was before the
                # stored form (tests/test_getrf.py pins its text)
                with jax.named_scope("panel_bcast"):
                    full = comm.allgather_panel_rows(pcol, p, k % q)
                full = tl.mark(full, "panel_bcast", step=k, device=dev,
                               kind=tl.KIND_COLLECTIVE, edge="e",
                               routine="getrf", ndev=ndev)
                with jax.named_scope("panel"):
                    panel2d, piv_k, info_k = panel_lu_factor(
                        full.reshape(M, nb), k * nb, m,
                        max_rows=panel_max_rows)
            with jax.named_scope("panel"):
                info = info + info_k
                pivots = pivots.at[k].set(piv_k)
                if not stored:
                    ptiles = panel2d.reshape(mt_p, nb, nb)
                    newcol = jnp.take(ptiles, gi, axis=0)
                a = jnp.where(
                    c == k % q,
                    lax.dynamic_update_index_in_dim(a, newcol, k // q,
                                                    axis=1), a)
            with jax.named_scope("row_swap"):
                a = _swap_rows_local(a, piv_k, k * nb, t_local, nb, p, q,
                                     exclude_col=k,
                                     min_col=swap_min if windowed else 0,
                                     max_col=win_hi)

            # ---- U block-row solve, window columns only ------------
            with jax.named_scope("diag_solve"):
                if not stored:
                    lkk = lax.dynamic_slice(panel2d, (k * nb, 0), (nb, nb))
                arow = lax.dynamic_index_in_dim(a, k // p, axis=0,
                                                keepdims=False)[c0s:c1s]
                solved = lax.linalg.triangular_solve(
                    jnp.broadcast_to(lkk, (nsub, nb, nb)), arow,
                    left_side=True, lower=True, unit_diagonal=True)
                right = (gjs > k) & (gjs < min(nt, whi))
                urow = jnp.where(right[:, None, None], solved, arow)
                a = jnp.where(
                    r == k % p,
                    lax.dynamic_update_index_in_dim(
                        a, a[k // p].at[c0s:c1s].set(urow), k // p,
                        axis=0), a)
                urow_b = comm.bcast_from_row(
                    jnp.where(right[:, None, None], urow,
                              jnp.zeros_like(urow)), k % p)

            # ---- trailing gemm on the window -----------------------
            with jax.named_scope("trailing"):
                if not stored:
                    lrows = jnp.take(ptiles, gis, axis=0)
                below = (gis > k) & (gis < mt)
                lrows = jnp.where(below[:, None, None], lrows,
                                  jnp.zeros_like(lrows))
            lrows = tl.mark(lrows, "trailing", step=k, device=dev,
                            kind=tl.KIND_COMPUTE, edge="b",
                            routine="getrf", ndev=ndev)
            with jax.named_scope("trailing"):
                upd = jnp.einsum("aik,bkj->abij", lrows, urow_b, **pk)
                sub = a[r0s:, c0s:c1s] - upd
                a = a.at[r0s:, c0s:c1s].set(sub)
            a = tl.mark(a, "trailing", step=k, device=dev,
                        kind=tl.KIND_COMPUTE, edge="e", routine="getrf",
                        ndev=ndev)
            a = tl.mark(a, "step", step=k, device=dev,
                        kind=tl.KIND_STEP, edge="e", routine="getrf",
                        ndev=ndev)
            return a, pivots, info

        a, pivots, info = lax.fori_loop(
            k0, k0 + klen, step, (a, pivots0, info0))
        return a[None, None], pivots, info

    return jax.shard_map(
        body, mesh=g.mesh, in_specs=(P(AXIS_P, AXIS_Q), P(), P()),
        out_specs=(P(AXIS_P, AXIS_Q), P(), P()), check_vma=False)(
            A.data, pivots0, info0)


_getrf_chunk_jit = cached_jit(_getrf_chunk_core, routine="getrf.chunk",
                              static_argnames=("k0", "klen", "win_hi",
                                               "swap_min", "tier"))
_getrf_chunk_jit_overwrite = cached_jit(
    _getrf_chunk_core, routine="getrf.chunk.overwrite", donate_argnums=0,
    static_argnames=("k0", "klen", "win_hi", "swap_min", "tier"))


def _getrf_pipe_chunk_core(A, pivots0, info0, k0, klen, depth=1,
                           tier=None):
    """Software-pipelined LU chunk at lookahead depth ``depth``: the
    schedule comes from the DAG runtime (``runtime.dag.chunk_plan``),
    validated against the window task DAG and the bitwise per-column
    contract — including pivot order — before this trace consumes it
    (the lookahead of reference src/getrf.cc as a scheduler parameter;
    see :func:`_potrf_pipe_chunk_core` for the potrf twin).

    Steady-state iteration k (effective depth d = min(depth, klen-1)):

    1. ``consume``    — retire step k's gathered+factored panel from
       the ring (its all-gather went on the wire d iterations ago);
    2. ``swap_solve`` — step k's row swaps + U block-row solve, BOTH
       excluding tile columns [k+1, k+d): those lookahead columns were
       already swapped and solved column-locally when they advanced;
    3. ``advance``    — bring tile column k+d fully up to date: step
       k's gemm from the fresh U row, then for each buffered step
       s ∈ (k, k+d) the column-local triple (swap_s on this column
       only, single-column U solve from buffer s's diagonal block,
       gemm_s), in ascending s order — exactly the element order the
       sequential loop produces, so panel k+d's pivot search sees
       bit-identical values;
    4. ``factor``     — gather + factor panel k+d (d gathers in
       flight);
    5. ``trailing``   — step k's big gemm behind them (columns > k+d:
       the U row is already zero on [k+1, k+d) and column k+d is
       masked out).

    Depth 1 degenerates to the old hand-rolled one-deep pipeline (the
    exclusion windows are empty and the advance is the single
    fresh-U-row gemm). ``depth`` is static and part of the
    executable-cache key. No windowed (``win_hi``/``swap_min``)
    variant — the superstep DAG keeps the sequential cores.

    This core keeps the gathered [M, nb] panel whatever its height (its
    ring buffer holds ``panel2d``): over the row cap it still assembles
    the panel on every device and runs ``_panel_lu_tournament`` there,
    where ``_getrf_chunk_core`` factors it where its rows are stored
    (``_panel_form`` says ``gathered`` for any depth > 0)."""
    plan = dag.chunk_plan("getrf", k0, klen, depth)
    d = plan.d_eff
    g = A.grid
    p, q, nb = g.p, g.q, A.nb
    m, n = A.m, A.n
    mt, nt = A.mt, A.nt
    mtl, ntl = A.data.shape[2], A.data.shape[3]
    mt_p = mtl * p
    M = mt_p * nb
    panel_max_rows = _panel_max_rows(g.devices[0].platform)
    r0s, c0s = k0 // p, k0 // q
    nsub = ntl - c0s
    pk = trailing_dot_kwargs(tier, A.dtype)
    k_last = k0 + klen - 1
    ep0 = k0 + klen - d               # first epilogue step

    def body(a, pivots0, info0):
        a = a[0, 0]
        r, c = comm.coords()
        gi = masks.local_tile_rows(mtl, p)
        gj = masks.local_tile_cols(ntl, q)
        gis, gjs = gi[r0s:], gj[c0s:]
        t_local = (gi[:, None] * nb + jnp.arange(nb)[None, :])
        dev = r * q + c
        ndev = p * q

        def factor_panel(kk, a, pivots, info):
            """Gather + redundantly factor panel kk, write the factored
            column back, record its pivots, and push the gathered
            panel onto the ring."""
            pcol = lax.dynamic_index_in_dim(a, kk // q, axis=1,
                                            keepdims=False)
            diag_slot = kk // p
            fixed = tile_diag_pad_identity(
                lax.dynamic_index_in_dim(pcol, diag_slot, axis=0,
                                         keepdims=False), kk, m, nb, n)
            pcol = jnp.where(
                (gi == kk)[:, None, None],
                lax.dynamic_update_index_in_dim(pcol, fixed, diag_slot,
                                                axis=0), pcol)
            pcol = dag.mark(pcol, "panel_bcast", step=kk, device=dev,
                            edge="b", routine="getrf", ndev=ndev)
            full = comm.allgather_panel_rows(pcol, p, kk % q)
            panel2d = full.reshape(M, nb)
            panel2d, piv_k, info_k = panel_lu_factor(
                panel2d, kk * nb, m, max_rows=panel_max_rows)
            info = info + info_k
            pivots = pivots.at[kk].set(piv_k)
            ptiles = panel2d.reshape(mt_p, nb, nb)
            newcol = jnp.take(ptiles, gi, axis=0)
            a = jnp.where(
                c == kk % q,
                lax.dynamic_update_index_in_dim(a, newcol, kk // q,
                                                axis=1), a)
            return a, pivots, info, panel2d

        def swap_solve(k, a, pivots, panel2d, excl_hi):
            """Step k's row swaps + U block-row solve from the ring
            buffer, skipping tile columns [k+1, excl_hi) — the
            lookahead columns already handled column-locally; returns
            the broadcast U row, masked the same way."""
            piv_k = lax.dynamic_index_in_dim(pivots, k, axis=0,
                                             keepdims=False)
            a = _swap_rows_local(a, piv_k, k * nb, t_local, nb, p, q,
                                 exclude_col=k, min_col=0,
                                 max_col=None, excl_lo=k + 1,
                                 excl_hi=excl_hi)
            lkk = lax.dynamic_slice(panel2d, (k * nb, 0), (nb, nb))
            arow = lax.dynamic_index_in_dim(a, k // p, axis=0,
                                            keepdims=False)[c0s:]
            solved = lax.linalg.triangular_solve(
                jnp.broadcast_to(lkk, (nsub, nb, nb)), arow,
                left_side=True, lower=True, unit_diagonal=True)
            right = (gjs > k) & (gjs < nt) \
                & ~((gjs > k) & (gjs < excl_hi))
            urow = jnp.where(right[:, None, None], solved, arow)
            a = jnp.where(
                r == k % p,
                lax.dynamic_update_index_in_dim(
                    a, a[k // p].at[c0s:].set(urow), k // p,
                    axis=0), a)
            urow_b = comm.bcast_from_row(
                jnp.where(right[:, None, None], urow,
                          jnp.zeros_like(urow)), k % p)
            return a, urow_b

        def lpanel_tiles(k, panel2d):
            """L tiles of the buffered step-k panel, masked below the
            diagonal block (zero rows contribute nothing to gemms)."""
            ptiles = panel2d.reshape(mt_p, nb, nb)
            lrows = jnp.take(ptiles, gis, axis=0)
            below = (gis > k) & (gis < mt)
            return jnp.where(below[:, None, None], lrows,
                             jnp.zeros_like(lrows))

        def gemm_col(s, j, a, u_tile, panel2d):
            """Step s's gemm on tile column j only, from the buffered
            panel's L tiles and one broadcast U tile."""
            lrows_f = jnp.take(panel2d.reshape(mt_p, nb, nb), gi,
                               axis=0)
            below_f = (gi > s) & (gi < mt)
            lrows_f = jnp.where(below_f[:, None, None], lrows_f,
                                jnp.zeros_like(lrows_f))
            upd1 = jnp.einsum("aik,bkj->abij", lrows_f, u_tile[None],
                              **pk)[:, 0]
            acol = lax.dynamic_index_in_dim(a, j // q, axis=1,
                                            keepdims=False)
            return jnp.where(
                c == j % q,
                lax.dynamic_update_index_in_dim(a, acol - upd1,
                                                j // q, axis=1), a)

        def col_advance(s, j, a, pivots, panel2d):
            """The column-local lookahead triple: apply step s's row
            swaps to tile column j only, solve the single U tile
            (s, j) from buffer s's diagonal block, write it back, and
            run step s's gemm on the column — element-for-element the
            work the sequential loop's step s would do to column j,
            just scheduled d-s iterations early."""
            piv_s = lax.dynamic_index_in_dim(pivots, s, axis=0,
                                             keepdims=False)
            a = _swap_rows_local(a, piv_s, s * nb, t_local, nb, p, q,
                                 exclude_col=-1, only_col=j)
            lkk = lax.dynamic_slice(panel2d, (s * nb, 0), (nb, nb))
            arow = lax.dynamic_index_in_dim(a, s // p, axis=0,
                                            keepdims=False)
            tile = lax.dynamic_index_in_dim(arow, j // q, axis=0,
                                            keepdims=False)
            solved = lax.linalg.triangular_solve(
                jnp.broadcast_to(lkk, (1, nb, nb)), tile[None],
                left_side=True, lower=True, unit_diagonal=True)[0]
            newrow = lax.dynamic_update_index_in_dim(arow, solved,
                                                     j // q, axis=0)
            a = jnp.where(
                (r == s % p) & (c == j % q),
                lax.dynamic_update_index_in_dim(a, newrow, s // p,
                                                axis=0), a)
            u_tile = comm.bcast_from_row(
                jnp.where(c == j % q, solved, jnp.zeros_like(solved)),
                s % p)
            return gemm_col(s, j, a, u_tile, panel2d)

        def trailing(k, a, panel2d, urow_t):
            """Step k's big trailing gemm from the ring buffer; the
            caller masks the U row to the columns still owed step k."""
            lrows = lpanel_tiles(k, panel2d)
            lrows = dag.mark(lrows, "trailing", step=k, device=dev,
                             edge="b", routine="getrf", ndev=ndev)
            upd = jnp.einsum("aik,bkj->abij", lrows, urow_t, **pk)
            sub = a[r0s:, c0s:] - upd
            a = a.at[r0s:, c0s:].set(sub)
            return dag.mark(a, "trailing", step=k, device=dev,
                            edge="e", routine="getrf", ndev=ndev)

        # prologue (plan-driven): fill the ring — factor k0, then for
        # t < d bring column k0+t up to date column-locally (no
        # swap_solve has run yet, so every source step is the full
        # swap/solve/gemm triple) and factor it
        a, pivots, info = a, pivots0, info0
        ring = ()
        for op in plan.prologue:
            if op[0] == "factor":
                a, pivots, info, fresh = factor_panel(op[1], a,
                                                      pivots, info)
                ring = ring + (fresh,)
            else:                                    # ("advance", j, srcs)
                for s in op[2]:
                    a = col_advance(s, op[1], a, pivots,
                                    ring[s - k0])

        def step(k, carry):
            a, pivots, info, ring = carry
            fresh = None
            urow_b = None
            a = dag.mark(a, "step", step=k, device=dev, edge="b",
                         routine="getrf", ndev=ndev)
            for op in plan.body:
                if op[0] == "consume":
                    ring = (dag.mark(ring[0], "panel_bcast", step=k,
                                     device=dev, edge="e",
                                     routine="getrf", ndev=ndev),
                            ) + ring[1:]
                elif op[0] == "swap_solve":
                    a, urow_b = swap_solve(k, a, pivots, ring[0],
                                           k + d)
                elif op[0] == "advance":
                    j = k + op[1]
                    for t in op[2]:
                        if t == 0:
                            # step k's U tile is fresh from swap_solve
                            u_tile = lax.dynamic_index_in_dim(
                                urow_b, j // q - c0s, axis=0,
                                keepdims=False)
                            a = gemm_col(k, j, a, u_tile, ring[0])
                        else:
                            a = col_advance(k + t, j, a, pivots,
                                            ring[t])
                elif op[0] == "factor":
                    a, pivots, info, fresh = factor_panel(
                        k + op[1], a, pivots, info)
                else:                                # ("trailing", 0, d)
                    j_adv = k + op[1] + op[2]
                    urow_t = jnp.where((gjs != j_adv)[:, None, None],
                                       urow_b,
                                       jnp.zeros_like(urow_b))
                    a = trailing(k + op[1], a, ring[0], urow_t)
            a = dag.mark(a, "step", step=k, device=dev, edge="e",
                         routine="getrf", ndev=ndev)
            return a, pivots, info, ring[1:] + (fresh,)

        a, pivots, info, ring = lax.fori_loop(
            plan.body_lo, plan.body_hi, step, (a, pivots, info, ring))

        # epilogue (plan-driven): drain the ring — every in-chunk
        # column already advanced, so swaps/solves/gemm touch only
        # columns beyond the chunk
        urow_b = None
        for op in plan.epilogue:
            k = op[1]
            if op[0] == "consume":
                a = dag.mark(a, "step", step=k, device=dev, edge="b",
                             routine="getrf", ndev=ndev)
                slot = k - ep0
                ring = ring[:slot] + (dag.mark(
                    ring[slot], "panel_bcast", step=k, device=dev,
                    edge="e", routine="getrf", ndev=ndev),
                    ) + ring[slot + 1:]
            elif op[0] == "swap_solve":
                a, urow_b = swap_solve(k, a, pivots, ring[k - ep0],
                                       k_last + 1)
            else:                                    # ("trailing", k, None)
                a = trailing(k, a, ring[k - ep0], urow_b)
                a = dag.mark(a, "step", step=k, device=dev, edge="e",
                             routine="getrf", ndev=ndev)
        return a[None, None], pivots, info

    return jax.shard_map(
        body, mesh=g.mesh, in_specs=(P(AXIS_P, AXIS_Q), P(), P()),
        out_specs=(P(AXIS_P, AXIS_Q), P(), P()), check_vma=False)(
            A.data, pivots0, info0)


_getrf_pipe_chunk_jit = cached_jit(
    _getrf_pipe_chunk_core, routine="getrf.chunk.pipe",
    static_argnames=("k0", "klen", "depth", "tier"))
_getrf_pipe_chunk_jit_overwrite = cached_jit(
    _getrf_pipe_chunk_core, routine="getrf.chunk.pipe.overwrite",
    donate_argnums=0,
    static_argnames=("k0", "klen", "depth", "tier"))


def _getrf_tail_core(A, pivots, k0, klen, lo, hi, tier=None):
    """Apply chunk [k0, k0+klen)'s factor to trailing tile columns
    [lo, hi) ONLY: per panel k — row swaps on the window, the U
    block-row solve, and the trailing gemm. The superstep DAG's
    tailLA/tailRest body (reference getrf.cc lookahead/trailing
    tasks); column-disjoint from the next chunk's factor task."""
    g = A.grid
    p, q, nb = g.p, g.q, A.nb
    m, n = A.m, A.n
    mt, nt = A.mt, A.nt
    mtl, ntl = A.data.shape[2], A.data.shape[3]
    mt_p = mtl * p
    M = mt_p * nb
    c0s, c1s = lo // q, cdiv(hi, q)
    r0s = k0 // p
    nsub = c1s - c0s
    pk = trailing_dot_kwargs(tier, A.dtype)

    def body(a, pivots):
        a = a[0, 0]
        r, c = comm.coords()
        gi = masks.local_tile_rows(mtl, p)
        gj = masks.local_tile_cols(ntl, q)
        gis, gjs = gi[r0s:], gj[c0s:c1s]
        t_local = (gi[:, None] * nb + jnp.arange(nb)[None, :])

        # ALL chunk swaps first: the stored L columns are in final
        # (fully back-pivoted) row order, so the per-panel solves
        # below are plain forward block substitution on the fully
        # permuted window — mixing per-panel swaps with final L rows
        # would be inconsistent
        def swap_step(k, a):
            return _swap_rows_local(a, pivots[k], k * nb, t_local, nb,
                                    p, q, exclude_col=-1, min_col=lo,
                                    max_col=hi)

        a = lax.fori_loop(k0, k0 + klen, swap_step, a)

        def step(k, a):
            # gather the factored panel column k (L below diagonal)
            pcol = lax.dynamic_index_in_dim(a, k // q, axis=1,
                                            keepdims=False)
            full = comm.allgather_panel_rows(pcol, p, k % q)
            panel2d = full.reshape(M, nb)
            lkk0 = lax.dynamic_slice(panel2d, (k * nb, 0), (nb, nb))
            lkk = jnp.tril(lkk0, -1) + jnp.eye(nb, dtype=a.dtype)
            arow = lax.dynamic_index_in_dim(a, k // p, axis=0,
                                            keepdims=False)[c0s:c1s]
            solved = lax.linalg.triangular_solve(
                jnp.broadcast_to(lkk, (nsub, nb, nb)), arow,
                left_side=True, lower=True, unit_diagonal=True)
            right = (gjs >= lo) & (gjs < min(nt, hi)) & (gjs > k)
            urow = jnp.where(right[:, None, None], solved, arow)
            a = jnp.where(
                r == k % p,
                lax.dynamic_update_index_in_dim(
                    a, a[k // p].at[c0s:c1s].set(urow), k // p,
                    axis=0), a)
            urow_b = comm.bcast_from_row(
                jnp.where(right[:, None, None], urow,
                          jnp.zeros_like(urow)), k % p)
            ptiles = panel2d.reshape(mt_p, nb, nb)
            lrows = jnp.take(ptiles, gis, axis=0)
            below = (gis > k) & (gis < mt)
            # keep only the strict L part of the gathered column
            rowid = (gis[:, None] * nb
                     + jnp.arange(nb, dtype=jnp.int32)[None, :])
            lmask = rowid[:, :, None] > (k * nb + jnp.arange(
                nb, dtype=jnp.int32))[None, None, :]
            lrows = jnp.where(below[:, None, None] & lmask, lrows,
                              jnp.zeros_like(lrows))
            upd = jnp.einsum("aik,bkj->abij", lrows, urow_b, **pk)
            sub = a[r0s:, c0s:c1s] - upd
            return a.at[r0s:, c0s:c1s].set(sub)

        a = lax.fori_loop(k0, k0 + klen, step, a)
        return a[None, None]

    return jax.shard_map(
        body, mesh=g.mesh, in_specs=(P(AXIS_P, AXIS_Q), P()),
        out_specs=P(AXIS_P, AXIS_Q), check_vma=False)(A.data, pivots)


_getrf_tail_jit = cached_jit(_getrf_tail_core, routine="getrf.tail",
                             static_argnames=("k0", "klen", "lo", "hi",
                                              "tier"))


def _getrf_backpiv_core(A, pivots, k0, klen, hi):
    """Back-pivot the STORED L: apply chunk [k0, k0+klen)'s row swaps
    to finished tile columns [0, hi) — the cross-chunk swap leg of
    the superstep DAG (reference getrf.cc applies pivots to the left
    of the panel post-factor)."""
    g = A.grid
    p, q, nb = g.p, g.q, A.nb
    mtl, ntl = A.data.shape[2], A.data.shape[3]

    def body(a, pivots):
        a = a[0, 0]
        gi = masks.local_tile_rows(mtl, p)
        t_local = (gi[:, None] * nb + jnp.arange(nb)[None, :])

        def step(k, a):
            return _swap_rows_local(a, pivots[k], k * nb, t_local, nb,
                                    p, q, exclude_col=-1, min_col=0,
                                    max_col=hi)

        return lax.fori_loop(k0, k0 + klen, step, a)[None, None]

    return jax.shard_map(
        body, mesh=g.mesh, in_specs=(P(AXIS_P, AXIS_Q), P()),
        out_specs=P(AXIS_P, AXIS_Q), check_vma=False)(A.data, pivots)


_getrf_backpiv_jit = cached_jit(_getrf_backpiv_core,
                                routine="getrf.backpiv",
                                static_argnames=("k0", "klen", "hi"))


def _panel_stored_rows(pcol, k, t_local, m, p, max_rows):
    """Step k's tournament panel factored where its rows are stored
    (CALU as the reference's getrf_tntpiv runs it: the first round on
    each rank's own rows). The [M, nb] panel is never assembled.

    pcol: [mtl, nb, nb] this device's slots of tile column k, padded
    diagonal fixed, the same on every device of a mesh row (the caller
    sent it over q); t_local: [mtl, nb] their global row ids.

    Round one picks nb winners among the local rows of the active
    window [k·nb, max(m, k·nb+nb)), the rest zeroed; the p·nb winners
    and the diagonal tile cross p (scope ``panel_bcast``), the last
    round and LAPACK's swap list run on every device. The column's own
    rows are then swapped — outside the diagonal block a swap can only
    bring in one of that block's rows — and the rows below the block
    take L21 = A21·U11⁻¹ in one triangular solve over local rows.

    Returns (newcol, lu_top, piv_k, info_k): the factored local slots
    of the column, the factored diagonal block (replicated), and the
    step's swap list and zero-pivot count as ``panel_lu_factor`` gives
    them."""
    from ..internal.tile_kernels import (tournament_winners,
                                         tournament_pivots, _safe_upper,
                                         _factor_dtype)
    mtl, nb, _ = pcol.shape
    L, M = mtl * nb, mtl * p * nb
    fd = _factor_dtype(pcol.dtype)
    r = lax.axis_index(AXIS_P)
    start = k * nb
    t_flat = t_local.reshape(L).astype(jnp.int32)
    with jax.named_scope("panel"):
        rows = pcol.reshape(L, nb)
        active = (t_flat >= start) & (t_flat < jnp.maximum(m, start + nb))
        # round one sees the slots from the first active one on (whole
        # tiles move, no row does): active rows first, so a tie falls
        # to one of them
        order = (jnp.sum(t_local[:, 0] < start) + jnp.arange(mtl)) % mtl
        masked = jnp.where(active.reshape(mtl, nb, 1), pcol,
                           jnp.zeros_like(pcol))
        ids = jnp.where(active, t_flat, M).reshape(mtl, nb)
        win_rows, win_ids = tournament_winners(
            jnp.take(masked, order, axis=0).reshape(L, nb),
            jnp.take(ids, order, axis=0).reshape(L), max_rows, M)
    with jax.named_scope("panel_bcast"):
        cand = comm.allgather_tiled(win_rows, AXIS_P, p)
        cand_idx = comm.allgather_tiled(win_ids, AXIS_P, p)
        diag = comm.bcast_from_row(
            lax.dynamic_index_in_dim(pcol, k // p, axis=0, keepdims=False),
            k % p)
    with jax.named_scope("panel"):
        lu_top, piv_k, locof, info_k = tournament_pivots(
            cand, cand_idx, start, M, max_rows, pcol.dtype)
        # where the diagonal block's rows went: local rows outside the
        # block that received one (anything else is dropped)
        pos = lax.dynamic_slice(locof, (start,), (nb,))
        tile = pos // nb
        here = (tile % p == r) & (tile != k)
        lidx = jnp.where(here, (tile // p) * nb + pos % nb,
                         L + jnp.arange(nb, dtype=jnp.int32))
        swapped = rows.at[lidx].set(diag, mode="drop", unique_indices=True)
        l21 = lax.linalg.triangular_solve(
            _safe_upper(lu_top).astype(fd), swapped.astype(fd),
            left_side=False, lower=False).astype(pcol.dtype)
        below = active & (t_flat >= start + nb)
        out = jnp.where(below[:, None], l21, rows).reshape(mtl, nb, nb)
        on_diag = (t_local[:, 0] == start)[:, None, None]
        newcol = jnp.where(on_diag, lu_top[None], out)
    return newcol, lu_top, piv_k, info_k


def _swap_rows_local(a, piv_k, start, t_local, nb, p, q, exclude_col,
                     min_col: int = 0, max_col: int | None = None,
                     excl_lo=None, excl_hi=None, only_col=None):
    """Apply one panel's sequential row swaps to the local tile stack,
    excluding tile-column ``exclude_col`` (already permuted in-panel)
    and tile columns outside [``min_col``, ``max_col``).

    a: [mtl, ntl, nb, nb]; piv_k: [nb] global pivot rows; swaps are
    row (start+j) ↔ piv_k[j] for j = 0..nb-1 in order.

    The DAG runtime's depth-k schedules add two column selections
    (both may be traced scalars): ``excl_lo``/``excl_hi`` skip tile
    columns in [excl_lo, excl_hi) — the lookahead columns a pipelined
    loop already swapped ahead of time — and ``only_col`` restricts
    the swap to that single tile column (the column-local early swap
    the lookahead applies, overriding every other column selector).
    """
    mtl, ntl = a.shape[0], a.shape[1]
    r = lax.axis_index(AXIS_P)
    mt_p = mtl * p
    M = mt_p * nb
    cand = jnp.concatenate([start + jnp.arange(nb, dtype=jnp.int32),
                            piv_k])                      # [2nb]

    # gather candidate rows' local-column data: [2nb, ntl, nb]
    z = jnp.int32(0)

    def grab(t):
        tile = t // nb
        slot = tile // p
        owner = (tile % p) == r
        row = lax.dynamic_slice(
            a, (jnp.where(owner, slot, z).astype(jnp.int32), z,
                jnp.where(owner, t % nb, z).astype(jnp.int32), z),
            (1, ntl, 1, nb))[0, :, 0, :]                 # [ntl, nb]
        return jnp.where(owner, row, jnp.zeros_like(row))

    cand_rows = jax.vmap(grab)(cand)                     # [2nb, ntl, nb]
    cand_rows = comm.psum_rows(cand_rows)

    # resolve the swap sequence into a content map on the row space
    content0 = jnp.arange(M, dtype=jnp.int32)

    def sim(j, content):
        aj = start + j
        bj = piv_k[j]
        ca, cb = content[aj], content[bj]
        return content.at[aj].set(cb).at[bj].set(ca)

    content = lax.fori_loop(0, nb, sim, content0)

    # local rows whose content changed get their new values
    t_flat = t_local.reshape(-1)                         # [mtl*nb]
    src = jnp.take(content, t_flat)                      # source row ids
    need = src != t_flat
    # index of src in cand (valid where need)
    match = (cand[None, :] == src[:, None])              # [L, 2nb]
    idx = jnp.argmax(match, axis=1)
    new_rows = jnp.take(cand_rows, idx, axis=0)          # [L, ntl, nb]
    new_rows = new_rows.reshape(mtl, nb, ntl, nb).transpose(0, 2, 1, 3)
    need4 = need.reshape(mtl, 1, nb, 1)
    # column exclusion at tile granularity (the panel column was
    # already permuted during the panel factorization):
    gj = masks.local_tile_cols(ntl, q)
    if only_col is not None:
        keep_col = gj == only_col
    else:
        keep_col = (gj != exclude_col) & (gj >= min_col)
        if max_col is not None:
            keep_col = keep_col & (gj < max_col)
        if excl_lo is not None:
            keep_col = keep_col & ~((gj >= excl_lo) & (gj < excl_hi))
    return jnp.where(need4 & keep_col[None, :, None, None], new_rows, a)


def _swap_cols_local(a, piv_k, start, nb, p, q, min_col: int = 0):
    """Column analog of :func:`_swap_rows_local`: apply one panel's
    sequential swaps to global COLUMNS (start+j) ↔ piv_k[j], touching
    only tile columns ≥ ``min_col``. Used by the symmetric (Aasen)
    factorization where pivots permute rows AND columns.
    """
    mtl, ntl = a.shape[0], a.shape[1]
    c = lax.axis_index(AXIS_Q)
    nt_q = ntl * q
    N = nt_q * nb
    cand = jnp.concatenate([start + jnp.arange(nb, dtype=jnp.int32),
                            piv_k])                      # [2nb]
    z = jnp.int32(0)

    def grab(t):
        tile = t // nb
        slot = tile // q
        owner = (tile % q) == c
        col = lax.dynamic_slice(
            a, (z, jnp.where(owner, slot, z).astype(jnp.int32), z,
                jnp.where(owner, t % nb, z).astype(jnp.int32)),
            (mtl, 1, nb, 1))[:, 0, :, 0]                 # [mtl, nb]
        return jnp.where(owner, col, jnp.zeros_like(col))

    cand_cols = jax.vmap(grab)(cand)                     # [2nb, mtl, nb]
    cand_cols = comm.psum_cols(cand_cols)

    content0 = jnp.arange(N, dtype=jnp.int32)

    def sim(j, content):
        aj = start + j
        bj = piv_k[j]
        ca, cb = content[aj], content[bj]
        return content.at[aj].set(cb).at[bj].set(ca)

    content = lax.fori_loop(0, nb, sim, content0)

    gj = masks.local_tile_cols(ntl, q)
    t_local = (gj[:, None] * nb + jnp.arange(nb)[None, :])  # [ntl, nb]
    t_flat = t_local.reshape(-1)
    src = jnp.take(content, t_flat)
    need = src != t_flat
    match = (cand[None, :] == src[:, None])
    idx = jnp.argmax(match, axis=1)
    new_cols = jnp.take(cand_cols, idx, axis=0)          # [L, mtl, nb]
    new_cols = new_cols.reshape(ntl, nb, mtl, nb).transpose(2, 0, 3, 1)
    need4 = need.reshape(1, ntl, 1, nb)
    keep_col = gj >= min_col
    return jnp.where(need4 & keep_col[None, :, None, None], new_cols, a)


# ---------------------------------------------------------------------------
# getrs / gesv
# ---------------------------------------------------------------------------

def getrs(LU: Matrix, piv, B: Matrix, trans: Op = Op.NoTrans, opts=None):
    """Solve A·X = B from getrf factors (reference src/getrs.cc):
    forward-permute B, unit-lower solve, upper solve (NoTrans);
    reversed for Aᵀ/Aᴴ."""
    from ..ops.blas import trsm
    from ..matrix import transpose, conj_transpose, TriangularMatrix
    L = TriangularMatrix(data=LU.data, m=LU.m, n=LU.n, nb=LU.nb,
                         grid=LU.grid, uplo=Uplo.Lower, diag=Diag.Unit)
    U = TriangularMatrix(data=LU.data, m=LU.m, n=LU.n, nb=LU.nb,
                         grid=LU.grid, uplo=Uplo.Upper, diag=Diag.NonUnit)
    with trace.block("getrs"):
        if trans == Op.NoTrans:
            with trace.block("getrs.apply_pivots",
                             **_apply_pivots_labels(B, piv)):
                Bp = _apply_pivots_matrix(B, piv, forward=True)
            Y = trsm(Side.Left, 1.0, L, Bp, opts)
            X = trsm(Side.Left, 1.0, U, Y, opts)
            return X
        opA = transpose if trans == Op.Trans else conj_transpose
        Y = trsm(Side.Left, 1.0, opA(U), B, opts)
        Z = trsm(Side.Left, 1.0, opA(L), Y, opts)
        with trace.block("getrs.apply_pivots",
                         **_apply_pivots_labels(Z, piv)):
            return _apply_pivots_matrix(Z, piv, forward=False)


def getrs_nopiv(LU: Matrix, B: Matrix, opts=None):
    from ..ops.blas import trsm
    from ..matrix import TriangularMatrix
    L = TriangularMatrix(data=LU.data, m=LU.m, n=LU.n, nb=LU.nb,
                         grid=LU.grid, uplo=Uplo.Lower, diag=Diag.Unit)
    U = TriangularMatrix(data=LU.data, m=LU.m, n=LU.n, nb=LU.nb,
                         grid=LU.grid, uplo=Uplo.Upper, diag=Diag.NonUnit)
    Y = trsm(Side.Left, 1.0, L, B, opts)
    return trsm(Side.Left, 1.0, U, Y, opts)


def gesv(A: Matrix, B: Matrix, opts=None):
    """Solve A·X = B by LU (reference src/gesv.cc).
    Returns (X, LU, piv, info)."""
    with trace.block("slate.gesv", routine="gesv", n=A.n, nb=A.nb,
                     nrhs=B.n, grid=f"{A.grid.p}x{A.grid.q}"):
        return _gesv(A, B, opts)


def _gesv(A, B, opts):
    method = MethodLU.select_algo(A, opts)
    if method == MethodLU.NoPiv:
        LU, info = getrf_nopiv(A, opts)
        return getrs_nopiv(LU, B, opts), LU, None, info
    Am = A.materialize()
    fm = (_fast_path_mode(Am, "partial")
          if (Am.grid.size == 1 and min(Am.mt, Am.nt) <= 64
              and B.grid.size == 1) else None)
    if fm is not None:
        # pivoting-by-index end to end: the factor emits the
        # elimination order, the solve applies it as ONE gather —
        # neither side runs an O(n) sequential swap simulation; the
        # LAPACK ipiv of the return contract is derived on host while
        # the device runs the solve
        obs.count("getrf.path", 1, phase="fast_path")
        with trace.block("getrf.chunk", phase="fast_path", k0=0,
                         klen=min(Am.mt, Am.nt)):
            data, order, info = _getrf_fast_jit(
                Am, interpret=(fm == "interpret"), want_ipiv=False,
                fold=_fold_now())
        LU = Am._replace(data=data)
        X = getrs(LU, PivotOrder(order), B, Op.NoTrans, opts)
        return X, LU, pivot_order_to_ipiv(order), info
    LU, piv, info = getrf(A, opts)
    X = getrs(LU, piv, B, Op.NoTrans, opts)
    return X, LU, piv, info


def gesv_nopiv(A: Matrix, B: Matrix, opts=None):
    LU, info = getrf_nopiv(A, opts)
    return getrs_nopiv(LU, B, opts), LU, info


def gesv_batched(a, b, opts=None, *, nb: int | None = None):
    """Leading-axis batched general solve on dense ``[batch, n, n]`` /
    ``[batch, n, nrhs]`` stacks — the serving-path sibling of
    :func:`gesv` (one executable per (bucket, batch rung, tier); see
    ``slate_tpu.serve.batched``).  Partial pivoting runs per instance;
    returns ``(x, lu, perm, info)`` where ``perm[i]`` is instance i's
    row permutation and ``info[i]`` its zero-pivot count."""
    from ..serve.batched import batched_gesv
    return batched_gesv(a, b, opts, nb=nb)


# ---------------------------------------------------------------------------
# pivot application to a full matrix (reference internal_swap.cc —
# the reference swaps rows one MPI_Sendrecv at a time; here the swap
# list is composed into one global permutation (``_sim_perm``: every
# panel's swaps replayed at once, nb + kt dependent steps, O(M) ints)
# and applied in one pass):
#
# * single device: local dense take (fastest, no comm);
# * multi-chip: a fori over destination tile rows, each gathering its
#   nb source rows by masked psum over the mesh rows and writing on
#   the owner — one matrix volume of ICI traffic, O(nb·N/q) peak
#   working memory, and **no replicated dense array** (so getri-scale
#   row permutes stay within a chip's local share).
# ---------------------------------------------------------------------------

def _apply_pivots_kind(B: Matrix, piv) -> str:
    """Which program applies ``piv`` to B's rows: ``order_gather`` (an
    elimination order, one gather), ``swap_sim`` (LAPACK pivots
    replayed panel by panel into a permutation, ``_sim_perm``, then one
    gather of the replicated B) or ``dist`` (the same replay, rows
    exchanged tile row by tile row with no replicated B)."""
    if isinstance(piv, PivotOrder):
        return "order_gather"
    if B.grid.size == 1:
        return "swap_sim"
    # narrow B (getrs RHS sizes): one replicated gather+take beats
    # mt_p sequential psum rounds; wide B (getri scale): the
    # distributed pass avoids materializing a replicated dense array
    repl_bytes = (B.data.shape[2] * B.grid.p * B.data.shape[3]
                  * B.grid.q * B.nb * B.nb * B.data.dtype.itemsize)
    if B.n <= 4 * B.nb or repl_bytes < 32 * 2**20:
        return "swap_sim"
    # latency guard: the dist pass runs mt_p sequential psum rounds
    # (one ICI collective each); with many tile rows the one-shot
    # replicated gather wins unless the replicated array itself is
    # prohibitive (≳1 GB/chip)
    mt_p = B.data.shape[2] * B.grid.p
    if mt_p > 256 and repl_bytes < 2**30:
        return "swap_sim"
    return "dist"


def _apply_pivots_labels(B: Matrix, piv) -> dict:
    """Labels of a ``getrs.apply_pivots`` span: the ``kind`` (also
    counted, ``getrs.apply_pivots{kind}``) and, where LAPACK pivots are
    replayed, the ``steps`` of that replay (kt·nb swaps) beside its
    ``serial_steps`` (nb + kt: what of ``_sim_perm`` waits on the step
    before it)."""
    kind = _apply_pivots_kind(B, piv)
    obs.count("getrs.apply_pivots", 1, kind=kind)
    if kind == "order_gather":
        return {"kind": kind}
    kt, nb = piv.shape
    return {"kind": kind, "steps": kt * nb, "serial_steps": nb + kt}


def _apply_pivots_matrix(B: Matrix, piv, forward: bool) -> Matrix:
    kind = _apply_pivots_kind(B, piv)
    if kind == "order_gather":
        # elimination order: the permutation IS the pivot data — no
        # swap simulation. Single-device only (the fast path's gate).
        slate_error_if(B.grid.size != 1,
                       "PivotOrder pivots require a single-device B")
        return _apply_order_jit(B, piv.order, forward)
    if kind == "swap_sim":
        return _apply_piv_jit(B, piv, forward)
    return _apply_piv_dist(B, piv, forward)


def _sim_perm(piv, Mrows, forward):
    """Compose the LAPACK swap list ``piv`` [kt, nb] (swap t exchanges
    rows t and ``piv.reshape(-1)[t]``, t ascending) into the row
    permutation it amounts to, out_row[i] = in_row[perm[i]], entry for
    entry what replaying the kt·nb swaps one after another gives, for
    any swap list, in nb + kt dependent steps.

    The swaps of panel k, started from the identity, are a permutation
    P_k that no other panel has a say in, and it moves at most the
    2·nb rows ``pos[k]`` it names: k·nb + j and ``piv[k, j]``.  So all
    kt panels are replayed at once on a [kt, 2·nb] state (the row held
    in each named place), nb steps of two masked passes; a row named
    more than once is followed in the slot of its first mention.  Then
    perm = P_0 ∘ P_1 ∘ … is kt gathers and scatters of 2·nb entries,
    and ``forward=False`` (the swaps undone, last first) is its
    inverse."""
    kt, nb = piv.shape
    iw = jnp.arange(2 * nb, dtype=jnp.int32)
    pos = jnp.concatenate(
        [jnp.arange(kt * nb, dtype=jnp.int32).reshape(kt, nb),
         piv.astype(jnp.int32)], axis=1)
    slot = jnp.min(jnp.where(pos[:, :, None] == pos[:, None, :], iw,
                             2 * nb), axis=2)       # slot[:, :nb] == iw[:nb]

    def swap(j, held):
        sb = lax.dynamic_slice_in_dim(slot, nb + j, 1, axis=1)
        at_b = iw == sb
        ha = lax.dynamic_slice_in_dim(held, j, 1, axis=1)
        hb = jnp.sum(jnp.where(at_b, held, 0), axis=1, keepdims=True,
                     dtype=jnp.int32)
        return jnp.where(at_b, ha, jnp.where(iw == j, hb, held))

    held = lax.fori_loop(0, nb, swap, pos)
    held = jnp.take_along_axis(held, slot, axis=1)      # P_k[pos[k]]

    def compose(k, perm):
        return perm.at[pos[k]].set(jnp.take(perm, held[k], mode="clip"))

    perm = lax.fori_loop(0, kt, compose, jnp.arange(Mrows, dtype=jnp.int32))
    if forward:
        return perm
    return jnp.zeros(Mrows, jnp.int32).at[perm].set(
        jnp.arange(Mrows, dtype=jnp.int32))


@partial(cached_jit, routine="getrs.apply_piv_dist",
         static_argnames=("forward",))
def _apply_piv_dist(B, piv, forward):
    g = B.grid
    p, nb = g.p, B.nb
    mtl = B.data.shape[2]
    mt_p = mtl * p
    Mrows = mt_p * nb

    def body(dat, piv):
        a = dat[0, 0]
        r, _ = comm.coords()
        perm = _sim_perm(piv, Mrows, forward)

        def tstep(t, out):
            need = lax.dynamic_slice(perm, (t * nb,), (nb,))
            tg, og = need // nb, need % nb
            mine = (tg % p) == r
            slot = jnp.where(mine, tg // p, 0)
            ogc = jnp.where(mine, og, 0)
            vals = a[slot, :, ogc, :]            # [nb, ntl, nb]
            vals = jnp.where(mine[:, None, None], vals,
                             jnp.zeros_like(vals))
            vals = comm.psum_rows(vals)
            own = (t % p) == r
            dslot = jnp.where(own, t // p, 0)
            blk = vals.transpose(1, 0, 2)        # [ntl, nb, nb]
            cur = lax.dynamic_index_in_dim(out, dslot, axis=0,
                                           keepdims=False)
            newv = jnp.where(own, blk, cur)
            return lax.dynamic_update_index_in_dim(out, newv, dslot,
                                                   axis=0)

        out = lax.fori_loop(0, mt_p, tstep, jnp.zeros_like(a))
        return out[None, None]

    data = jax.shard_map(
        body, mesh=g.mesh, in_specs=(P(AXIS_P, AXIS_Q), P()),
        out_specs=P(AXIS_P, AXIS_Q), check_vma=False)(B.data, piv)
    return B._replace(data=data)


@partial(cached_jit, routine="getrs.apply_order",
         static_argnames=("forward",))
def _apply_order_jit(B, order, forward):
    """Apply an elimination-order permutation to B's rows in one
    gather (forward: out[j] = in[order[j]]) or its inverse scatter
    (backward: out[order[j]] = in[j]). Rows past the pivoted range
    (tile padding) map to themselves."""
    from ..matrix import bc_to_tiles, bc_from_tiles, tiles_to_dense, \
        dense_to_tiles
    tiles = bc_to_tiles(B.data)
    mt_p, nt_p, nb, _ = tiles.shape
    Mrows = mt_p * nb
    dense = tiles_to_dense(tiles, Mrows, nt_p * nb)
    o = order.reshape(-1).astype(jnp.int32)
    npiv = o.shape[0]
    if npiv < Mrows:
        o = jnp.concatenate([o, jnp.arange(npiv, Mrows, dtype=jnp.int32)])
    if forward:
        perm = o
    else:
        perm = jnp.zeros(Mrows, jnp.int32).at[o].set(
            jnp.arange(Mrows, dtype=jnp.int32))
    dense = jnp.take(dense, perm, axis=0)
    tiles = dense_to_tiles(dense, nb, mt_p, nt_p)
    data = bc_from_tiles(tiles, B.grid.p, B.grid.q)
    data = jax.lax.with_sharding_constraint(data, B.grid.sharding())
    return B._replace(data=data)


@partial(cached_jit, routine="getrs.apply_piv",
         static_argnames=("forward",))
def _apply_piv_jit(B, piv, forward):
    from ..matrix import bc_to_tiles, bc_from_tiles, tiles_to_dense, \
        dense_to_tiles
    tiles = bc_to_tiles(B.data)
    mt_p, nt_p, nb, _ = tiles.shape
    Mrows = mt_p * nb
    dense = tiles_to_dense(tiles, Mrows, nt_p * nb)
    perm = _sim_perm(piv, Mrows, forward)
    dense = jnp.take(dense, perm, axis=0)
    tiles = dense_to_tiles(dense, nb, mt_p, nt_p)
    data = bc_from_tiles(tiles, B.grid.p, B.grid.q)
    data = jax.lax.with_sharding_constraint(data, B.grid.sharding())
    return B._replace(data=data)


# ---------------------------------------------------------------------------
# Band LU (reference src/gbtrf.cc:213-221 / gbtrs.cc / gbsv.cc).
# Packed-band kernel on dgbtrf working storage (fill-in band kl+ku):
# one jit, O(n·(kl+ku)²) flops, pivoting restricted to the band the
# way partial pivoting naturally confines it (see linalg/band.py).
# ---------------------------------------------------------------------------

def gbtrf(A, opts=None):
    """Band LU with partial pivoting. Returns ``(BandLUFactor, piv,
    info)`` — packed dgbtrf-layout factor (``.to_dense()`` available);
    piv[k, j] = global row swapped with row k·nb+j."""
    from . import band as _band
    Am = A.materialize()          # resolves op views; flips kl/ku
    kl, ku = Am.kl, Am.ku
    kuf = kl + ku
    nbw = _band._band_block(min(Am.m, Am.n), kl + kuf)
    nt = cdiv(min(Am.m, Am.n), nbw)
    ncols = nt * nbw + nbw + kl + kuf
    with trace.block("gbtrf"):
        ab = _band.pack_tiled(Am, kl, kuf, ncols, band=(kl, ku))
        ab, lpan, piv, info = _band.gbtrf_packed(ab, Am.m, Am.n, kl, ku,
                                                 nbw)
    return (_band.BandLUFactor(ab, lpan, piv, Am.m, Am.n, kl, ku, nbw),
            piv, info)


def gbtrs(F, piv=None, B: Matrix = None, trans: Op = Op.NoTrans,
          opts=None):
    """Solve from gbtrf factors (reference src/gbtrs.cc — interleaved
    row swaps in the L sweep, here at panel-block granularity).
    ``piv`` defaults to the factor's own pivots (it must follow the
    same per-panel layout to be meaningful)."""
    from . import band as _band
    slate_error_if(F.n != B.m, "gbtrs dims")
    pv = F.piv if piv is None else piv
    pad = cdiv(min(F.m, F.n), F.nb) * F.nb + F.kl + F.kl + F.ku
    with trace.block("gbtrs"):
        b = _band._b_to_dense(B, pad)
        x = _band.gbtrs_packed(F.ab, F.lpan, pv, b, F.m, F.n, F.kl,
                               F.ku, F.nb, trans)
        return _band._dense_to_b(x, B)


def gbsv(A, B: Matrix, opts=None):
    LU, piv, info = gbtrf(A, opts)
    return gbtrs(LU, piv, B), LU, piv, info


def san_cases(grid, opts=None, n=64, nb=16):
    """slatesan sweep entry: (label, thunk) pairs running this
    driver's jitted surface once at a small shape on ``grid`` (see
    tools/slatesan; armed by SLATE_TPU_SAN=1 + an armed store)."""
    import numpy as np

    def run():
        rng = np.random.default_rng(12)
        a = rng.standard_normal((n, n)).astype(np.float32)
        a += n * np.eye(n, dtype=np.float32)
        A = Matrix.from_dense(a, nb=nb, grid=grid)
        _, _, info = getrf(A, opts=opts)
        return info.block_until_ready()
    return [("getrf", run)]
