"""QR/LQ factorization and least squares: geqrf, gelqf, unmqr, unmlq,
cholqr, gels.

Reference: src/geqrf.cc:150-370 (CAQR: per-rank Householder panel via
internal::geqrf + ttqrt tree reduction over ranks, V/T broadcasts),
src/unmqr.cc, src/gels.cc:96-110 (method dispatch), src/gels_qr.cc,
src/cholqr.cc, src/gelqf.cc.

TPU redesign: the panel (a full tile column) is all-gathered and every
chip runs the same masked Householder column loop
(internal/tile_kernels.panel_qr_factor) — the gather IS the TSQR tree
(reference internal_ttqrt.cc's binary rank tree collapses into one ICI
all-gather + redundant compute, SURVEY §2.6's recommended mapping).
The trailing update uses the compact-WY form with T from ``larft``:

    A₂ ← A₂ − V·Tᴴ·(Vᴴ·A₂)

where Vᴴ·A₂ is a local einsum + psum down mesh rows and the outer
product is a local einsum — two collectives per panel total, versus
the reference's per-tile V/T broadcasts + ttmqr tree exchanges
(src/geqrf.cc:225-307).

Factors: A is overwritten LAPACK-style (R on/above the diagonal, V's
unit-lower columns below); the T matrices ([kt, nb, nb], replicated)
are the analog of SLATE's ``TriangularFactors`` (slate.hh:860).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..cache.jitcache import cached_jit
from ..grid import AXIS_P, AXIS_Q
from ..matrix import (Matrix, TriangularMatrix, cdiv, transpose,
                      conj_transpose)
from ..types import Op, Uplo, Diag, Side, MethodGels
from ..errors import slate_error_if
from ..internal import comm, masks
from ..internal.precision import resolve_tier, trailing_dot_kwargs
from ..internal.tile_kernels import panel_qr_factor, extract_v, larft
from .. import obs
from ..obs import timeline as tl
from ..runtime import dag
from ..utils import trace

# what a least-squares solve reports (docs/observability.md): the spans
# (the root first, then its children in the order the QR branch opens
# them; the existing ``geqrf`` / ``geqrf.chunk`` / ``unmqr`` / ``trsm``
# blocks nest under them) and the counters (``/metrics``):
# ``gels.method{method}`` (what ``MethodGels.select_algo`` resolved),
# ``geqrf.path{program}`` (``fast``: the exact-shape one-chip program,
# ``one_program``: the SPMD one) and ``geqrf.panel{panel}`` (``pallas``:
# every panel through internal/panel_qr.py, ``xla``: none, ``mixed``),
# each counted once a factorization, where the choice is made
SPANS = ("slate.gels", "gels.factor", "gels.apply_q", "gels.solve_r")
COUNTERS = ("gels.method", "geqrf.path", "geqrf.panel")


def geqrf(A: Matrix, opts=None):
    """QR: A = Q·R (reference src/geqrf.cc). Returns (QR, T) with QR
    holding V below / R on-above the diagonal and T the [kt, nb, nb]
    block-reflector triangles."""
    QR, T, _ = _geqrf_chosen(A, opts)
    return QR, T


def _geqrf_chosen(A: Matrix, opts=None):
    """``geqrf`` and what answered it: (QR, T, {"program", "panel",
    "tier"}). The choice of program and panel form is made here, once a
    factorization, and counted here (``geqrf.path``, ``geqrf.panel``)."""
    A = A.materialize()
    from .. import tune
    tier, depth = tune.driver_config("geqrf", A.n, opts)
    fast = _qr_fast_applies(A)
    panel_mode = _qr_panel_mode(A) if fast else None
    program = "fast" if fast else "one_program"
    panel = _panel_form(A, panel_mode)
    obs.count("geqrf.path", 1, program=program)
    obs.count("geqrf.panel", 1, panel=panel)
    with trace.block("geqrf", routine="geqrf", m=A.m, n=A.n, nb=A.nb,
                     precision=tier, program=program, panel=panel):
        if fast:
            with trace.block("geqrf.chunk", phase="fast_path"):
                data, T = _geqrf_fast_jit(A, panel_mode=panel_mode,
                                          tier=tier)
        else:
            with trace.block("geqrf.chunk", phase="one_program"):
                data, T = _geqrf_jit(A, tier, depth)
    return (A._replace(data=data), T,
            {"program": program, "panel": panel, "tier": tier})


def _panel_takes_kernel(panel_mode, fd, rows: int, w: int) -> bool:
    """Whether one [rows, w] panel of the exact-shape program runs the
    Pallas Householder kernel (f32, whole 128-lane subpanels, no taller
    than the kernel's VMEM window); else XLA's geqrf factors it."""
    from ..internal import panel_qr
    return (panel_mode is not None and fd == jnp.float32
            and w % panel_qr.W == 0 and rows <= panel_qr.H_MAX)


def _panel_form(A, panel_mode) -> str:
    """``pallas`` when every panel of the factorization takes the
    kernel, ``xla`` when none does (the SPMD program's panels never
    do), ``mixed`` when only the shorter ones fit."""
    from ..internal.tile_kernels import _factor_dtype
    if panel_mode is None:
        return "xla"
    fd = _factor_dtype(A.dtype)
    kt = min(A.mt, A.nt)
    takes = [_panel_takes_kernel(panel_mode, fd, A.m - k * A.nb,
                                 min(A.nb, A.n - k * A.nb))
             for k in range(kt)]
    return "pallas" if all(takes) else "mixed" if any(takes) else "xla"


def _qr_panel_mode(A):
    """'tpu'/'interpret' when panels should run the Pallas Householder
    kernel (internal/panel_qr.py) instead of XLA geqrf's ~6 µs/column
    path; None keeps XLA panels. SLATE_QR_PANEL=1 forces (interpret on
    CPU — tests), =0 disables."""
    import os
    flag = os.environ.get("SLATE_QR_PANEL", "")
    if flag == "0":
        return None
    on_tpu = A.grid.devices[0].platform == "tpu"
    if flag == "1":
        return "tpu" if on_tpu else "interpret"
    return "tpu" if on_tpu else None


# one chip: from this n up an exact-shape operand with m >= n takes the
# exact-shape program. Measured (PERF.md section 6, PR 44: one v5e, f32,
# nb=256, nrhs=8, slate.gels by hand through both programs, median wall
# of 20-30 calls, SPMD / exact-shape in ms): [16384, 1024] 18.89 / 12.18,
# [16384, 512] 9.66 / 7.65, [1024, 1024] 5.91 / 5.17, [2048, 2048]
# 12.25 / 5.42; level at [4096, 1024] 7.40 / 7.57 and [16384, 256]
# 7.47 / 7.30 (one panel: nothing to win)
FAST_FROM_N = 512


def _qr_fast_applies(A) -> bool:
    """Single-device dense fast path: exact-shape unrolled panels.
    The SPMD path's uniform full-height panels + masked einsum
    trailing cost 1.1-2.3× on one chip (same trade as potrf/getrf
    dense paths); auto-on for accelerators from ``FAST_FROM_N``,
    SLATE_QR_FAST=1/0 forces/disables (tests force on CPU)."""
    import os
    flag = os.environ.get("SLATE_QR_FAST", "")
    if flag == "0":
        return False
    mtl, ntl = A.data.shape[2], A.data.shape[3]
    kt = min(A.mt, A.nt)
    exact = (A.grid.size == 1 and A.m == mtl * A.nb
             and A.n == ntl * A.nb and A.m >= A.n and kt <= 64)
    if not exact:
        return False
    if flag == "1":
        return True
    return (A.grid.devices[0].platform == "tpu" and A.n >= FAST_FROM_N)


def _blocked_T(G, taus, nb, base: int = 8):
    """Compact-WY T from the reflector Gram G = VᴴV and taus, built
    block-recursively: base-width T's via a (vmapped) larft-style
    column recurrence on G's diagonal blocks, then log₂(nb/base)
    pairwise combines T = [[T₁, −T₁·G₁₂·T₂], [0, T₂]] — all MXU
    matmuls on G blocks, no O(nb) sequential scan over full-height V
    (reference larft role; base=8 keeps the sequential recurrence to
    8 steps — the base=128 fori profiled at ~0.4 ms per call, ~12 ms
    of a 59 ms [16384,4096] factorization)."""
    # largest block width ≤ base with nb/bs a power of two (the
    # pairwise combine needs clean halving)
    bs = nb
    while bs > base and bs % 2 == 0:
        bs //= 2
    C = nb // bs
    Gd = jnp.stack([G[i * bs:(i + 1) * bs, i * bs:(i + 1) * bs]
                    for i in range(C)])              # [C, bs, bs]
    tv = taus.reshape(C, bs)

    def base_T(Gb, tb):
        T0 = jnp.zeros((bs, bs), G.dtype)

        def col(j, T):
            colmask = jnp.arange(bs) < j
            wj = jnp.where(colmask, Gb[:, j], jnp.zeros_like(Gb[:, j]))
            tcol = -tb[j] * (T @ wj)
            tcol = jnp.where(colmask, tcol,
                             jnp.zeros_like(tcol)).at[j].set(tb[j])
            return T.at[:, j].set(tcol)

        return lax.fori_loop(0, bs, col, T0)

    Ts = jax.vmap(base_T)(Gd, tv)                    # [C, bs, bs]
    size = bs
    while size < nb:
        C2 = Ts.shape[0] // 2
        T1 = Ts[0::2]                                # [C2, size, size]
        T2 = Ts[1::2]
        # G12 blocks: rows of block 2i, cols of block 2i+1
        g12 = jnp.stack([
            G[(2 * i) * size:(2 * i + 1) * size,
              (2 * i + 1) * size:(2 * i + 2) * size]
            for i in range(C2)])
        T12 = -jnp.einsum("cij,cjk,ckl->cil", T1, g12, T2)
        top = jnp.concatenate([T1, T12], axis=2)
        bot = jnp.concatenate([jnp.zeros_like(T12.transpose(0, 2, 1)),
                               T2], axis=2)
        Ts = jnp.concatenate([top, bot], axis=1)
        size *= 2
    return Ts[0]


def _geqrf_fast_core(A, panel_mode=None, tier=None):
    """Unrolled dense blocked QR (single device): per panel a
    Pallas Householder kernel (internal/panel_qr.py — or exact-shape
    XLA geqrf when the kernel doesn't apply) on the SHRINKING
    [m−k·nb, nb] column, the Gram-based blocked T, and the trailing
    update as three plain MXU matmuls A₂ −= V·(Tᴴ·(VᴴA₂)) — no masked
    full-height work, no per-column larft scan (reference geqrf.cc
    panel + unmqr trailing, on one chip)."""
    from ..matrix import tiles_to_dense, dense_to_tiles, bc_from_tiles
    from ..internal.tile_kernels import _factor_dtype, _geqrf
    from ..internal import panel_qr
    nb = A.nb
    m, n = A.m, A.n
    kt = min(A.mt, A.nt)
    fd = _factor_dtype(A.dtype)
    a = tiles_to_dense(A.data[0, 0], m, n).astype(fd)
    pk = trailing_dot_kwargs(tier, fd)
    Ts = []
    for k in range(kt):
        r0 = k * nb
        w = min(nb, n - r0)
        with jax.named_scope("qr_panel"):
            pan = a[r0:, r0:r0 + w]                  # [m-r0, w] exact
            if _panel_takes_kernel(panel_mode, fd, pan.shape[0], w):
                qr_, taus = panel_qr.qr_panel_blocked(
                    pan, interpret=(panel_mode == "interpret"))
            else:
                qr_, taus = _geqrf(pan)
            a = a.at[r0:, r0:r0 + w].set(qr_)
        with jax.named_scope("qr_T"):
            rows = jnp.arange(m - r0)[:, None]
            diag = jnp.arange(w)[None, :]
            V = jnp.where(rows > diag, qr_, jnp.zeros_like(qr_)) \
                + (rows == diag).astype(fd)
            G = jnp.conj(V.T) @ V
            # w == nb always here (the gate requires exact tile
            # multiples)
            T = _blocked_T(G, taus.astype(fd), w)
            Ts.append(T)
        if r0 + w < n:
            with jax.named_scope("qr_trailing"):
                C = a[r0:, r0 + w:]
                W1 = jnp.matmul(jnp.conj(V.T), C, **pk)  # [w, n-r0-w]
                W2 = jnp.conj(T).T @ W1
                a = a.at[r0:, r0 + w:].set(C - jnp.matmul(V, W2, **pk))
    Tst = jnp.stack(Ts).astype(A.dtype)
    tiles = dense_to_tiles(a.astype(A.dtype), nb, A.data.shape[2],
                           A.data.shape[3])
    return bc_from_tiles(tiles, 1, 1), Tst


_geqrf_fast_jit = cached_jit(_geqrf_fast_core, routine="geqrf.fast",
                             static_argnames=("panel_mode", "tier"))


@partial(cached_jit, static_argnames=("tier", "depth"))
def _geqrf_jit(A, tier=None, depth=0):
    """One-program SPMD blocked QR. ``depth`` ≥ 1 runs the DAG
    runtime's lookahead schedule (``runtime.dag.chunk_plan``): while
    step k's compact-WY trailing apply runs, panels k+1…k+depth are
    already factored and their all-gathers in flight, and step k's
    ``reflector_psum`` rides directly under the apply einsums — QR
    never had PR 10's hand-rolled lookahead, it gets the scheduler
    parameter form directly. Bitwise identical to depth 0 at every
    depth (the per-column compact-WY apply reads only that column).
    ``depth`` is static and part of the executable-cache key."""
    g = A.grid
    p, q, nb = g.p, g.q, A.nb
    m, n = A.m, A.n
    mt, nt = A.mt, A.nt
    kt = min(mt, nt)
    mtl, ntl = A.data.shape[2], A.data.shape[3]
    mt_p = mtl * p
    M = mt_p * nb
    cplx = jnp.issubdtype(A.dtype, jnp.complexfloating)
    pk = trailing_dot_kwargs(tier, A.dtype)

    def body(a):
        a = a[0, 0]
        r, c = comm.coords()
        gi = masks.local_tile_rows(mtl, p)
        gj = masks.local_tile_cols(ntl, q)

        # slatedag device track (see linalg/potrf.py)
        dev = r * q + c
        ndev = p * q

        def factor_panel(kk, a, Ts):
            """Gather + redundantly QR-factor panel kk, write it back,
            record T, and hand (V tiles, T) to the ring."""
            with jax.named_scope("qr_panel"):
                pcol = lax.dynamic_index_in_dim(a, kk // q, axis=1,
                                                keepdims=False)
                pcol = dag.mark(pcol, "panel_bcast", step=kk,
                                device=dev, edge="b", routine="geqrf",
                                ndev=ndev)
                full = comm.allgather_panel_rows(pcol, p, kk % q)
                panel2d = full.reshape(M, nb)
                panel2d, taus = panel_qr_factor(panel2d, kk * nb, m)
            with jax.named_scope("qr_T"):
                V = extract_v(panel2d, kk * nb, m)       # [M, nb]
                T = larft(V, taus)                       # [nb, nb]
                Ts = Ts.at[kk].set(T)
            ptiles = panel2d.reshape(mt_p, nb, nb)
            newcol = jnp.take(ptiles, gi, axis=0)
            a = jnp.where(
                c == kk % q,
                lax.dynamic_update_index_in_dim(a, newcol, kk // q,
                                                axis=1), a)
            return a, Ts, (V.reshape(mt_p, nb, nb), T)

        @jax.named_scope("qr_trailing")
        def col_advance(s, j, a, entry):
            """Step s's compact-WY apply on block column j only, from
            the ring buffer — element-for-element the slice of the big
            trailing apply that touches column j, scheduled early so
            panel j can factor (non-owner mesh columns compute junk
            that the final ``where`` masks out, like getrf's column
            advance)."""
            vt, T = entry
            vloc = jnp.take(vt, gi, axis=0)
            acol = lax.dynamic_index_in_dim(a, j // q, axis=1,
                                            keepdims=False)
            w1 = jnp.einsum("aiv,aij->vj", jnp.conj(vloc), acol, **pk)
            w1 = comm.psum_rows(w1)                      # [nb, nb]
            tw = jnp.einsum("uv,vj->uj", jnp.conj(T).T, w1)
            upd = jnp.einsum("aiv,vj->aij", vloc, tw, **pk)
            return jnp.where(
                c == j % q,
                lax.dynamic_update_index_in_dim(a, acol - upd, j // q,
                                                axis=1), a)

        @jax.named_scope("qr_trailing")
        def trailing(k, a, entry, jlo):
            """Step k's big trailing apply A₂ −= V·Tᴴ·(Vᴴ·A₂) on
            columns > jlo, from the ring buffer."""
            vt, T = entry
            vloc = jnp.take(vt, gi, axis=0)              # [mtl, nb, nb]
            right = (gj > jlo) & (gj < nt)
            amask = jnp.where(right[None, :, None, None], a,
                              jnp.zeros_like(a))
            w = jnp.einsum("aiv,abij->bvj", jnp.conj(vloc), amask, **pk)
            w = dag.mark(w, "reflector_psum", step=k, device=dev,
                         edge="b", routine="geqrf", ndev=ndev)
            w = comm.psum_rows(w)                      # [ntl, nb, nb]
            w = dag.mark(w, "reflector_psum", step=k, device=dev,
                         edge="e", routine="geqrf", ndev=ndev)
            # Qᴴ block: (I − V·T·Vᴴ)ᴴ = I − V·Tᴴ·Vᴴ  ⇒ coeff = Tᴴ
            tw = jnp.einsum("uv,bvj->buj", jnp.conj(T).T, w)
            tw = dag.mark(tw, "trailing", step=k, device=dev, edge="b",
                          routine="geqrf", ndev=ndev)
            upd = jnp.einsum("aiv,bvj->abij", vloc, tw, **pk)
            a = a - jnp.where(right[None, :, None, None], upd,
                              jnp.zeros_like(upd))
            return dag.mark(a, "trailing", step=k, device=dev,
                            edge="e", routine="geqrf", ndev=ndev)

        Ts0 = jnp.zeros((kt, nb, nb), A.dtype)

        if depth < 1:
            # sequential: factor panel k, apply it to columns > k
            def step(k, carry):
                a, Ts = carry
                a = dag.mark(a, "step", step=k, device=dev, edge="b",
                             routine="geqrf", ndev=ndev)
                a, Ts, entry = factor_panel(k, a, Ts)
                entry = (dag.mark(entry[0], "panel_bcast", step=k,
                                  device=dev, edge="e",
                                  routine="geqrf", ndev=ndev),
                         entry[1])
                a = trailing(k, a, entry, k)
                a = dag.mark(a, "step", step=k, device=dev, edge="e",
                             routine="geqrf", ndev=ndev)
                return a, Ts

            a, Ts = lax.fori_loop(0, kt, step, (a, Ts0))
            return a[None, None], Ts

        # ---- pipelined: the plan-driven lookahead schedule ----------
        plan = dag.chunk_plan("geqrf", 0, kt, depth)
        d = plan.d_eff
        ep0 = kt - d
        k_last = kt - 1

        # prologue: fill the ring — factor panel 0, then bring each
        # column t < d up to date column-locally and factor it
        Ts = Ts0
        ring = ()
        for op in plan.prologue:
            if op[0] == "factor":
                a, Ts, fresh = factor_panel(op[1], a, Ts)
                ring = ring + (fresh,)
            else:                                # ("advance", j, srcs)
                for s in op[2]:
                    a = col_advance(s, op[1], a, ring[s])

        def step(k, carry):
            a, Ts, ring = carry
            fresh = None
            a = dag.mark(a, "step", step=k, device=dev, edge="b",
                         routine="geqrf", ndev=ndev)
            for op in plan.body:
                if op[0] == "consume":
                    vt0 = dag.mark(ring[0][0], "panel_bcast", step=k,
                                   device=dev, edge="e",
                                   routine="geqrf", ndev=ndev)
                    ring = ((vt0, ring[0][1]),) + ring[1:]
                elif op[0] == "advance":
                    j = k + op[1]
                    for t in op[2]:
                        a = col_advance(k + t, j, a, ring[t])
                elif op[0] == "factor":
                    a, Ts, fresh = factor_panel(k + op[1], a, Ts)
                else:                            # ("trailing", 0, d)
                    a = trailing(k + op[1], a, ring[0],
                                 k + op[1] + op[2])
            a = dag.mark(a, "step", step=k, device=dev, edge="e",
                         routine="geqrf", ndev=ndev)
            return a, Ts, ring[1:] + (fresh,)

        a, Ts, ring = lax.fori_loop(plan.body_lo, plan.body_hi, step,
                                    (a, Ts, ring))

        # epilogue: drain the ring — every in-range column already
        # advanced, so the applies touch only columns beyond k_last
        for op in plan.epilogue:
            k = op[1]
            if op[0] == "consume":
                a = dag.mark(a, "step", step=k, device=dev, edge="b",
                             routine="geqrf", ndev=ndev)
                slot = k - ep0
                vt0 = dag.mark(ring[slot][0], "panel_bcast", step=k,
                               device=dev, edge="e", routine="geqrf",
                               ndev=ndev)
                ring = ring[:slot] + ((vt0, ring[slot][1]),) \
                    + ring[slot + 1:]
            else:                                # ("trailing", k, None)
                a = trailing(k, a, ring[k - ep0], k_last)
                a = dag.mark(a, "step", step=k, device=dev, edge="e",
                             routine="geqrf", ndev=ndev)
        return a[None, None], Ts

    data, T = jax.shard_map(
        body, mesh=g.mesh, in_specs=(P(AXIS_P, AXIS_Q),),
        out_specs=(P(AXIS_P, AXIS_Q), P()), check_vma=False)(A.data)
    return data, T


def unmqr(side: Side, trans: Op, QR: Matrix, T, C: Matrix, opts=None):
    """C ← op(Q)·C or C·op(Q) from geqrf factors (src/unmqr.cc).

    op(Q)·C applies the panel reflectors H_k = I − V_k·T_k·V_kᴴ:
    Q·C in reverse panel order with T, Qᴴ·C in forward order with Tᴴ;
    C·Q forward with T, C·Qᴴ in reverse with Tᴴ — both sides native
    (no transpose materialization; trans ∈ {NoTrans, ConjTrans}, like
    LAPACK unmqr).
    """
    if trans == Op.Trans:
        # real dtypes: 'T' ≡ 'C' (LAPACK dormqr accepts 'T'); complex
        # rejects it like cunmqr
        slate_error_if(jnp.issubdtype(QR.dtype, jnp.complexfloating),
                       "unmqr: trans must be NoTrans or ConjTrans for "
                       "complex types (LAPACK cunmqr semantics)")
        trans = Op.ConjTrans
    if side == Side.Right:
        # native right apply: C ← C − (C·V_k)·op(T_k)·V_kᴴ, forward
        # panel order for C·Q, reverse for C·Qᴴ — the mirrored einsum
        # chain of the Left core (reference src/unmqr.cc right-side
        # task graph); no conj-transpose materialization round-trips.
        with trace.block("unmqr_right"):
            return _unmqr_right_jit(QR, T, C, trans == Op.NoTrans)
    with trace.block("unmqr"):
        return _unmqr_jit(QR, T, C, trans == Op.NoTrans)


@partial(cached_jit, static_argnames=("notrans",))
def _unmqr_jit(QR, T, C, notrans):
    g = C.grid
    p, q, nb = g.p, g.q, QR.nb
    m = QR.m
    mt, nt_qr = QR.mt, QR.nt
    kt = T.shape[0]
    mtl, ntl = C.data.shape[2], C.data.shape[3]
    mtl_qr = QR.data.shape[2]
    mt_p = mtl_qr * p
    M = mt_p * nb

    def body(aq, cdat, T):
        aq, cdat = aq[0, 0], cdat[0, 0]
        r, c = comm.coords()
        gi = masks.local_tile_rows(mtl, p)
        gj = masks.local_tile_cols(ntl, q)

        @jax.named_scope("unmqr_apply")
        def apply_one(k, cdat):
            pcol = lax.dynamic_index_in_dim(aq, k // q, axis=1,
                                            keepdims=False)
            full = comm.allgather_panel_rows(pcol, p, k % q)
            panel2d = full.reshape(M, nb)
            V = extract_v(panel2d, k * nb, m)
            vt = V.reshape(mt_p, nb, nb)
            vloc = jnp.take(vt, gi, axis=0)
            Tk = T[k]
            Top = Tk if notrans else jnp.conj(Tk).T     # T or Tᴴ
            w = jnp.einsum("aiv,abij->bvj", jnp.conj(vloc), cdat)
            w = comm.psum_rows(w)
            tw = jnp.einsum("uv,bvj->buj", Top, w)
            upd = jnp.einsum("aiv,bvj->abij", vloc, tw)
            return cdat - upd

        if notrans:
            cdat = lax.fori_loop(0, kt,
                                 lambda t, x: apply_one(kt - 1 - t, x), cdat)
        else:
            cdat = lax.fori_loop(0, kt, apply_one, cdat)
        return cdat[None, None]

    data = jax.shard_map(
        body, mesh=g.mesh,
        in_specs=(P(AXIS_P, AXIS_Q), P(AXIS_P, AXIS_Q), P()),
        out_specs=P(AXIS_P, AXIS_Q), check_vma=False)(QR.data, C.data, T)
    return C._replace(data=data)


@partial(cached_jit, static_argnames=("notrans",))
def _unmqr_right_jit(QR, T, C, notrans):
    """C·Q (forward order, coeff T) or C·Qᴴ (reverse order, coeff Tᴴ):
    w = C·V is a local einsum contracting C's column tiles against V's
    row tiles + one psum across mesh columns; the outer product is
    local — two collectives per panel, the mirror of _unmqr_jit."""
    g = C.grid
    p, q, nb = g.p, g.q, QR.nb
    m = QR.m
    kt = T.shape[0]
    mtl, ntl = C.data.shape[2], C.data.shape[3]
    mtl_qr = QR.data.shape[2]
    mt_p = mtl_qr * p
    M = mt_p * nb

    def body(aq, cdat, T):
        aq, cdat = aq[0, 0], cdat[0, 0]
        gj = masks.local_tile_cols(ntl, q)
        gj_clip = jnp.clip(gj, 0, mt_p - 1)

        @jax.named_scope("unmqr_apply")
        def apply_one(k, cdat):
            pcol = lax.dynamic_index_in_dim(aq, k // q, axis=1,
                                            keepdims=False)
            full = comm.allgather_panel_rows(pcol, p, k % q)
            panel2d = full.reshape(M, nb)
            V = extract_v(panel2d, k * nb, m)
            vt = V.reshape(mt_p, nb, nb)
            # padding col tiles of C beyond V's padded rows must see a
            # ZERO V block (the clip would alias them onto a real one)
            vcols = jnp.where((gj < mt_p)[:, None, None],
                              jnp.take(vt, gj_clip, axis=0),
                              0.0)                       # [ntl, nb, nb]
            Tk = T[k]
            Top = Tk if notrans else jnp.conj(Tk).T      # T or Tᴴ
            w = jnp.einsum("abij,bjv->aiv", cdat, vcols)
            w = comm.psum_cols(w)                      # [mtl, nb, nb]
            tw = jnp.einsum("aiv,vu->aiu", w, Top)
            upd = jnp.einsum("aiu,bju->abij", tw, jnp.conj(vcols))
            return cdat - upd

        if notrans:                                      # C·Q: forward
            cdat = lax.fori_loop(0, kt, apply_one, cdat)
        else:                                            # C·Qᴴ: reverse
            cdat = lax.fori_loop(
                0, kt, lambda t, x: apply_one(kt - 1 - t, x), cdat)
        return cdat[None, None]

    data = jax.shard_map(
        body, mesh=g.mesh,
        in_specs=(P(AXIS_P, AXIS_Q), P(AXIS_P, AXIS_Q), P()),
        out_specs=P(AXIS_P, AXIS_Q), check_vma=False)(QR.data, C.data, T)
    return C._replace(data=data)


def gelqf(A: Matrix, opts=None):
    """LQ: A = L·Q via QR of Aᴴ (reference src/gelqf.cc uses dedicated
    ttlqt kernels; the transpose reduction is numerically identical)."""
    Ah = conj_transpose(A).materialize()
    QR, T = geqrf(Ah, opts)
    return QR, T


def unmlq(side: Side, trans: Op, LQ: Matrix, T, C: Matrix, opts=None):
    """Apply Q from gelqf (src/unmlq.cc): Q_lq = (Q_qr)ᴴ."""
    flip = Op.NoTrans if trans != Op.NoTrans else Op.ConjTrans
    return unmqr(side, flip, LQ, T, C, opts)


def cholqr(A: Matrix, opts=None):
    """Cholesky QR (reference src/cholqr.cc): R = chol(AᴴA) upper;
    Q = A·R⁻¹. Returns (Q, R, info)."""
    from ..ops.blas import herk, trsm
    from ..matrix import HermitianMatrix
    from .potrf import potrf
    with trace.block("cholqr"):
        Cg = HermitianMatrix.zeros(A.n, A.n, A.nb, A.grid, dtype=A.dtype,
                                   uplo=Uplo.Lower)
        # AᴴA via rank-k: (Aᴴ)(Aᴴ)ᴴ with the conj-transpose view
        Cg = herk(1.0, conj_transpose(A), 0.0, Cg)
        L, info = potrf(Cg, opts)
        # A·L⁻ᴴ = Q;  R = Lᴴ (upper)
        Q = trsm(Side.Right, 1.0, conj_transpose(L), A, opts)
        R = conj_transpose(L).materialize()
        R = TriangularMatrix(data=R.data, m=A.n, n=A.n, nb=A.nb,
                             grid=A.grid, uplo=Uplo.Upper, diag=Diag.NonUnit)
    return Q, R, info


def gels(A: Matrix, BX: Matrix, opts=None):
    """Least squares (reference src/gels.cc dispatch → gels_qr.cc /
    gels_cholqr.cc). Overdetermined m ≥ n: min‖AX − B‖₂ via QR/CholQR.
    Underdetermined m < n: the minimum-norm solution via LQ
    (A = L·Q ⇒ X = Qᴴ·L⁻¹·B), like the reference's gels_qr LQ branch.
    Returns the [n, nrhs] solution X.

    ``Option.MethodGels``: ``Geqrf`` is LAPACK's ``gels`` (Householder
    QR: ``geqrf``, ``unmqr`` Qᴴ·B, ``trsm`` with R; backward stable for
    any full-rank A). ``Auto`` takes **CholQR** whenever m ≥ 2n, as the
    reference does (``herk`` AᴴA, ``potrf``, a right ``trsm``, ``gemm``,
    ``trsm``: none of the QR family runs). CholQR factors AᴴA, whose
    condition is κ(A)²: in f32 ``potrf`` fails (or the answer holds no
    digit) from κ(A) ≈ ε^-1/2 ≈ 4096 up, and below that the error grows
    as κ(A)²·ε where Householder QR's grows as κ(A)·ε. A caller who
    does not know κ(A) to be small passes ``MethodGels.Geqrf``.

    What a call reports (docs/observability.md): the spans
    :data:`SPANS` (a root ``slate.gels`` with ``routine``, ``m``, ``n``,
    ``nrhs``, ``nb``, ``grid``, ``method``, ``tier``; at its end, on
    the Householder branches, ``program`` and ``panel``, what answered
    ``geqrf``, and ``tier`` as its driver resolved it) and the counters
    :data:`COUNTERS`."""
    from ..ops.blas import trsm
    wide = A.m < A.n
    method = (MethodGels.Geqrf if wide
              else MethodGels.select_algo(A, BX, opts))
    obs.count("gels.method", 1, method=method.name)
    with trace.block("slate.gels", routine="gels", m=A.m, n=A.n,
                     nrhs=BX.n, nb=A.nb, grid=f"{A.grid.p}x{A.grid.q}",
                     method=method.name, tier=resolve_tier(opts)) as root:
        if wide:
            with trace.block("gels_lq"):
                with trace.block("gels.factor"):
                    # gelqf: QR factors of Aᴴ [n, m]
                    LQ, T, chosen = _geqrf_chosen(conj_transpose(A),
                                                  opts)
                    root.label(**chosen)
                with trace.block("gels.solve_r"):
                    Rh = _upper_view(LQ)    # R̂ (m×m upper): A = R̂ᴴ·Q̂ᴴ
                    Y = trsm(Side.Left, 1.0, conj_transpose(Rh), BX,
                             opts)
                with trace.block("gels.apply_q"):
                    Ypad = _pad_rows(Y, A.n)        # [y; 0] in n rows
                    return unmqr(Side.Left, Op.NoTrans, LQ, T, Ypad,
                                 opts)
        if method == MethodGels.Cholqr:
            with trace.block("gels.factor"):
                Q, R, info = cholqr(A, opts)
            # X = R⁻¹·(Qᴴ B)
            with trace.block("gels.apply_q"):
                QhB = _gemm_qhb(Q, BX)
            with trace.block("gels.solve_r"):
                return trsm(Side.Left, 1.0, R, QhB, opts)
        with trace.block("gels.factor"):
            QR, T, chosen = _geqrf_chosen(A, opts)
            root.label(**chosen)
        with trace.block("gels.apply_q"):
            QhB = unmqr(Side.Left, Op.ConjTrans, QR, T, BX, opts)
        with trace.block("gels.solve_r"):
            R = _upper_view(QR)
            Xfull = _top_rows(QhB, A.n)
            return trsm(Side.Left, 1.0, R, Xfull, opts)


def _gemm_qhb(Q: Matrix, B: Matrix) -> Matrix:
    from ..ops.blas import gemm
    C = Matrix.zeros(Q.n, B.n, Q.nb, Q.grid, dtype=B.dtype)
    return gemm(1.0, conj_transpose(Q), B, 0.0, C)


def _upper_view(QR: Matrix) -> TriangularMatrix:
    """Top-left n×n upper triangle of the QR result."""
    ntR = cdiv(QR.n, QR.nb)
    sub = QR.sub(0, ntR - 1, 0, ntR - 1)
    return TriangularMatrix(data=sub.data, m=QR.n, n=QR.n, nb=QR.nb,
                            grid=QR.grid, uplo=Uplo.Upper, diag=Diag.NonUnit)


def _top_rows(B: Matrix, n: int) -> Matrix:
    """First n rows of B as a re-laid-out matrix."""
    ntR = cdiv(n, B.nb)
    sub = B.sub(0, ntR - 1, 0, B.nt - 1)
    return Matrix(data=sub.data, m=n, n=B.n, nb=B.nb, grid=B.grid)


def _pad_rows(B: Matrix, m_new: int) -> Matrix:
    """B extended with zero rows to m_new (B's padding is zero by the
    storage invariant, so only new tile rows are appended)."""
    return _pad_rows_jit(B.materialize(), m_new)


@partial(cached_jit, static_argnames=("m_new",))
def _pad_rows_jit(B, m_new):
    from ..matrix import bc_to_tiles, bc_from_tiles
    g = B.grid
    tiles = bc_to_tiles(B.data)
    mt_p_new = cdiv(cdiv(m_new, B.nb), g.p) * g.p
    pad = mt_p_new - tiles.shape[0]
    if pad > 0:
        tiles = jnp.pad(tiles, ((0, pad), (0, 0), (0, 0), (0, 0)))
    else:
        tiles = tiles[:mt_p_new]
    data = bc_from_tiles(tiles, g.p, g.q)
    data = jax.lax.with_sharding_constraint(data, g.sharding())
    return Matrix(data=data, m=m_new, n=B.n, nb=B.nb, grid=g)


def san_cases(grid, opts=None, n=64, nb=16):
    """slatesan sweep entry: (label, thunk) pairs running this
    driver's jitted surface once at a small shape on ``grid`` (see
    tools/slatesan; armed by SLATE_TPU_SAN=1 + an armed store)."""
    import numpy as np

    def run():
        rng = np.random.default_rng(12)
        a = rng.standard_normal((n, n)).astype(np.float32)
        A = Matrix.from_dense(a, nb=nb, grid=grid)
        QR, T = geqrf(A, opts=opts)
        return QR.data.block_until_ready()
    return [("geqrf", run)]
