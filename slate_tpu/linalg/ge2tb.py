"""Two-stage SVD, stage 1: ge2tb (general → triangular band) with its
back-transforms, and the full two-stage gesvd pipeline.

Reference: src/ge2tb.cc (585 LoC), src/tb2bd.cc (378, bulge chasing),
src/bdsqr.cc, wired in src/gesvd.cc:77-102; back-transforms
unmbr_ge2tb / unmbr_tb2bd.

TPU redesign — one jitted program, ``_ge2tb_jit``, with two bodies
chosen from the operand's shape (``_program``). On a grid, or with a
ragged edge, a ``shard_map`` fori-loop alternating:

* **QR panel** on block column k (rows ≥ k·nb): XLA-native geqrf on
  the gathered panel; compact-WY left update of the trailing columns
  A ← A − V·Tᴴ·(Vᴴ·A)  (one psum down mesh rows per panel).
* **LQ panel** on block row k (cols ≥ (k+1)·nb): the row panel is
  gathered along mesh columns, conj-transposed, and factored with the
  same geqrf kernel; right update A ← A − (A·V)·T·Vᴴ (one psum across
  mesh columns; the W stays row-local — no gather needed).

On one device at whole tiles (m ≥ n) the same two half-steps run on
what is left of the matrix (``_ge2tb_exact``): stages of ``STAGE``
steps, each a fori-loop on the window its first step owns, panels at
the window's height, no mask on the matrix and no collective.

Either way the result is an upper triangular band of width nb+1 (diagonal blocks
upper-triangular, superdiagonal blocks lower-triangular) with the QR
reflectors stored below the diagonal and the LQ reflectors right of
the superdiagonal — LAPACK gebrd's in-place convention at block scale.

Stage 2 (band → bidiagonal) is ``tb2bd``: the band is gathered to the
host (2·nt tiles; the reference gathers it to rank 0 and chases there,
SURVEY §3.5) and chased by the ``robust.ladder`` rung that takes it, on
a TPU in f32 the VMEM-resident Pallas kernel
(``internal/band_wave_vmem_bd.py``). Stage 3 is the SVD of the
bidiagonal: with vectors ``bulge.bdsdc``, the divide & conquer of
``linalg/stedc.py`` on the Golub-Kahan form of order 2n with Z, the
secular solves and the merge products on the device and U_B, V_B cut
out of Z there; ``bulge.bdsqr`` on the host for values alone and for a
B that is rank deficient to working precision. The two back-transforms
a side (``unmbr_tb2bd`` = ``bulge.apply_bulge_reflectors``,
``unmbr_ge2tb_u`` / ``_v``) run on the device, the second distributed,
on operands made where they lie. ``gesvd_two_stage`` strings them
together under ``svd.gesvd``'s root span ``slate.gesvd``, as the spans
``gesvd.stage1`` (``ge2tb``), ``gesvd.gather``, ``gesvd.stage2``
(``tb2bd``), ``gesvd.bidiag``, ``gesvd.back.tb2bd.u`` / ``.v`` and
``gesvd.back.ge2tb.u`` / ``.v`` (docs/observability.md).
``Option.TrailingPrecision`` reaches the trailing products of ``ge2tb``
alone.
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..cache.jitcache import cached_jit
from ..grid import AXIS_P, AXIS_Q
from ..matrix import Matrix, cdiv
from ..types import Op
from ..errors import slate_error_if
from ..internal import comm, masks
from ..internal.precision import resolve_tier, trailing_dot_kwargs
from ..internal.tile_kernels import (panel_qr_factor, extract_v, larft,
                                     _factor_dtype)
from ..utils import trace
from .. import obs
from .geqrf import _blocked_T


def ge2tb(A: Matrix, opts=None):
    """Reduce A (m ≥ n) to upper triangular band: A = U·B·Vᴴ.
    Returns (Aout, Tq, Tl): Aout stores the band + both reflector
    sets in place; Tq [nt, nb, nb], Tl [nt-1, nb, nb]. The program is
    chosen from the operand's shape (``_program``) and said on the span
    (``program``; ``panel``: XLA's geqrf in both, the Pallas Householder
    kernel read no faster at the gesvd cell and cost 25 s of set-up,
    PERF.md section 6, PR 49) and in the counter
    ``ge2tb.path{program}``."""
    slate_error_if(A.m < A.n, "ge2tb v1 expects m >= n")
    A = A.materialize()
    tier = resolve_tier(opts)
    program = _program(A)
    obs.count("ge2tb.path", 1, program=program)
    with trace.block("ge2tb", routine="ge2tb", m=A.m, n=A.n, nb=A.nb,
                     precision=tier, program=program, panel="xla"):
        data, Tq, Tl = _ge2tb_jit(A, tier)
    return A._replace(data=data), Tq, Tl


# steps a stage of the one-chip program: a stage is one ``fori_loop`` on
# the window its first step owns, so its panels and products have one
# shape (eight loop bodies where the ``gesvd_12288x8192_vec_1x1`` cell
# has 127 panels; 46 % of the SPMD body's tiles summed over the call,
# where a window a step would be 40 % and was 546 MB of code and 275 s
# of compile, PERF.md section 6, PR 49)
STAGE = 8


def _program(A) -> str:
    """``exact``: the one-chip program on what is left of the matrix
    (``_ge2tb_exact``), for an operand on one device whose m and n are
    whole tiles, m ≥ n; ``spmd``: the ``shard_map`` loop of uniform
    full-height panels under masks, which a block-cyclic grid and a
    ragged edge need. Read off the operand alone, on every platform."""
    mtl, ntl = A.data.shape[2], A.data.shape[3]
    exact = (A.grid.size == 1 and A.m == mtl * A.nb
             and A.n == ntl * A.nb and A.m >= A.n)
    return "exact" if exact else "spmd"


# a function inside _ge2tb_jit's program, never a program of its own:
# jitted so that the T, one shape for every panel, is traced and lowered
# once and not once a stage and a side (0.1 s each, in every process's
# set-up)
# slatelint: disable-next-line=SL009 -- inside _ge2tb_jit's program
_panel_T = jax.jit(_blocked_T, static_argnums=(2,))


def _panel_step(pan, d0):
    """One panel of the one-chip program, its diagonal at the traced
    row ``d0`` (the rows above ride along untouched): (factored panel,
    V, T) by XLA's geqrf on the rows from ``d0`` (``panel_qr_factor``),
    V the unit lower trapezoid and T from its Gram matrix as
    ``_geqrf_fast_core`` builds it (``geqrf._blocked_T``: no per-column
    scan over V)."""
    h, nb = pan.shape
    qr_, taus = panel_qr_factor(pan, d0, h)
    V = extract_v(qr_, d0, h)
    return qr_, V, _panel_T(jnp.conj(V.T) @ V, taus, nb)


def _ge2tb_exact(A, tier):
    """ge2tb on one chip, on what is left of the matrix. Steps run in
    stages of ``STAGE``: a stage is one ``fori_loop`` on the window
    ``a[s0:, s0:]`` its first step owns. Step k of it factors the
    window's column at the window's height with the diagonal's row
    handed to the panel step (``_panel_step``) and the conj-transposed
    row likewise, and updates the window alone, each side as three
    plain matmuls around the Gram-built T. What a step no longer owns
    inside its stage's window is kept by zeroing those columns (rows)
    of the thin factor W, [nb, width] or [height, nb]: no mask on the
    matrix, no gathered full-height panel, no work outside the window
    (the SPMD body's uniform shapes cost 2.6× the products on one
    chip). The last QR panel has no trailing window and is factored at
    its own height. Tiles → dense → tiles inside the program: nothing
    but the three outputs outlives it."""
    from ..matrix import tiles_to_dense, dense_to_tiles, bc_from_tiles
    nb, m, n, nt = A.nb, A.m, A.n, A.nt
    fd = _factor_dtype(A.dtype)
    pk = trailing_dot_kwargs(tier, fd)
    a = tiles_to_dense(A.data[0, 0], m, n).astype(fd)

    def step(j, carry, k0):
        """Step k0 + j on its stage's window: QR of the column at
        r = j·nb, LQ of the row there."""
        win, Tq, Tl = carry
        h, w = win.shape
        r, past = j * nb, (j + 1) * nb
        with jax.named_scope("qr_panel"):
            pan, V, T = _panel_step(
                lax.dynamic_slice(win, (0, r), (h, nb)), r)
            win = lax.dynamic_update_slice(win, pan, (0, r))
            Tq = Tq.at[k0 + j].set(T)
        with jax.named_scope("qr_trailing"):
            # Qᴴ·C = C − V·Tᴴ·(Vᴴ·C), on the columns past the panel
            W = jnp.conj(T).T @ jnp.matmul(jnp.conj(V.T), win, **pk)
            W = jnp.where(jnp.arange(w) >= past, W, jnp.zeros_like(W))
            win = win - jnp.matmul(V, W, **pk)
        with jax.named_scope("lq_panel"):
            # the row, conj-transposed into a column panel over the
            # window's columns: its QR is the row's LQ
            row = lax.dynamic_slice(win, (r, 0), (nb, w))
            pan, V, T = _panel_step(jnp.conj(row.T), past)
            win = lax.dynamic_update_slice(win, jnp.conj(pan.T), (r, 0))
            Tl = Tl.at[k0 + j].set(T)
        with jax.named_scope("lq_trailing"):
            # C·Q = C − (C·V)·T·Vᴴ, on the rows past the panel
            W = jnp.matmul(win, V, **pk) @ T
            W = jnp.where(jnp.arange(h)[:, None] >= past, W,
                          jnp.zeros_like(W))
            win = win - jnp.matmul(W, jnp.conj(V.T), **pk)
        return win, Tq, Tl

    Tq = jnp.zeros((nt, nb, nb), fd)
    Tl = jnp.zeros((max(nt - 1, 1), nb, nb), fd)
    for k0 in range(0, nt - 1, STAGE):
        s0 = k0 * nb
        win, Tq, Tl = lax.fori_loop(
            0, min(STAGE, nt - 1 - k0), partial(step, k0=k0),
            (a[s0:, s0:], Tq, Tl))
        a = a.at[s0:, s0:].set(win)
    with jax.named_scope("qr_panel"):
        r0 = (nt - 1) * nb
        pan, _, T = _panel_step(a[r0:, r0:], 0)
        a = a.at[r0:, r0:].set(pan)
        Tq = Tq.at[nt - 1].set(T)
    tiles = dense_to_tiles(a.astype(A.dtype), nb, A.data.shape[2],
                           A.data.shape[3])
    return (bc_from_tiles(tiles, 1, 1), Tq.astype(A.dtype),
            Tl.astype(A.dtype))


@partial(cached_jit, static_argnames=("tier",))
def _ge2tb_jit(A, tier=None):
    """Two bodies under one name, chosen from the operand's shape
    (``_program``): ``_ge2tb_exact`` on one chip at whole tiles, the
    ``shard_map`` loop below for everything else. ``tier``
    (``Option.TrailingPrecision``) reaches the trailing products alone
    (``qr_trailing``, ``lq_trailing``); the panels and their T factors
    stay at the package default."""
    if _program(A) == "exact":
        return _ge2tb_exact(A, tier)
    g = A.grid
    p, q, nb = g.p, g.q, A.nb
    m, n = A.m, A.n
    mt, nt = A.mt, A.nt
    mtl, ntl = A.data.shape[2], A.data.shape[3]
    mt_p, nt_p = mtl * p, ntl * q
    Nr = mt_p * nb            # padded row space
    Nc = nt_p * nb            # padded col space
    kq = nt                   # QR panels
    kl = max(nt - 1, 0)       # LQ panels
    pk = trailing_dot_kwargs(tier, A.dtype)

    def body(a):
        a = a[0, 0]
        r, c = comm.coords()
        gi = masks.local_tile_rows(mtl, p)
        gj = masks.local_tile_cols(ntl, q)
        gi_clip = jnp.clip(gi, 0, nt_p - 1)

        def qr_step(k, a, Ts):
            """Left reduction of column k (reference ge2tb QR half)."""
            with jax.named_scope("qr_panel"):
                pcol = lax.dynamic_index_in_dim(a, k // q, axis=1,
                                                keepdims=False)
                full = comm.allgather_panel_rows(pcol, p, k % q)
                panel2d = full.reshape(Nr, nb)
                panel2d, taus = panel_qr_factor(panel2d, k * nb, m)
                V = extract_v(panel2d, k * nb, m)
                T = larft(V, taus)
                Ts = Ts.at[k].set(T)
                ptiles = panel2d.reshape(mt_p, nb, nb)
                newcol = jnp.take(ptiles, gi, axis=0)
                a = jnp.where(
                    c == k % q,
                    lax.dynamic_update_index_in_dim(a, newcol, k // q,
                                                    axis=1),
                    a)
            with jax.named_scope("qr_trailing"):
                vt = V.reshape(mt_p, nb, nb)
                vloc = jnp.take(vt, gi, axis=0)
                right = (gj > k) & (gj < nt)
                amask = jnp.where(right[None, :, None, None], a,
                                  jnp.zeros_like(a))
                w = jnp.einsum("aiv,abij->bvj", jnp.conj(vloc), amask,
                               **pk)
                w = comm.psum_rows(w)
                tw = jnp.einsum("uv,bvj->buj", jnp.conj(T).T, w, **pk)
                upd = jnp.einsum("aiv,bvj->abij", vloc, tw, **pk)
                a = a - jnp.where(right[None, :, None, None], upd,
                                  jnp.zeros_like(upd))
            return a, Ts

        def lq_step(k, a, Ts):
            """Right reduction of row k (reference ge2tb LQ half).
            Row panel tiles (k, j), j ≥ k+1, conj-transposed into a
            column panel over the col-index space, then geqrf."""
            start = (k + 1) * nb
            with jax.named_scope("lq_panel"):
                prow = lax.dynamic_index_in_dim(
                    a, k // p, axis=0, keepdims=False)   # [ntl,nb,nb]
                # gather along mesh cols; mask to owner row
                prow = jnp.where(r == k % p, prow, jnp.zeros_like(prow))
                prow = comm.psum_rows(prow)
                fullrow = comm.allgather_cyclic(prow, q, AXIS_Q)
                # conj-transpose the row block [nt_p,nb,nb] into
                # column-panel form: row i of the panel = global col i
                panel2d = jnp.conj(
                    fullrow.transpose(0, 2, 1)).reshape(Nc, nb)
                panel2d, taus = panel_qr_factor(panel2d, start, n)
                V = extract_v(panel2d, start, n)         # [Nc, nb]
                T = larft(V, taus)
                Ts = Ts.at[k].set(T)
                # write the factored panel back into row k
                # (conj-transpose back)
                ptiles = jnp.conj(panel2d.reshape(nt_p, nb, nb)
                                  .transpose(0, 2, 1))  # [nt_p, nb, nb]
                newrow = jnp.take(ptiles, gj, axis=0)
                a = jnp.where(
                    r == k % p,
                    lax.dynamic_update_index_in_dim(a, newrow, k // p,
                                                    axis=0),
                    a)
            # right update of trailing rows: A ← A − (A·V)·T·Vᴴ
            with jax.named_scope("lq_trailing"):
                vt = V.reshape(nt_p, nb, nb)
                vcols = jnp.take(vt, gj, axis=0)         # [ntl, nb, nb]
                below = (gi > k) & (gi < mt)
                amask = jnp.where(below[:, None, None, None], a,
                                  jnp.zeros_like(a))
                w2 = jnp.einsum("abij,bjv->aiv", amask, vcols, **pk)
                w2 = comm.psum_cols(w2)            # [mtl, nb, nb] rows
                w2t = jnp.einsum("aiv,vu->aiu", w2, T, **pk)
                upd = jnp.einsum("aiu,bju->abij", w2t, jnp.conj(vcols),
                                 **pk)
                a = a - jnp.where(below[:, None, None, None], upd,
                                  jnp.zeros_like(upd))
            return a, Ts

        def step(k, carry):
            a, Tq, Tl = carry
            a, Tq = qr_step(k, a, Tq)
            if kl > 0:
                do_lq = k < kl
                a2, Tl2 = lq_step(jnp.minimum(k, kl - 1), a, Tl)
                a = jnp.where(do_lq, a2, a)
                Tl = jnp.where(do_lq, Tl2, Tl)
            return a, Tq, Tl

        Tq0 = jnp.zeros((kq, nb, nb), A.dtype)
        Tl0 = jnp.zeros((max(kl, 1), nb, nb), A.dtype)
        a, Tq, Tl = lax.fori_loop(0, kq, step, (a, Tq0, Tl0))
        return a[None, None], Tq, Tl

    data, Tq, Tl = jax.shard_map(
        body, mesh=g.mesh, in_specs=(P(AXIS_P, AXIS_Q),),
        out_specs=(P(AXIS_P, AXIS_Q), P(), P()), check_vma=False)(A.data)
    return data, Tq, Tl


def ge2tb_gather(Aout: Matrix) -> np.ndarray:
    """Gather the (nb+1)-wide upper band to host compact storage
    ``ub[d, j] = A[j, j+d]``, d = 0..nb (reference ge2tbGather analog)
    — fetches only the 2·nt band tiles, never the dense matrix."""
    from .bulge import gather_band_upper
    return gather_band_upper(Aout)


def tb2bd(ub: np.ndarray):
    """Upper triangular band → real bidiagonal via band-limited bulge
    chasing, O(n²·nb) work — never materializing a dense n×n matrix
    (reference src/tb2bd.cc:40-140 + internal_gebr.cc task types).

    Backend dispatch, as hb2st's (the reference pipelines this stage
    with an OpenMP taskloop, tb2bd.cc:272-294; here the same (sweep,
    chase) DAG runs ON DEVICE as batched anti-diagonal waves), through
    ``robust.ladder.tb2bd_ladder``:

    * ``vmem`` — VMEM-resident Pallas chaser (internal/
      band_wave_vmem_bd.py): the whole ribbon stays in VMEM across
      the wave grid (the XLA wave's per-wave cost is HBM segment
      traffic — BASELINE.md r4). Auto-selected on TPU when the shape
      passes the twin's own gate ``vmem_applies_bd`` (f32, band a
      power of two in [8, 256], ribbon and output windows fit VMEM).
    * ``wave`` — device wavefront (internal/band_bulge_wave_bd.py),
      auto on accelerators at n >= 1024;
    * ``native`` — single-thread C++ chase (host), default on CPU;
    * ``numpy`` — pure-numpy twin (tests).

    ``SLATE_TB2BD=vmem|wave|native|numpy`` pins the STARTING rung; a
    rung that cannot take the problem (failed probe, raise, non-finite
    output) still demotes to the next one, logged in
    ``robust.ladder.demotion_log()`` and counted as
    ``tb2bd.demotion{from,to}``; ``tb2bd.backend{rung}`` counts the
    rung whose answer was used.

    Returns (d, e, Vu, tauu, Vv, tauv, phase0): bidiagonal plus the
    packed U-side and V-side reflectors and the column-0 phase;
    A_band = U2·B·V2ᴴ·diag(conj(phase0), 1, …) with U2/V2 the
    H_1ᴴ·…·H_Kᴴ products (apply with bulge.apply_bulge_reflectors)."""
    from ..robust.ladder import tb2bd_ladder
    from .bulge import chase
    return chase(tb2bd_ladder(), "SLATE_TB2BD", ub)


def unmbr_ge2tb_u(trans: Op, Aout: Matrix, Tq, C: Matrix, opts=None):
    """Apply U-side reflectors (QR panels) to C — identical layout to
    unmqr over the ge2tb output (reference unmbr_ge2tb U side)."""
    from .geqrf import unmqr
    from ..types import Side
    return unmqr(Side.Left, trans, Aout, Tq, C, opts)


def unmbr_ge2tb_v(trans: Op, Aout: Matrix, Tl, C: Matrix, opts=None):
    """Apply V-side reflectors (LQ panels) to C:
    NoTrans: C ← Qr_1…Qr_K·C (reverse order), Qr_k = I − V_k·T_k·V_kᴴ
    with V_k gathered from block row k of Aout."""
    with trace.block("unmbr_ge2tb_v"):
        return _unmbr_v_jit(Aout, Tl, C, trans == Op.NoTrans)


@partial(cached_jit, static_argnames=("notrans",))
def _unmbr_v_jit(AV, T, C, notrans):
    g = C.grid
    p, q, nb = g.p, g.q, AV.nb
    n = AV.n
    kt = T.shape[0]
    ntt = AV.nt
    mtl, ntl = C.data.shape[2], C.data.shape[3]
    nt_p = AV.data.shape[3] * q
    Nc = nt_p * nb

    def body(av, cdat, T):
        av, cdat = av[0, 0], cdat[0, 0]
        r, c = comm.coords()
        gi = masks.local_tile_rows(mtl, p)
        gi_clip = jnp.clip(gi, 0, nt_p - 1)

        def apply_one(k, cdat):
            start = (k + 1) * nb
            prow = lax.dynamic_index_in_dim(av, k // p, axis=0,
                                            keepdims=False)
            prow = jnp.where(r == k % p, prow, jnp.zeros_like(prow))
            prow = comm.psum_rows(prow)
            fullrow = comm.allgather_cyclic(prow, q, AXIS_Q)
            panel2d = jnp.conj(fullrow.transpose(0, 2, 1)).reshape(Nc, nb)
            V = extract_v(panel2d, start, n)
            vt = V.reshape(nt_p, nb, nb)
            vloc = jnp.take(vt, gi_clip, axis=0)     # C-row indexed
            vloc = jnp.where((gi < nt_p)[:, None, None], vloc,
                             jnp.zeros_like(vloc))
            Tk = T[k]
            Top = Tk if notrans else jnp.conj(Tk).T
            w = jnp.einsum("aiv,abij->bvj", jnp.conj(vloc), cdat)
            w = comm.psum_rows(w)
            tw = jnp.einsum("uv,bvj->buj", Top, w)
            upd = jnp.einsum("aiv,bvj->abij", vloc, tw)
            return cdat - upd

        if kt > 0 and ntt > 1:
            if notrans:
                cdat = lax.fori_loop(
                    0, kt, lambda t, x: apply_one(kt - 1 - t, x), cdat)
            else:
                cdat = lax.fori_loop(0, kt, apply_one, cdat)
        return cdat[None, None]

    data = jax.shard_map(
        body, mesh=g.mesh,
        in_specs=(P(AXIS_P, AXIS_Q), P(AXIS_P, AXIS_Q), P()),
        out_specs=P(AXIS_P, AXIS_Q), check_vma=False)(AV.data, C.data, T)
    return C._replace(data=data)


@partial(cached_jit, static_argnames=("rows",))
def _rows_padded_jit(x, rows):
    """[x; 0] with ``rows`` rows, made where x lies."""
    return jnp.zeros((rows, x.shape[1]), x.dtype).at[:x.shape[0]].set(x)


def gesvd_two_stage(A: Matrix, opts=None, want_u=False, want_vt=False,
                    root=None):
    """Two-stage SVD of a tall or square A (reference gesvd.cc:77-102
    pipeline), A = Q₁·[B_b; 0]·P₁ᴴ, B_b = U₂·B·V₂ᴴ, B = U_B·Σ·V_Bᵀ:
    ge2tb (distributed) → band gather (2·nt tiles) → tb2bd bulge
    chasing (the ``robust.ladder`` rung that takes the band) → the
    bidiagonal SVD (with vectors ``bulge.bdsdc``: everything O(n²) and
    up on the device; ``bulge.bdsqr`` on the host for values alone and
    for a rank-deficient B) → back-transforms unmbr_tb2bd (device,
    column-sharded) and unmbr_ge2tb (distributed): U = Q₁·[U₂·U_B; 0],
    V = P₁·V₂·V_B.  Inside a call with vectors only the band, (d, e)
    and the O(n) reads of the merges cross to the host.  ``root`` is
    ``slate.gesvd``'s span (``svd.gesvd``), labelled here with what
    the pipeline chose: ``band``, ``chase_backend``, ``bidiag``."""
    from .bulge import apply_bulge_reflectors, bdsdc, bdsqr
    from ..matrix import conj_transpose
    from ..robust.ladder import tb2bd_ladder
    from ..types import Option, get_option
    # re-block to the two-stage band width (same trade as
    # he2hb.heev_two_stage: stage-2 chase + back-transform are
    # O(n²·band), so a gemm-sized nb as band overloads stage 2);
    # prefer 128 when the VMEM Pallas chaser can take it (see
    # heev_two_stage — the chase dominates and the VMEM kernel at 128
    # far outruns the XLA wave at 256)
    from ..internal.band_wave_vmem import preferred_eig_band
    band_nb = get_option(opts, Option.EigBand,
                         preferred_eig_band(min(A.m, A.n), A.dtype))
    if A.nb > band_nb and min(A.m, A.n) > 2 * band_nb:
        if A.nb % band_nb == 0:
            # tile-level re-block — no replicated dense round trip
            # (ADVICE r3; see Matrix.retile)
            A = A.retile(band_nb)
        else:
            A = Matrix.from_dense(A.to_dense(), nb=band_nb, grid=A.grid)
    m, n, nb, grid, dtype = A.m, A.n, A.nb, A.grid, A.dtype
    rdt = np.zeros(1, dtype).real.dtype
    with trace.block("gesvd.stage1", phase="ge2tb", m=m, n=n):
        Aout, Tq, Tl = ge2tb(A, opts)
    del A       # the re-tiled copy: nothing below reads it
    with trace.block("gesvd.gather", phase="band_gather", n=n):
        ub = ge2tb_gather(Aout)
    with trace.block("gesvd.stage2", phase="tb2bd", n=n):
        d, e, Vu, tauu, Vv, tauv, phase0 = tb2bd(ub)

    def chose(route):
        obs.count("gesvd.bidiag", 1, route=route)
        if root is not None:
            root.label(band=nb, bidiag=route,
                       chase_backend=tb2bd_ladder().last_rung)

    if not (want_u or want_vt):
        with trace.block("gesvd.bidiag", phase="bdsqr_values", n=n):
            s = np.asarray(bdsqr(d, e)).astype(rdt)
        chose("host")
        return s, None, None
    with trace.block("gesvd.bidiag", phase="bdsdc", n=n):
        route, solved = "gk_stedc", bdsdc(d, e, grid, rdt)
        if solved is None:
            # σ = 0 to working precision: the host branch completes
            # the null spaces (and is the one O(n²) host solve left)
            route = "host"
            s, Ubd, VbdT = bdsqr(d, e, want_uv=True)
            solved = s, jnp.asarray(Ubd, rdt), jnp.asarray(VbdT.T, rdt)
        s, Ubd, Vbd = solved
        del solved
    chose(route)
    # each n x n or m x n intermediate is dropped where it was last
    # read: the call's peak is the top merge's, not the sum of both sides
    U = VT = None
    if want_u:
        # U = Q1u · [U2·Ubd ; 0]  (stage-2 then stage-1 left sets)
        with trace.block("gesvd.back.tb2bd.u", phase="unmbr_tb2bd", n=n):
            u2 = apply_bulge_reflectors(Vu, tauu, Ubd.astype(dtype),
                                        nb, grid=grid)
        with trace.block("gesvd.back.ge2tb.u", phase="unmbr_ge2tb",
                         m=m, n=n):
            Ub = Matrix.from_dense(_rows_padded_jit(u2, rows=m),
                                   nb=nb, grid=grid)
            del u2
            U = unmbr_ge2tb_u(Op.NoTrans, Aout, Tq, Ub, opts)
            del Ub
    del Vu, tauu, Ubd
    if want_vt:
        # V = Q1v · diag(phase0,1,…)·(V2·Vbd)  →  VT = Vᴴ
        with trace.block("gesvd.back.tb2bd.v", phase="unmbr_tb2bd", n=n):
            v2 = apply_bulge_reflectors(Vv, tauv, Vbd.astype(dtype),
                                        nb, grid=grid)
            if np.iscomplexobj(phase0):
                v2 = v2.at[0].multiply(phase0)
        with trace.block("gesvd.back.ge2tb.v", phase="unmbr_ge2tb",
                         n=n):
            Vb = Matrix.from_dense(v2, nb=nb, grid=grid)
            del v2
            Vm = unmbr_ge2tb_v(Op.NoTrans, Aout, Tl, Vb, opts)
            del Vb
            VT = conj_transpose(Vm).materialize()
    return np.asarray(s).astype(rdt), U, VT
