"""Single-tile and panel kernels.

Analogs of reference ``include/slate/Tile_blas.hh`` (tile::gemm/trsm/…)
and the panel micro-kernels ``src/internal/Tile_getrf.hh`` /
``Tile_geqrf.hh``. On TPU a "tile op" is an XLA primitive on an
[nb, nb] block (MXU-friendly), and a "panel kernel" is a masked
``lax.fori_loop`` over the panel's columns on a **replicated** copy of
the panel — every device runs it redundantly, which replaces both
SLATE's multi-threaded panel (internal_getrf.cc:70-110, spin
ThreadBarrier util.hh:132-153) and its cross-rank pivot exchange
(the data is already everywhere; no communication at all).

Panels are always full height (padded rows masked), so one compiled
program serves every k — the price is O(m·nb) masked work per column,
the payoff is a single static XLA loop with no dynamic shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# LAPACK-layout Householder QR (geqrf): no public name in jax 0.9
from jax._src.lax.linalg import geqrf as _geqrf


# ---------------------------------------------------------------------------
# tile-level wrappers (reference Tile_blas.hh:30-103)
# ---------------------------------------------------------------------------

def tile_gemm(alpha, a, b, beta, c, tier=None):
    """alpha·a·b + beta·c on one tile. ``tier`` (a precision-tier name
    from internal/precision.py, static under jit) selects the MXU
    bf16-split lowering for f32 operands; None keeps the package
    default (bf16_6x). When the rank_k rung is armed and the
    contraction is a sub-nb remainder (k below one lane tile — the
    shape XLA pads to 128), the update runs in the VMEM-resident
    Pallas tail kernel instead."""
    from .precision import trailing_dot_kwargs
    from . import pallas_kernels as pk
    if (isinstance(alpha, (int, float)) and isinstance(beta, (int, float))
            and getattr(a, "ndim", 0) == 2
            and getattr(b, "ndim", 0) == 2
            and getattr(c, "ndim", 0) == 2
            and pk.rung_enabled("rank_k")
            and pk.pallas_supported(a.shape[1], a.dtype, kernel="rank_k")
            and c.shape[0] % 8 == 0 and c.shape[1] % 128 == 0
            and pk.rank_k_vmem_applies(c.shape[0], c.shape[1],
                                       a.shape[1])):
        return pk.rank_k_tail_pallas(
            c, a, b, alpha=float(alpha), beta=float(beta), tier=tier,
            interpret=pk.default_interpret())
    mm = jnp.matmul(a, b, **trailing_dot_kwargs(tier, a.dtype))
    return alpha * mm + beta * c


def _factor_dtype(dt):
    """XLA's factorization primitives (lu/cholesky/geqrf/
    triangular_solve) need >= f32; low-precision tiles factor in f32
    and cast back (mirrors the reference's mixed-precision stance:
    storage precision != panel compute precision)."""
    if dt in (jnp.bfloat16, jnp.float16):
        return jnp.float32
    return dt


def _pallas_tile_enabled() -> bool:
    """VMEM-resident Pallas tile factorizations instead of XLA's —
    armed by SLATE_PALLAS_TILE=1 or the autotuner's rung registry
    (pallas_kernels.active_rung). Measured on v5e, XLA's native
    cholesky/lu win (47–50µs vs 85–133µs per [128..512]² f32 tile —
    the Pallas kernels' serialized VPU column sweeps dominate), so the
    default stays XLA; the Pallas path is kept as the escape hatch
    SURVEY §2.4 calls for, for shapes/chips where the balance flips."""
    from . import pallas_kernels as pk
    return pk.rung_enabled("tile")


def tile_potrf(a):
    """Cholesky of one [nb,nb] tile → lower factor (reference
    internal_potrf.cc device LAPACK potrf)."""
    from . import pallas_kernels as pk
    if (a.ndim == 2 and _pallas_tile_enabled()
            and pk.pallas_supported(a.shape[-1], a.dtype)
            and pk.tile_vmem_applies(a.shape[-1])):
        return pk.potrf_tile_pallas(a, interpret=pk.default_interpret())
    fd = _factor_dtype(a.dtype)
    return lax.linalg.cholesky(a.astype(fd)).astype(a.dtype)


def _trsm_pallas_ok(pk, l, b, trans_or_conj: bool, n: int,
                    m: int) -> bool:
    """Shared gate for the blocked Pallas trsm rung: square real
    lower factor of a supported width, plain (non-transposed op on
    the left / non-conjugated on the right), within the VMEM model.
    ``m`` (the B dimension the factor doesn't touch) must be a full
    lane tile: for the left solve it is the B window's last (lane)
    dimension, which Mosaic wants 128-aligned for f32 — sub-lane
    widths would fail at trace time instead of falling back."""
    return (not trans_or_conj and l.ndim == 2 and b.ndim == 2
            and l.shape[0] == l.shape[1] and m % 128 == 0 and m > 0
            and pk.rung_enabled("trsm")
            and pk.pallas_supported(n, l.dtype, kernel="trsm")
            and pk.trsm_vmem_applies(n, m))


def tile_trsm_left_lower(l, b, unit: bool = False, trans: bool = False):
    from . import pallas_kernels as pk
    if _trsm_pallas_ok(pk, l, b, trans, l.shape[0], b.shape[1]):
        fd = _factor_dtype(l.dtype)
        return pk.trsm_left_lower_pallas(
            l.astype(fd), b.astype(fd), unit=unit,
            interpret=pk.default_interpret()).astype(b.dtype)
    return lax.linalg.triangular_solve(
        l, b, left_side=True, lower=True, unit_diagonal=unit,
        transpose_a=trans)


def tile_trsm_right_lower_t(l, b, unit: bool = False, conj: bool = False):
    """b · op(L)^{-1} with op = (conj-)transpose — the potrf panel op."""
    from . import pallas_kernels as pk
    if _trsm_pallas_ok(pk, l, b, conj, l.shape[0], b.shape[0]):
        fd = _factor_dtype(l.dtype)
        return pk.trsm_right_lower_t_pallas(
            l.astype(fd), b.astype(fd), unit=unit,
            interpret=pk.default_interpret()).astype(b.dtype)
    return lax.linalg.triangular_solve(
        l, b, left_side=False, lower=True, unit_diagonal=unit,
        transpose_a=True, conjugate_a=conj)


# ---------------------------------------------------------------------------
# LU panel with partial pivoting (reference Tile_getrf.hh:161-300 +
# internal_getrf.cc — re-designed as a replicated masked column loop)
# ---------------------------------------------------------------------------

# XLA's LuDecompositionBlock runs out of scoped vmem above roughly
# 11k panel rows on a v5e; panels taller than this go through the
# chunked tournament (CALU) path below. Within 2.4 % of the cap a
# [10000, 384] f32 panel (the first of n=10000, nb=384 through
# slate.gesv) compiled and ran on a v5e, 35 ms of LuDecompositionBlock
# a factorization for 10,000 columns (2026-09-27, jax 0.9.0, PR 27).
LU_PANEL_MAX_ROWS = 10240


def panel_lu_factor(panel: jax.Array, start: jax.Array | int, m: int,
                    max_rows: int | None = None):
    """Pivoted LU of a replicated panel via XLA's native blocked LU.

    panel: [M, nb] full-height gathered panel (global row i at index i).
    start: global row of the panel's diagonal (k * nb, traced).
    m:     true matrix rows; rows >= m are padding (the caller placed
           identity on padded diagonal entries, so padding self-pivots).

    The active window [start, max(m, start+nb)) is rolled to row 0,
    rows outside it zeroed, and the whole strip is handed to
    ``lax.linalg.lu`` — XLA's TPU-optimized blocked partial-pivoting
    LU — then rolled back. This replaces a hand-written column loop
    (latency-bound: nb sequential argmax/swap/rank-1 steps) with the
    compiler's MXU-blocked kernel; numerics are identical partial
    pivoting. (Reference analog: the panel micro-kernel
    Tile_getrf.hh:161-300 + internal_getrf.cc thread teams.)

    Returns (panel, piv, info): L (unit diag implicit) below / U on and
    above the diagonal; ``piv[j]`` = global row swapped with row
    ``start+j`` (LAPACK ipiv semantics, 0-based); info = number of
    zero pivots encountered (0 ⇒ success), like getrf's info.

    ``max_rows``: per-instance row cap of the single-shot ``lu`` call
    (TPU scoped-vmem limit). Panels taller than this use the chunked
    tournament-pivot path (CALU, reference getrf_tntpiv.cc) instead.
    """
    M, nb = panel.shape
    if max_rows is not None and M > max_rows:
        return _panel_lu_tournament(panel, start, m, max_rows)
    rows = jnp.arange(M)
    # active rows: at/below the diagonal and real — plus the diagonal
    # block itself so identity-padded columns (global col >= n) can
    # self-pivot on their 1.
    hi = jnp.maximum(m, start + nb)
    keep = (rows >= start) & (rows < hi)
    masked = jnp.where(keep[:, None], panel, jnp.zeros_like(panel))
    rolled = jnp.roll(masked, -start, axis=0)
    fd = _factor_dtype(panel.dtype)
    from . import pallas_kernels as pk
    if (pk.rung_enabled("panel_plu")
            and pk.pallas_supported(nb, fd, kernel="panel_plu")
            and pk.panel_plu_vmem_applies(M, nb)):
        # fused in-VMEM pivot search + row swap + rank-1 update; the
        # pivot vector is LAPACK sequential-swap order, same as
        # lax.linalg.lu's — ipiv semantics stay bitwise-compatible
        lu, piv_r, _ = pk.panel_plu_pallas(
            rolled.astype(fd), interpret=pk.default_interpret())
    else:
        lu, piv_r, _ = lax.linalg.lu(rolled.astype(fd))
    lu = lu.astype(panel.dtype)
    diag = jnp.diagonal(lu)[:nb]
    info = jnp.sum(diag == 0).astype(jnp.int32)
    back = jnp.roll(lu, start, axis=0)
    out = jnp.where(keep[:, None], back, panel)
    pg = piv_r[:nb].astype(jnp.int32) + jnp.int32(start)
    # a wrapped pivot (>= M) can only arise for an all-zero column
    # (singular); self-swap in that case.
    piv = jnp.where(pg < M, pg,
                    jnp.int32(start) + jnp.arange(nb, dtype=jnp.int32))
    return out, piv, info


def _tournament_select(cand: jax.Array, cand_idx: jax.Array,
                       max_rows: int, sentinel: int):
    """The tournament's rounds on ``cand`` [R, nb] (zero rows lose every
    round) with row ids ``cand_idx`` [R]: while more than ``max_rows``
    candidates are left, split them into chunks of ``max_rows``, run
    XLA's pivoted ``lu`` on each chunk (vmapped: one batched call a
    round) and keep each chunk's nb winner rows; then one pivoted
    ``lu`` of the survivors. Returns that ``lu``, the positions of its
    first nb pivot rows among the survivors (elimination order), the
    survivors and their ids. Rows padded onto the last chunk are zero
    and carry the id ``sentinel``."""
    nb = cand.shape[1]
    R = cand.shape[0]
    if R > max_rows and max_rows < 2 * nb:
        # a round of c chunks leaves c·nb rows: no fewer under 2·nb
        raise ValueError(f"tournament chunks of {max_rows} rows cannot "
                         f"reduce {R} candidates of width {nb}")
    while R > max_rows:
        c = -(-R // max_rows)
        pad = c * max_rows - R
        cand = jnp.pad(cand, ((0, pad), (0, 0)))
        # pad rows are zero (they lose every real tournament); the
        # sentinel id marks them so a degenerate win (all-zero column)
        # resolves to a self-swap in _tournament_swap_list.
        cand_idx = jnp.pad(cand_idx, (0, pad), constant_values=sentinel)
        chunks = cand.reshape(c, max_rows, nb)
        _, _, perm_c = jax.vmap(lax.linalg.lu)(chunks)
        sel = perm_c[:, :nb]                    # [c, nb] winners
        cand = jnp.take_along_axis(chunks, sel[:, :, None], axis=1)
        cand = cand.reshape(c * nb, nb)
        cand_idx = jnp.take_along_axis(
            cand_idx.reshape(c, max_rows), sel, axis=1).reshape(c * nb)
        R = c * nb
    lu_f, _, perm_f = lax.linalg.lu(cand)
    return lu_f, perm_f[:nb], cand, cand_idx


def _tournament_swap_list(win: jax.Array, first, M: int):
    """LAPACK's sequential swaps that bring winner j (row id ``win[j]``
    of an M-row space, ids >= M are sentinels) to position ``first+j``,
    j = 0..nb-1 in order. Returns (content, locof, piv): ``piv[j]`` =
    position of winner j when swaps 0..j-1 have been applied;
    ``content[i]`` = the row whose data ends at position i and
    ``locof`` its inverse."""
    nb = win.shape[0]
    rows = jnp.arange(M, dtype=jnp.int32)

    def sim(j, carry):
        content, locof, piv = carry
        at = first + j
        t = win[j]
        # sentinel winner (all-zero column, singular) → self-swap
        t = jnp.where(t < M, t, content[at])
        loc = locof[t]
        piv = piv.at[j].set(loc)
        cj = content[at]
        content = content.at[at].set(t).at[loc].set(cj)
        locof = locof.at[t].set(at).at[cj].set(loc)
        return content, locof, piv

    return lax.fori_loop(0, nb, sim,
                         (rows, rows, jnp.zeros(nb, jnp.int32)))


def _safe_upper(lu_top: jax.Array) -> jax.Array:
    """U of a factored diagonal block with 1 in place of a zero pivot,
    so that L21 = A21·U⁻¹ stays finite where ``info`` counts it."""
    nb = lu_top.shape[0]
    u11 = jnp.triu(lu_top)
    return u11 + jnp.diag(jnp.where(jnp.diagonal(u11) == 0,
                                    jnp.ones(nb, u11.dtype),
                                    jnp.zeros(nb, u11.dtype)))


def _panel_lu_tournament(panel: jax.Array, start, m: int, max_rows: int):
    """Tournament-pivot LU of a tall panel (CALU — reference
    src/getrf_tntpiv.cc / internal_getrf_tntpiv.cc:334's binary
    tournament, here a ``max_rows``-ary reduction).

    Round structure (:func:`_tournament_select`): split the candidate
    rows into chunks of ``max_rows``, run XLA's pivoted ``lu`` on each
    chunk, keep each chunk's nb winner rows, repeat until one chunk
    remains; a final pivoted ``lu`` of the survivors fixes the nb pivot
    rows *and* their elimination order. The panel is then permuted with
    the LAPACK-equivalent sequential-swap permutation and factored in
    place: the winners' LU is already the top block's factorization,
    and the remaining rows get L21 = A21·U11⁻¹ in one MXU triangular
    solve.

    Same contract as :func:`panel_lu_factor`; pivot *choices* are
    CALU's (backward stable, tighter comm profile) rather than classic
    partial pivoting's. On a grid the chunk driver runs the first round
    on the rows each device stores and never assembles this panel
    (:func:`tournament_winners`, :func:`tournament_pivots`).
    """
    M, nb = panel.shape
    fd = _factor_dtype(panel.dtype)
    rows = jnp.arange(M)
    hi = jnp.maximum(m, start + nb)
    keep = (rows >= start) & (rows < hi)
    masked = jnp.where(keep[:, None], panel, jnp.zeros_like(panel))
    rolled = jnp.roll(masked, -start, axis=0)   # active window at row 0

    # --- phase A: tournament pivot selection (rolled-space ids) ------
    lu_f, sel, _, cand_idx = _tournament_select(
        rolled.astype(fd), rows.astype(jnp.int32), max_rows, M)
    win = jnp.take(cand_idx, sel)               # winners, elim. order
    lu_top = lu_f[:nb].astype(panel.dtype)      # LU of permuted top blk
    diag = jnp.diagonal(lu_f)[:nb]
    info = jnp.sum(diag == 0).astype(jnp.int32)

    # --- phase B: LAPACK-style sequential-swap permutation -----------
    content, _, piv_r = _tournament_swap_list(win, 0, M)
    permuted = jnp.take(rolled, content, axis=0)

    # --- factor in place: top block is done; rows below get L21 ------
    l21 = lax.linalg.triangular_solve(
        _safe_upper(lu_top).astype(fd), permuted[nb:].astype(fd),
        left_side=False, lower=False).astype(panel.dtype)
    out_rolled = jnp.concatenate([lu_top, l21], axis=0)
    # rows outside the active window were zeroed before the permutation
    # and no swap touches them (winners are active rows), so the keep
    # mask restores them exactly.
    back = jnp.roll(out_rolled, start, axis=0)
    out = jnp.where(keep[:, None], back, panel)
    piv = jnp.int32(start) + piv_r
    return out, piv, info


def tournament_winners(rows: jax.Array, ids: jax.Array, max_rows: int,
                       sentinel: int):
    """The tournament's first round where the rows are stored (the
    reference's getrf_tntpiv runs it on each rank's own rows too):
    ``rows`` [L, nb] are one device's rows of the panel, the active
    ones first and the rest zero, ``ids`` [L] their global row ids
    (``sentinel`` on the zeroed ones). Returns this device's nb winner
    rows (their original values, elimination order) and their ids — all
    that has to cross the mesh; further rounds only where L exceeds
    ``max_rows``. With the active rows first a tie (a column of zeros)
    falls to an active row while one is left, so the winners are real
    rows first, sentinels after."""
    _, sel, cand, cand_idx = _tournament_select(
        rows.astype(_factor_dtype(rows.dtype)), ids, max_rows, sentinel)
    return jnp.take(cand, sel, axis=0), jnp.take(cand_idx, sel)


def tournament_pivots(cand: jax.Array, cand_idx: jax.Array, first, M: int,
                      max_rows: int, dtype):
    """The tournament's last rounds on the gathered winners ``cand``
    [R, nb] with global row ids ``cand_idx`` (ids >= M are sentinels:
    zero rows of a device with fewer than nb active rows), the same on
    every device. The real rows are put first, so that a tie never
    falls to a sentinel while a real row is left. Returns (lu_top, piv,
    locof, info): the winners' LU (the factored diagonal block), the
    LAPACK swap list that brings winner j to global row ``first+j``,
    the position every global row ends at
    (:func:`_tournament_swap_list`), and the number of zero pivots."""
    nb = cand.shape[1]
    real_first = jnp.argsort(cand_idx >= M, stable=True)
    lu_f, sel, _, cand_idx = _tournament_select(
        jnp.take(cand, real_first, axis=0), jnp.take(cand_idx, real_first),
        max_rows, M)
    info = jnp.sum(jnp.diagonal(lu_f)[:nb] == 0).astype(jnp.int32)
    _, locof, piv = _tournament_swap_list(jnp.take(cand_idx, sel), first, M)
    return lu_f[:nb].astype(dtype), piv, locof, info


def lu_nopiv_block(a: jax.Array, ib: int = 32):
    """Unpivoted LU of a square [nb, nb] block, ib-strip blocked:
    short sequential chains on [nb, ib] strips + MXU block updates.
    Returns (lu, info)."""
    from . import pallas_kernels as pk
    if (a.ndim == 2 and _pallas_tile_enabled()
            and pk.pallas_supported(a.shape[-1], a.dtype)
            and pk.tile_vmem_applies(a.shape[-1])):
        return pk.lu_nopiv_tile_pallas(a, interpret=pk.default_interpret())
    nb = a.shape[0]
    rows = jnp.arange(nb)
    info = jnp.zeros((), jnp.int32)
    ib = min(ib, nb)

    for j0 in range(0, nb, ib):
        j_hi = min(j0 + ib, nb)
        ibw = j_hi - j0
        S = a[:, j0:j_hi]

        def strip(jj, carry, j0=j0, ibw=ibw):
            S, info = carry
            dj = j0 + jj
            pivval = S[dj, jj]
            info = info + jnp.where(jnp.abs(pivval) == 0, 1, 0)
            safe = jnp.where(jnp.abs(pivval) == 0,
                             jnp.ones_like(pivval), pivval)
            below = rows > dj
            lcol = jnp.where(below, jnp.take(S, jj, axis=1) / safe,
                             jnp.zeros(nb, S.dtype))
            urow = jnp.where(jnp.arange(ibw) > jj, S[dj],
                             jnp.zeros(ibw, S.dtype))
            S = S - jnp.outer(lcol, urow)
            S = S.at[:, jj].set(
                jnp.where(below, lcol, jnp.take(S, jj, axis=1)))
            return S, info

        S, info = lax.fori_loop(0, ibw, strip, (S, info))
        a = lax.dynamic_update_slice(a, S, (0, j0))
        if j_hi < nb:
            l11 = S[j0:j_hi]
            u12 = lax.linalg.triangular_solve(
                l11, a[j0:j_hi, j_hi:], left_side=True, lower=True,
                unit_diagonal=True)
            a = a.at[j0:j_hi, j_hi:].set(u12)
            l21 = jnp.where((rows >= j_hi)[:, None], S,
                            jnp.zeros_like(S))
            a = a.at[:, j_hi:].add(-(l21 @ u12))
    return a, info


def panel_lu_nopiv(panel: jax.Array, start, m: int):
    """Unpivoted LU of a full-height panel (reference getrf_nopiv.cc):
    factor the diagonal [nb, nb] block, then one MXU triangular solve
    for the whole sub-diagonal L21 — no full-height column loop."""
    M, nb = panel.shape
    rows = jnp.arange(M)
    d = lax.dynamic_slice(panel, (start, 0), (nb, nb))
    d_f, info = lu_nopiv_block(d)
    panel = lax.dynamic_update_slice(panel, d_f, (start, 0))
    safe_u = _safe_upper(d_f)
    below = (rows >= start + nb) & (rows < m)
    a21 = jnp.where(below[:, None], panel, jnp.zeros_like(panel))
    # L21 = A21·U11⁻¹  (right-side upper solve)
    l21 = lax.linalg.triangular_solve(safe_u, a21, left_side=False,
                                      lower=False)
    panel = jnp.where(below[:, None], l21, panel)
    return panel, info


# ---------------------------------------------------------------------------
# Householder QR panel (reference Tile_geqrf / internal_geqrf.cc:24-446,
# replicated-masked redesign) + larft T factor
# ---------------------------------------------------------------------------

def panel_qr_factor(panel: jax.Array, start, m: int):
    """Householder QR of a replicated full-height panel via XLA's
    native blocked ``geqrf`` (same roll-to-origin trick as the LU
    panel: the active window [start, m) moves to row 0, rows outside
    are zeroed and restored afterwards; zero rows contribute nothing
    to the reflectors, so numerics match factoring the window alone).

    Returns (panel, taus): V's unit-lower columns stored below the
    diagonal (LAPACK geqrf convention), R on/above; taus [nb].
    Reference analog: internal_geqrf.cc:24-446 panel + ttqrt tree.
    """
    M, nb = panel.shape
    rows = jnp.arange(M)
    keep = (rows >= start) & (rows < m)
    masked = jnp.where(keep[:, None], panel, jnp.zeros_like(panel))
    rolled = jnp.roll(masked, -start, axis=0)
    fd = _factor_dtype(panel.dtype)
    a, taus = _geqrf(rolled.astype(fd))
    back = jnp.roll(a, start, axis=0).astype(panel.dtype)
    out = jnp.where(keep[:, None], back, panel)
    return out, taus.astype(panel.dtype)


def extract_v(panel: jax.Array, start, m: int) -> jax.Array:
    """Unit-lower-trapezoid V from a factored panel: V[i,j] = panel[i,j]
    for i > start+j, 1 at i = start+j, 0 above and in padding."""
    M, nb = panel.shape
    rows = jnp.arange(M)[:, None]
    diag = start + jnp.arange(nb)[None, :]
    v = jnp.where((rows > diag) & (rows[:, :] < m), panel,
                  jnp.zeros_like(panel))
    return v + (rows == diag).astype(panel.dtype)


def larft(V: jax.Array, taus: jax.Array) -> jax.Array:
    """Forward compact-WY T: H_0 H_1 … = I − V T V^H (LAPACK larft).

    V: [M, nb] unit lower trapezoid; taus: [nb]. T: [nb, nb] upper tri.
    """
    nb = taus.shape[0]
    W = jnp.conj(V.T) @ V                        # [nb, nb] Gram
    T0 = jnp.zeros((nb, nb), V.dtype)

    def body(j, T):
        colmask = jnp.arange(nb) < j
        wj = jnp.where(colmask, W[:, j], jnp.zeros_like(W[:, j]))
        tcol = -taus[j] * (T @ wj)
        tcol = jnp.where(colmask, tcol, jnp.zeros_like(tcol)).at[j].set(taus[j])
        return T.at[:, j].set(tcol)

    return lax.fori_loop(0, nb, body, T0)
