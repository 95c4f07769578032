"""VMEM-resident Pallas wavefront bulge chaser (hb2st stage 2).

The XLA wavefront (band_bulge_wave.py) costs ~0.37 ms/wave at
n=8192/b=128 — NOT dispatch overhead but HBM traffic: every wave
slices + updates a ~13 MB sliding segment and materializes
O(segment)-sized delta compositions, ~65 MB of HBM round-trips per
wave x ~2n waves (BASELINE.md round 4). The reference chases bulges
serially on rank 0 with OpenMP tasks (src/hb2st.cc:143-207,
internal_hebr.cc); the TPU answer here keeps the ENTIRE ribbon in
VMEM across a Pallas grid (v5e: 128 MB VMEM; the n=8192/b=128 ribbon
is ~34 MB) so a wave touches no HBM at all.

Design (f32, b a power of two, 8 <= b <= 256):

* Storage: 2-D diagonal ribbon ``R[r, off + c - r]``, off = 2b-1,
  width 4b (c - r spans [-(2b-1), 2b-1] while bulges are in flight —
  the XLA wave's flat 3b layout packs the same span via a deliberate
  row wrap; the clean 4b width keeps every block a per-row SHIFT of a
  static column window).
* Tasks read/write SHEARED blocks: B[i, k] of the task at i0 lives at
  (i0 + i, off - b + k - i). All Householder applications are rank-1,
  and a sheared rank-1 factors into (column vector — broadcast, free)
  x (row vector — sheared): the only lane shuffles build sheared row
  vectors and rotate a block's rows for a column sum; block data
  itself is never unsheared. On the band-128 frame layout (``_fw``)
  each is ONE pass — a lane gather (``_shear_rowvec``) or a strided
  rotate (``_antishear``), ``shear_form`` — elsewhere a ladder of
  log2(b) masked rolls. On one v5e at n=8192/b=128 (PR 45,
  ``tools/chase_probe.py``): a shear 0.16 us single-pass, 0.72 us by
  the ladder; the kernel 1.11 s, 2.38 s with ladders, 0.79 s with no
  shear at all — the window RMW and the rest of the body are now the
  larger part.
* The Hermitian mirror (upper triangle) is maintained by CONJUGATE
  rank-1s — U = conj(B)^T evolves as U -= tau * v_col x w_row with
  vectors already computed on the B side, so no in-kernel transposes.
* Grid: ``(G, 2)`` — one (wave, parity) per step, sequential on TPU
  (par 0 then par 1 inside each g, matching the chain). Inside each
  step a ``fori_loop`` walks NCH chunks of U_SLOTS statically-unrolled
  wave slots. The round-4 mega-kernel unrolled ALL P = T//2+1 slots
  x 2 parities into one body (64 task bodies at n=8192/b=128) and
  took >25 min of Mosaic compile on this toolchain; the chunked form
  compiles a single U_SLOTS-task body and loops, at the cost of one
  extra window load/roll/store per chunk (VMEM-rate, ~cheap).
* Each chunk read-modify-writes its own aligned window of the ribbon
  directly (tasks of one wave touch provably disjoint elements, so
  sequential chunk RMW composes exactly like the old single-window
  add; the one-row overlap between adjacent slots/chunks ADDS, same
  invariant as the XLA wave). Window bases stay 8-aligned because
  b >= 8 and U_SLOTS * stride is a multiple of 8; the per-g remainder
  arrives via scalar-prefetched (base8, delta) and one dynamic sublane
  roll (Mosaic requires provably 8-aligned dynamic row offsets, and
  ``(x // 8) * 8`` mis-lowers on this toolchain).
* The reflector chain between waves lives in two VMEM scratch pairs
  (v0/t0 for parity 0, v1/t1 for parity 1): wave (g, 0) slot u chains
  from (g-1, 1) slot u-1, wave (g, 1) from (g, 0) slot u — the
  previous-slot rows are extracted with a one-hot MXU contraction
  (dynamic sublane reads of scratch rows would need 8-alignment the
  slot index doesn't have).
* Validity is scalar algebra on (g, u): the chase-count bound
  t < (n-2-s)//b + 1 is tested division-free as t*b <= n-2-s.

Numerics follow band_bulge.hb2st's task order and larfg convention;
values differ from the numpy twin only by summation association
(sheared lane reductions) — tests/test_band_wave.py asserts twin
agreement at f32 tolerance plus eigenvalue residuals vs dense.
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .band_bulge import max_chase
from .. import obs

TAUP = 128     # tau slots padded to one lane tile
U_SLOTS = 8    # wave slots unrolled per chunk body (the compile-time
               # knob: body size is ~U_SLOTS task bodies)


def _ceil8(x):
    return -(-x // 8) * 8


def _geometry(n: int, b: int):
    """(G, P, PP, NCH, CH, PAD, ROWS) exactly as _hb2st_vmem_jit lays
    the ribbon out — single source of truth for the VMEM-footprint
    gate. PP = ceil8(P) == NCH * U_SLOTS (U_SLOTS = 8)."""
    S = n - 1
    T = max_chase(n, b)
    P = T // 2 + 1
    PP = _ceil8(P)
    NCH = PP // U_SLOTS if PP >= U_SLOTS else 1
    Wmax = 2 * (S - 1) + T + 1
    G = (Wmax + 1) // 2
    PAD = b + 7
    stride = 2 * b - 1
    # chunk window: U_SLOTS slabs at `stride` apart + the 8-row
    # alignment slack
    CH = _ceil8(U_SLOTS * stride + 1 + 8)
    # Active-range chunk skipping bounds the window excursion: the
    # last ACTIVE slot u_hi satisfies g + par*b + u_hi*(2b-1) <= n-2,
    # so the furthest ribbon row touched is n+6 plus the tail of its
    # chunk ((U_SLOTS-1) more slots) plus the window itself — ~n+CH,
    # not ~2n (without skipping, late waves' dead slots would slide
    # the window a further ~n rows past the matrix).
    last = (n + 6) + (U_SLOTS - 1) * stride + CH + 16
    ROWS = _ceil8(max(PAD + n + 2 * b, last) + 8)
    return G, P, PP, NCH, CH, PAD, ROWS


def shear_form(rows: int, W4: int, col0: int | None = None) -> str:
    """How a [rows, W4] sheared vector is built, read off the shape:
    ``"single_pass"`` on the FRAMES layout (rows a lane-tile multiple,
    frame width 2*rows, local col0 = rows-1: the vector's lane tiles
    line up with the frame's, so one lane gather or one strided rotate
    does what the ladder does in log2(rows) masked rolls), ``"ladder"``
    elsewhere (FW = 4b is under one lane tile for b < 32, and no chip
    run has validated bands under 128)."""
    single = (rows % 128 == 0 and W4 == 2 * rows
              and col0 in (None, rows - 1))
    return "single_pass" if single else "ladder"


def chase_shear_form(band: int) -> str:
    """``shear_form`` of the task bodies of a chase at ``band``: what
    ``hb2st.shear{form}`` counts and the ``hb2st`` span's ``shear``
    says."""
    return shear_form(band, _fw(band), band - 1)


def _shear_rowvec_ladder(vec_row, col0, rows, W4):
    """``_shear_rowvec`` by log2(rows) masked-roll passes: any shape,
    and the reference the single-pass form is tested against."""
    s = jnp.broadcast_to(pltpu.roll(vec_row, shift=col0, axis=1),
                         (rows, W4))
    ii = lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    shift = 1
    while shift < rows:
        # left-roll by `shift` == right-roll by W4 - shift (pltpu.roll
        # rejects negative static shifts)
        rolled = pltpu.roll(s, shift=W4 - shift, axis=1)
        s = jnp.where((ii & shift) != 0, rolled, s)
        shift *= 2
    return s


def _shear_lanes(rows, W4, col0):
    """The index array of the single-pass ``_shear_rowvec``,
    m[i, l] = l + 1 + i on one 128-lane tile (None where the form is
    the ladder): a constant of the shape, built once a grid step
    beside the blocks' column indices and handed to every call."""
    if shear_form(rows, W4, col0) == "ladder":
        return None
    return (lax.broadcasted_iota(jnp.int32, (rows, 128), 1) + 1
            + lax.broadcasted_iota(jnp.int32, (rows, 128), 0))


def _shear_rowvec(vec_row, col0, rows, W4, lanes=None):
    """S[i, c] = vec[c - col0 + i] — the sheared broadcast matching a
    block whose element (i, k) lives at column col0 + k - i.

    vec_row: [1, W4] with the vector in cols [0, b), zeros elsewhere.
    Returns [rows, W4]; only S[i, c] with 0 <= c - col0 + i < rows is
    defined (every call site masks the rest away).

    Single pass (``shear_form``): with col0 = rows-1 the wanted index
    on the 128-lane tile j of the frame is (128*(j - T) + m) mod rows,
    T = rows // 128, m = l + 1 + i (``lanes``: ``_shear_lanes``) —
    lane m mod 128 of source tile (j + m // 128) mod T, and tiles j
    and j + T are the same array. So one lane gather of each broadcast
    source tile (a Mosaic gather reads one source vreg along lanes)
    and, past one tile, a select between them. Elsewhere: the ladder."""
    m = _shear_lanes(rows, W4, col0) if lanes is None else lanes
    if m is None:
        return _shear_rowvec_ladder(vec_row, col0, rows, W4)
    T = rows // 128
    lane = m & 127
    # source tile h is rotated down to lanes [0, 128) first: Mosaic
    # refuses to broadcast a lane-offset slice of a one-row value
    # ("Invalid input layout")
    src = [jnp.take_along_axis(
        jnp.broadcast_to(
            (pltpu.roll(vec_row, shift=W4 - 128 * h, axis=1)
             if h else vec_row)[:, :128], (rows, 128)),
        lane, axis=1, mode="promise_in_bounds") for h in range(T)]
    tiles = []
    for j in range(T):
        tile = src[0]
        for h in range(1, T):
            tile = jnp.where(((j + (m >> 7)) % T) == h, src[h], tile)
        tiles.append(tile)
    return jnp.concatenate(tiles + tiles, axis=1)


def _antishear_ladder(Q, rows, W4):
    """``_antishear`` by log2(rows) masked-roll passes."""
    ii = lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    shift = 1
    while shift < rows:
        rolled = pltpu.roll(Q, shift=shift, axis=1)
        Q = jnp.where((ii & shift) != 0, rolled, Q)
        shift *= 2
    return Q


def _antishear(Q, rows, W4):
    """Row i of Q rotated right by i lanes: one strided rotate on the
    FRAMES layout (``shear_form``), the ladder elsewhere. Pure data
    movement, so both forms give the same bits."""
    if shear_form(rows, W4) == "ladder":
        return _antishear_ladder(Q, rows, W4)
    return pltpu.roll(Q, 0, axis=1, stride=1, stride_axis=0)


def _antishear_sum(Q, rows, W4):
    """out[0, c'] = sum_i Q[i, c' - i] — column reductions of sheared
    blocks (v^H B, v^H D): shift row i right by i, then one sublane
    sum. Exact up to summation order — replaces the
    Hermitian v^H D = (D v)^T shortcut, whose rounding asymmetry fed
    back through deep chase sequences (eig error grew to O(10) by
    n=1024; measured round 4)."""
    return jnp.sum(_antishear(Q, rows, W4), axis=0, keepdims=True)


def _col2row(xcol, E):
    """[b, 1] column -> [1, W4] row via a one-hot MXU dot (exact:
    one nonzero per output lane). Lane-dim pads/updates of values
    (jnp.pad, dynamic_update_slice) fail to lower in Mosaic."""
    return lax.dot_general(xcol, E,
                           dimension_numbers=(((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _row2col(xrow, E):
    """[1, W4] row -> [b, 1] column via the same one-hot contraction."""
    return lax.dot_general(E, xrow,
                           dimension_numbers=(((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _larfg_f32(x_row, L, W4):
    """LAPACK larfg on a [1, W4] row holding x in cols [0, b); active
    length L (traced). Returns (v [1, W4] with v[0] = 1 and zeros at
    cols >= L; tau; beta). Matches band_bulge_wave._masked_larfg."""
    lane = lax.broadcasted_iota(jnp.int32, x_row.shape, 1)
    m = lane < L
    xm = jnp.where(m, x_row, 0.0)
    alpha = jnp.sum(jnp.where(lane == 0, xm, 0.0))
    xnorm2 = jnp.sum(jnp.where(lane > 0, xm * xm, 0.0))
    trivial = xnorm2 == 0.0
    sgn = jnp.where(alpha != 0.0, jnp.sign(alpha), 1.0)
    beta = -sgn * jnp.sqrt(alpha * alpha + xnorm2)
    beta = jnp.where(trivial, alpha, beta)
    denom = jnp.where(trivial, 1.0, beta)
    tau = (beta - alpha) / denom
    tau = jnp.where(trivial, 0.0, tau)
    vden = jnp.where(trivial, 1.0, alpha - beta)
    v = jnp.where(m, xm / vden, 0.0)
    v = jnp.where(lane == 0, 1.0, v)
    v = jnp.where(m, v, 0.0)
    return v, tau, beta


def _active_chunk_range(n, b, G):
    """Host-side per-(g, par) active-chunk bounds, flattened to
    [2G] i32 arrays indexed g*2 + par (scalar prefetch). Chunk c is
    run iff c in [clo, chi]; slots outside the true active range
    [u_lo, u_hi] inside those chunks still self-mask via do_any.
    Active u: s_u = g-u in [0, n-2] gives u >= g-(n-2); the chase
    bound (par+2u)b <= n-2-s_u gives u <= (n-2-g-par*b)//(2b-1) (and
    implies i0 <= n-1); the seed task adds u = 0 for par 0 while
    g <= n-2."""
    gi = np.arange(G, dtype=np.int64)
    u_lo = np.maximum(0, gi - (n - 2))
    clo = np.zeros(2 * G, np.int32)
    chi = np.zeros(2 * G, np.int32)
    for par in (0, 1):
        num = n - 2 - gi - par * b
        u_hi = np.where(num >= 0, num // (2 * b - 1), -1)
        u_hi = np.minimum(gi, u_hi)
        if par == 0:
            u_hi = np.maximum(u_hi, np.where(gi <= n - 2, 0, -1))
        clo[2 * gi + par] = u_lo // U_SLOTS
        chi[2 * gi + par] = np.where(u_hi >= u_lo,
                                     u_hi // U_SLOTS,
                                     u_lo // U_SLOTS - 1)
    return jnp.asarray(clo), jnp.asarray(chi)


def _fw(b: int) -> int:
    """Frame width for the task-body math: when b is a lane-tile
    multiple, every block (B at global col0 = b-1 over lanes [0, 2b),
    D at off over [b, 3b), mirror-U at off+b over [2b, 4b)) is an
    ALIGNED static [b, 2b] lane window with the SAME local col0 = b-1,
    so shears/masks/reductions run on half-width arrays, and the
    shears themselves take their single-pass form (``shear_form``).
    Other bands keep the full 4b width (unaligned static lane slices
    don't lower)."""
    return 2 * b if b % 128 == 0 else 4 * b


def _wave_kernel(base8_ref, delta_ref, clo_ref, chi_ref, rib_ref,
                 out_rib_ref, v_out_ref,
                 tau_out_ref, v0_scr, v1_scr, t0_scr, t1_scr,
                 *, n, b, P, PP, NCH, CH, PAD):
    g = pl.program_id(0)
    par = pl.program_id(1)
    W4 = 4 * b
    off = 2 * b - 1
    stride = 2 * b - 1
    U = U_SLOTS
    FRAMES = (b % 128 == 0)
    FW = _fw(b)
    c0B = b - 1                      # == off - b: the B frame needs no
    #                                  lane offset in either mode
    c0D = b - 1 if FRAMES else off
    c0U = b - 1 if FRAMES else off + b
    c0S = 2 * b - 2                  # == off - 1 (seed column, B frame)

    @pl.when((g == 0) & (par == 0))
    def _init():
        out_rib_ref[:] = rib_ref[:]
        v0_scr[:] = jnp.zeros_like(v0_scr)
        v1_scr[:] = jnp.zeros_like(v1_scr)
        t0_scr[:] = jnp.zeros_like(t0_scr)
        t1_scr[:] = jnp.zeros_like(t1_scr)

    b8 = pl.multiple_of(base8_ref[g], 8)
    delta = delta_ref[g]

    li1 = lax.broadcasted_iota(jnp.int32, (b, 1), 0)
    lcF = lax.broadcasted_iota(jnp.int32, (b, FW), 1)
    liF = lax.broadcasted_iota(jnp.int32, (b, FW), 0)
    colB = lcF - c0B + liF
    colD = lcF - c0D + liF
    colU = lcF - c0U + liF
    colS = lcF - c0S + liF               # seed column c = s (B frame)
    shl = _shear_lanes(b, FW, c0B)       # c0B == c0D == c0U where not None
    E = (lcF == li1).astype(jnp.float32)    # [b, FW] one-hot
    rowPP = lax.broadcasted_iota(jnp.int32, (PP, 1), 0)
    ohu = lax.broadcasted_iota(jnp.int32, (U, PP), 0)   # slot uu
    ohr = lax.broadcasted_iota(jnp.int32, (U, PP), 1)   # scratch row
    ohtl = lax.broadcasted_iota(jnp.int32, (U, TAUP), 1)
    ohtu = lax.broadcasted_iota(jnp.int32, (U, TAUP), 0)
    laneT = lax.broadcasted_iota(jnp.int32, (1, TAUP), 1)

    # previous-wave chain source: par 0 reads parity-1 scratch at slot
    # u-1; par 1 reads parity-0 scratch (same g) at slot u
    vprev_all = jnp.where(par == 0, v1_scr[:], v0_scr[:])   # [PP, FW]
    tprev_all = jnp.where(par == 0, t1_scr[:], t0_scr[:])   # [1, TAUP]

    def chunk(c, carry):
        vnew_all, tnew_all = carry
        cU = c * U
        cbase = pl.multiple_of(b8 + par * b + cU * stride, 8)
        win = out_rib_ref[pl.ds(cbase, CH), :]
        # negative DYNAMIC sublane shifts mis-lower on this toolchain
        # (roll(-d) lands at -(d + 128) on multi-tile arrays —
        # measured); roll up by `size - delta` instead, guarding 0
        up = jnp.where(delta == 0, 0, CH - delta)
        win = pltpu.roll(win, shift=up, axis=0)
        # local row 0 == matrix row (g+1-b) + par*b + cU*stride

        # chain rows/taus for the whole chunk via one-hot MXU
        previdx = cU - 1 + par + ohu                    # [U, PP]
        ohp = (ohr == previdx).astype(jnp.float32)
        Vp = lax.dot_general(ohp, vprev_all,
                             dimension_numbers=(((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ohpt = (ohtl == (cU - 1 + par + ohtu)).astype(jnp.float32)
        Tp = lax.dot_general(ohpt, tprev_all,
                             dimension_numbers=(((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [U,1]

        deltas = []
        for uu in range(U):
            u_idx = cU + uu
            r_u = uu * stride                # static local window row
            s_u = g - u_idx
            t_u = par + 2 * u_idx
            i0 = s_u + 1 + t_u * b
            is_chase = ((s_u >= 0) & (s_u < n - 1) & (t_u >= 1)
                        & (t_u * b <= n - 2 - s_u) & (i0 <= n - 1))
            if uu == 0:
                # the seed task (t = 0) only ever lives at slot 0 of
                # chunk 0, parity 0 — traced-gated into this one body
                is_seed = ((par == 0) & (c == 0) & (s_u >= 0)
                           & (s_u < n - 1) & (i0 <= n - 1))
                do_any = is_seed | is_chase
            else:
                is_seed = jnp.asarray(False)
                do_any = is_chase
            L2 = jnp.clip(n - i0, 0, b)
            L1 = jnp.clip(n - (i0 - b), 0, b)

            slab = win[r_u:r_u + 2 * b, :]   # [2b, W4]
            if FRAMES:
                urowsU = slab[:b, 2 * b:4 * b]   # mirror-U frame
                browsB = slab[b:, 0:2 * b]       # B frame
                browsD = slab[b:, b:3 * b]       # D frame
            else:
                urowsU = slab[:b, :]
                browsB = slab[b:, :]
                browsD = browsB

            mrow2 = liF < L2
            mrow1 = liF < L1
            mB = (colB >= 0) & (colB < L1) & mrow2
            mD = (colD >= 0) & (colD < L2) & mrow2
            mU = (colU >= 0) & (colU < L2) & mrow1

            B0 = jnp.where(mB, browsB, 0.0)
            U0 = jnp.where(mU, urowsU, 0.0)

            # ---------------- chase branch -----------------------
            vp_row = Vp[uu:uu + 1, :]              # [1, FW]
            tp = Tp[uu, 0]
            VPb = jnp.where(mB, _shear_rowvec(vp_row, c0B, b, FW, shl),
                            0.0)
            wv = jnp.sum(B0 * VPb, axis=1, keepdims=True)  # B0 vp [b,1]
            B1 = B0 - tp * wv * VPb
            # mirror: U1 = U0 - tp * vp_col x wv_row
            vp_col = _row2col(vp_row, E)                   # [b, 1]
            WVu = jnp.where(mU, _shear_rowvec(
                _col2row(wv, E), c0U, b, FW, shl), 0.0)
            U1 = U0 - tp * vp_col * WVu
            # larfg on B1 col k=0 (bulge column)
            e0 = (colB == 0) & mrow2
            x_ch = jnp.sum(jnp.where(e0, B1, 0.0), axis=1,
                           keepdims=True)               # [b, 1]
            v_ch, tau_ch, beta_ch = _larfg_f32(
                _col2row(x_ch, E), L2, FW)
            # col-0 fix: (beta, 0, ..) — and its mirror on U row 0
            B1 = jnp.where(e0, jnp.where(li1 == 0, beta_ch, 0.0), B1)
            rowU0 = (liF == 0) & (colU >= 0) & (colU < L2)
            U1 = jnp.where(rowU0, jnp.where(colU == 0, beta_ch, 0.0),
                           U1)
            # z[k] = sum_i v[i] B1[i, k], k >= 1 — exact column
            # reduction via anti-shear + sublane sum
            v_col = _row2col(v_ch, E)
            Qz = jnp.where(mB & (colB >= 1), B1, 0.0) * v_col
            z_row = _antishear_sum(Qz, b, FW)      # z[k] at c0B + k
            z_at0 = pltpu.roll(z_row, shift=FW - c0B, axis=1)
            z_col = _row2col(z_at0, E)
            # B2 = B1 - tau v_col x z_row ; U2 = U1 - tau z_col x v_row
            VUs = jnp.where(mU, _shear_rowvec(v_ch, c0U, b, FW, shl),
                            0.0)
            Zb = jnp.where(mB & (colB >= 1), _shear_rowvec(
                z_at0, c0B, b, FW, shl), 0.0)
            B2 = B1 - tau_ch * v_col * Zb
            U2 = U1 - tau_ch * z_col * VUs
            # D two-sided: w = v^H D0 exactly (anti-shear), then
            # D1 = D0 - tau v x w ; D2 = D1 - tau (D1 v) x v^H
            D0 = jnp.where(mD, browsD, 0.0)
            VDs = jnp.where(mD, _shear_rowvec(v_ch, c0D, b, FW, shl), 0.0)
            Qw = D0 * v_col
            w_at0 = pltpu.roll(_antishear_sum(Qw, b, FW),
                               shift=FW - c0D, axis=1)
            Ws = jnp.where(mD, _shear_rowvec(w_at0, c0D, b, FW, shl), 0.0)
            D1 = D0 - tau_ch * v_col * Ws
            y2 = jnp.sum(D1 * VDs, axis=1, keepdims=True)
            D2 = D1 - tau_ch * y2 * VDs

            dB_ch = jnp.where(mB, B2 - browsB, 0.0)
            dD_ch = jnp.where(mD, D2 - browsD, 0.0)
            dU_ch = jnp.where(mU | rowU0, U2 - urowsU, 0.0)

            # ---------------- seed branch ------------------------
            if uu == 0:
                eS = (colS == 0) & mrow2
                x_sd = jnp.sum(jnp.where(eS, browsB, 0.0), axis=1,
                               keepdims=True)
                v_sd, tau_sd, beta_sd = _larfg_f32(
                    _col2row(x_sd, E), L2, FW)
                # seed column <- (beta, 0, ..); its mirror row s (=
                # urows row b-1) <- the same values transposed — in
                # frame coords the mirror row is colU over [0, L2)
                eM = (liF == b - 1) & (colU >= 0) & (colU < L2)
                dB_sd = jnp.where(
                    eS, jnp.where(li1 == 0, beta_sd, 0.0) - browsB,
                    0.0)
                dU_sd = jnp.where(
                    eM, jnp.where(colU == 0, beta_sd, 0.0) - urowsU,
                    0.0)
                # seed's diag block: the seed-column update is outside
                # mD (c - r < 0), so D0s == D0
                VDsd = jnp.where(mD, _shear_rowvec(v_sd, c0D, b, FW, shl),
                                 0.0)
                vsd_col = _row2col(v_sd, E)
                ws_at0 = pltpu.roll(
                    _antishear_sum(D0 * vsd_col, b, FW),
                    shift=FW - c0D, axis=1)
                Wss = jnp.where(mD, _shear_rowvec(ws_at0, c0D, b, FW, shl),
                                0.0)
                D1s = D0 - tau_sd * vsd_col * Wss
                y2s = jnp.sum(D1s * VDsd, axis=1, keepdims=True)
                D2s = D1s - tau_sd * y2s * VDsd
                dD_sd = jnp.where(mD, D2s - browsD, 0.0)

                dB = jnp.where(is_seed, dB_sd, dB_ch)
                dD = jnp.where(is_seed, dD_sd, dD_ch)
                dU = jnp.where(is_seed, dU_sd, dU_ch)
                v_task = jnp.where(is_seed, v_sd, v_ch)
                t_task = jnp.where(is_seed, tau_sd, tau_ch)
            else:
                dB, dD, dU = dB_ch, dD_ch, dU_ch
                v_task, t_task = v_ch, tau_ch

            if FRAMES:
                zb = jnp.zeros((b, b), jnp.float32)
                d_up = jnp.concatenate([zb, zb, dU], axis=1)
                d_dn = (jnp.concatenate([dB, zb, zb], axis=1)
                        + jnp.concatenate([zb, dD, zb], axis=1))
            else:
                d_up, d_dn = dU, dB + dD
            d_slab = jnp.concatenate(
                [jnp.where(do_any, d_up, 0.0),
                 jnp.where(do_any, d_dn, 0.0)], axis=0)
            deltas.append(d_slab)            # [2b, W4]
            v_task = jnp.where(do_any, v_task, 0.0)
            t_task = jnp.where(do_any, t_task, 0.0)
            vnew_all = jnp.where(rowPP == u_idx, v_task, vnew_all)
            tnew_all = jnp.where(laneT == u_idx, t_task, tnew_all)

        # compose the chunk's wave slice: slabs start at uu*stride and
        # overlap by ONE row (2b vs stride 2b-1); deltas are
        # element-disjoint so the overlap rows ADD. The cross-chunk
        # overlap row composes through the sequential ribbon RMW.
        pieces = []
        for uu in range(U):
            d = deltas[uu]
            head = d[:1, :] if uu == 0 else d[:1, :] + deltas[uu - 1][
                stride:, :]
            pieces.append(head)
            pieces.append(d[1:stride, :])
        pieces.append(deltas[U - 1][stride:, :])
        comp = jnp.concatenate(pieces, axis=0)
        rows_used = U * stride + 1
        win = win + jnp.pad(
            comp, ((0, CH - rows_used), (0, 0)))
        win = pltpu.roll(win, shift=delta, axis=0)
        out_rib_ref[pl.ds(cbase, CH), :] = win
        return vnew_all, tnew_all

    i2 = g * 2 + par
    vnew_all, tnew_all = lax.fori_loop(
        clo_ref[i2], chi_ref[i2] + 1, chunk,
        (jnp.zeros((PP, FW), jnp.float32),
         jnp.zeros((1, TAUP), jnp.float32)))

    @pl.when(par == 0)
    def _store0():
        v0_scr[:] = vnew_all
        t0_scr[:] = tnew_all

    @pl.when(par == 1)
    def _store1():
        v1_scr[:] = vnew_all
        t1_scr[:] = tnew_all

    v_out_ref[0, 0] = vnew_all[:, :b]
    tau_out_ref[0, 0] = jnp.broadcast_to(tnew_all, (8, TAUP))


@partial(jax.jit, static_argnames=("band", "n", "interpret"))
def _hb2st_vmem_jit(ab, band, n, interpret=False):
    b = band
    W4 = 4 * b
    off = 2 * b - 1
    S = n - 1
    T = max_chase(n, b)
    G, P, PP, NCH, CH, PAD, ROWS = _geometry(n, b)
    # trace-time witness of the tau-tile capacity the packed
    # read-back below relies on: uu = tt//2 <= (T-1)//2 < P <= TAUP
    assert P <= TAUP, (
        f"hb2st_vmem: {P} chase slots exceed the {TAUP}-lane tau "
        "tile; vmem_applies must reject this shape")

    R = jnp.zeros((ROWS, W4), jnp.float32)
    for d in range(b + 1):
        rr = jnp.arange(n - d)
        R = R.at[rr + d + PAD, off - d].set(ab[d, : n - d])
        if d > 0:
            R = R.at[rr + PAD, off + d].set(ab[d, : n - d])

    gi = jnp.arange(G, dtype=jnp.int32)
    base = gi + 8                    # ribbon row of window start
    base8 = (base // 8) * 8
    delta = base - base8
    clo, chi = _active_chunk_range(n, b, G)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(G, 2),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, PP, b), lambda g, p, *_: (g, p, 0, 0)),
            pl.BlockSpec((1, 1, 8, TAUP), lambda g, p, *_: (g, p, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((PP, _fw(band)), jnp.float32),
            pltpu.VMEM((PP, _fw(band)), jnp.float32),
            pltpu.VMEM((1, TAUP), jnp.float32),
            pltpu.VMEM((1, TAUP), jnp.float32),
        ],
    )
    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=120 * 1024 * 1024)
    Rf, V_all, tau_all = pl.pallas_call(
        partial(_wave_kernel, n=n, b=b, P=P, PP=PP, NCH=NCH, CH=CH,
                PAD=PAD),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((ROWS, W4), jnp.float32),
            jax.ShapeDtypeStruct((G, 2, PP, b), jnp.float32),
            jax.ShapeDtypeStruct((G, 2, 8, TAUP), jnp.float32),
        ),
        input_output_aliases={4: 0},
        interpret=interpret,
        **kw,
    )(base8, delta, clo, chi, R)

    rr = jnp.arange(n)
    d_out = Rf[rr + PAD, off]
    re = jnp.arange(n - 1)
    e_out = Rf[re + 1 + PAD, off - 1]

    # task (s, t) ran in wave 2s + t => step g = s + t//2, par = t%2,
    # slot u = t//2
    ss, tt = jnp.meshgrid(jnp.arange(S), jnp.arange(T), indexing="ij")
    gg = jnp.clip(ss + tt // 2, 0, G - 1)
    uu = tt // 2
    V = V_all[gg, tt % 2, uu]                # [S, T, b]
    tau = tau_all[gg, tt % 2, 0, uu]
    return d_out, e_out, V, tau


# the design's 8 <= b <= 256 envelope (wider bands break the sheared
# 4b-lane layout economics and were never validated) and the VMEM
# ceiling the kernel compiles against (vmem_limit_bytes above): the
# whole ribbon must stay resident with headroom for the window copy,
# the per-step output blocks and double-buffering
_B_MAX = 256
_VMEM_RIBBON_BUDGET = 96 * 1024 * 1024


def vmem_applies(n: int, band: int, dtype) -> bool:
    """True when the VMEM-resident chaser supports (n, band, dtype) —
    shared gate for hb2st_wave_vmem and the hb2st dispatch."""
    if not (np.dtype(dtype) == np.float32
            and 8 <= band <= _B_MAX and (band & (band - 1)) == 0
            and n > 2 * band):
        return False
    _G, P, PP, _NCH, CH, _PAD, ROWS = _geometry(n, band)
    # slot capacity: task t stores its tau in lane u = t//2 of ONE
    # 128-lane tile, so the kernel supports at most TAUP slots. With
    # P > TAUP the store would write lane >= 128 (dropped) and the
    # packed read-back tau_all[..., 0, uu] would clamp to lane 127 —
    # silently wrong eigenvalues from n = 32770 at band 128. Fall
    # back to the XLA wave, which sizes its packs by P.
    if P > TAUP:
        return False
    W4 = 4 * band
    # resident set: ribbon + aligned chunk window (+ its roll double
    # buffer) + the two reflector-chain scratch pairs — all f32
    resident = (ROWS * W4 + 2 * CH * W4 + 2 * (PP * W4 + TAUP)) * 4
    return resident <= _VMEM_RIBBON_BUDGET


def preferred_eig_band(n: int, dtype, default: int = 256) -> int:
    """Two-stage band width for heev/gesvd pipelines: the chase is
    the pipeline's dominant cost, and the VMEM chaser at band 128
    beats the XLA wave at 256 by a wide margin (at n=8192 on one v5e
    1.11 s since its shears are single-pass, PR 45; 2.37 s with the
    ladder before; the wave was 5.95 s in round 5 and has not been
    timed since) — so prefer 128 whenever the VMEM kernel would take
    the problem ON THE COMPILED TPU PATH (f32 real only: the gate
    must see the ACTUAL dtype — complex inputs fall back to the XLA
    wave, where the tuned 256 default stands)."""
    try:
        if (jax.default_backend() == "tpu"
                and vmem_applies(n, 128, dtype)):
            return 128
    except Exception:  # pragma: no cover
        pass
    return default


def hb2st_wave_vmem(ab, interpret=None):
    """VMEM-resident wavefront hb2st: contract of band_bulge.hb2st
    (lower band storage ab[d, j] = A[j+d, j], d = 0..band), f32 real
    only; returns (d, e, V, tau) — d/e as numpy (host tridiagonal
    stage), V/tau as DEVICE arrays in the shared packed format of
    linalg/bulge.apply_bulge_reflectors (the fallback wave path
    returns numpy packs; both are accepted by every consumer via
    jnp/np.asarray). Falls back to the XLA
    wavefront for unsupported shapes/dtypes (band not a power of two
    in [8, 256], non-f32, or a ribbon too large for VMEM).
    ``interpret=None`` compiles on TPU and interprets elsewhere (the
    Mosaic kernel only targets TPU)."""
    ab = np.asarray(ab)
    band = ab.shape[0] - 1
    n = ab.shape[1]
    if not vmem_applies(n, band, ab.dtype):
        from .band_bulge_wave import hb2st_wave
        return hb2st_wave(ab)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    obs.count("hb2st.shear", 1, form=chase_shear_form(band))
    d, e, V, tau = _hb2st_vmem_jit(jnp.asarray(ab), band, n,
                                   interpret=interpret)
    # d/e go to the host tridiagonal stage; V/tau stay DEVICE arrays —
    # values-only pipelines never read them, and pulling the [S, T, b]
    # pack to the host costs ~0.6 GB at n=12288/b=128 (the
    # vectors path feeds them straight back into device einsums via
    # apply_bulge_reflectors' jnp.asarray)
    d, e = obs.sync_read("hb2st.tridiagonal", jax.device_get, (d, e))
    return d, e, V, tau
