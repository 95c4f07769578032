"""Pallas pivoted-LU panel kernel — the fast-path panel engine.

Reference analog: the dedicated LU panel machinery of
``src/internal/internal_getrf.cc:21-125`` and
``src/internal/Tile_getrf.hh:161-300`` (per-thread local argmax, spin
ThreadBarrier reduce, row swap, rank-ib update). The reference makes
the panel fast with CPU thread teams; XLA's built-in ``lu`` pays a
~6 µs/column latency floor (measured, BASELINE.md) and LAPACK-style
row swaps cost ~10.6 ms/panel in row gathers on (8,128)-tiled HBM.

TPU redesign — *pivoting by index, no row movement*:

* The subpanel is held **transposed** ``[W, H]`` so the panel height
  runs along the lane dimension: a [128, 16384] f32 block is 8 MB and
  lives entirely in VMEM; per-column ops are single-vreg-row sweeps,
  and "column j" is a *static* sublane index (the column loop is
  fully unrolled at trace time).
* Rows are never swapped. An **active-lane mask** tracks which rows
  are not yet pivots; pivot selection is a masked argmax over lanes,
  the pivot row is extracted with a one-hot reduction, and the
  multiplier row is written back in place. Eliminated rows simply
  leave the mask — the physical permutation is applied *once* per
  compaction group by the driver (linalg/getrf.py), not per panel.
* Blocked right-looking updates: within an ``ib``-column strip the
  rank-1 updates run on the VPU; at strip boundaries the remaining
  subpanel columns get one MXU update ``P -= Uᵀ·Lstrip`` with the
  strip's U entries recovered by a one-hot MXU contraction and a
  tiny [ib, ib] forward substitution (the strip's pivot rows were
  not updated in-strip — exactly LAPACK's delayed-update algebra).

Pivot choices match classic partial pivoting (ties → lowest index;
an all-zero column self-selects the first active row and counts into
``info``, LAPACK semantics). Panels taller than VMEM go through a
CALU tournament (reference src/getrf_tntpiv.cc) built from the same
kernel: chunk-local winners, a winners-only final round, then one
MXU triangular solve for the full-height multipliers.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fold_enabled() -> bool:
    import os
    return os.environ.get("SLATE_LU_FOLD", "1") != "0"


W = 128          # subpanel width (one lane tile)
IB = 8           # strip width for the in-kernel blocked update
H_MAX = 16384    # tallest single-shot subpanel: the aliased [128, H]
                 # f32 buffer (8 MB) + one [128, H_CHUNK] strip-end
                 # value + temporaries must fit 16 MB scoped VMEM
H_CHUNK = 4096   # strip-end delayed update processed in lane chunks
                 # (avoids materializing a second full [W, h] value;
                 # 8192 measured 838 KB over the 16 MB scoped-VMEM
                 # limit at h=16384 — two chunk values live at once)

# the ceiling every panel-PLU pallas_call compiles against
# (vmem_limit_bytes below): operand windows + Mosaic's cumulative
# scoped-temporary accounting must fit it with headroom
_PLU_VMEM_BUDGET = 40 * 1024 * 1024


def _plu_vmem_footprint(h: int, w: int = W) -> int:
    """Resident VMEM estimate (bytes) for one panel-PLU kernel call
    at subpanel height ``h`` and window width ``w``: the aliased
    [w, h] panel window, the activity row in and out, the pivot and
    info tiles (one padded lane tile each), and the strip-end chunk
    temporaries Mosaic's scoped accounting charges cumulatively —
    ~2× the panel window at h=16384 (the measured ~16.8 MB that
    forced the 40 MB ceiling). Asserted against _PLU_VMEM_BUDGET at
    every call site so a new window must be added HERE to compile."""
    return (w * h + 2 * W * h + 2 * h + 2 * W) * 4


def _plu_kernel(pT_ref, act_ref, out_ref, actout_ref, piv_ref, info_ref,
                *, h):
    """Pivoted LU of a transposed subpanel.

    pT_ref:   [W, h] f32 — subpanel, columns as sublanes (transposed).
    act_ref:  [1, h] f32 — 1.0 at rows still eligible as pivots.
    out_ref:  [W, h] f32 — factored subpanel (aliased onto pT_ref).
    actout:   [1, h] f32 — act with this subpanel's pivots cleared.
    piv_ref:  [1, W] i32 — physical row (lane) of each elimination step.
    info_ref: [1, 1] i32 — number of zero pivots.

    Structure: a ``fori_loop`` over W/IB strips (keeps the Mosaic trace
    small — full unrolling of all W columns compiled ~10× slower); each
    strip holds its IB panel columns as a [IB, h] value, runs IB
    unrolled elimination steps on the VPU, then applies one masked MXU
    block update to the whole [W, h] subpanel (LAPACK's delayed-update
    algebra: the strip's U rows are recovered by a one-hot contraction
    and a tiny [IB, IB] unit-lower inverse, exact because the nilpotent
    Neumann series terminates).
    """
    lane = lax.broadcasted_iota(jnp.int32, (1, h), 1)
    wlane = lax.broadcasted_iota(jnp.int32, (1, W), 1)
    rowW = lax.broadcasted_iota(jnp.int32, (W, 1), 0)
    row8 = lax.broadcasted_iota(jnp.int32, (IB, 1), 0)
    out_ref[:] = pT_ref[:]

    def strip(si, carry):
        act, piv, info = carry
        s0 = pl.multiple_of(si * IB, IB)
        blk = out_ref[pl.ds(s0, IB), :]                  # [IB, h]
        lrows = []       # multiplier rows of this strip
        onehots = []     # pivot-lane indicators
        for jj in range(IB):
            colv = blk[jj:jj + 1, :]                     # [1, h]
            # masked pivot search; all-zero column → first active lane
            # (max + index-min: the Mosaic-stable formulation — argmax
            # variants fail TPU lowering; ties → lowest index, LAPACK
            # semantics)
            score = jnp.where(act > 0, jnp.abs(colv), -1.0)
            mx = jnp.max(score)
            r = jnp.min(jnp.where(score >= mx, lane, h))
            onehot = (lane == r).astype(colv.dtype)
            # ONE [IB, h] contraction serves double duty: row jj gives
            # the pivot value, rows > jj the in-strip U entries (MXU
            # dot — the VPU reduction tree over 16k lanes was the
            # sweep's second-hottest op)
            uc0 = lax.dot_general(
                blk, onehot, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            pivval = uc0[jj, 0]
            info = info + (pivval == 0.0).astype(jnp.int32)
            rsafe = jnp.where(pivval == 0.0, 1.0,
                              1.0 / jnp.where(pivval == 0.0, 1.0,
                                              pivval))
            act = act * (1.0 - onehot)
            lvec = colv * act * rsafe
            # fused single pass: write the multiplier row AND apply the
            # eager rank-1 to the strip's not-yet-factored columns
            blk = jnp.where(row8 == jj,
                            jnp.where(act > 0, lvec, colv),
                            blk - jnp.where(row8 > jj, uc0 * lvec, 0.0))
            piv = jnp.where(wlane == s0 + jj, r, piv)
            lrows.append(lvec)
            onehots.append(onehot)
        out_ref[pl.ds(s0, IB), :] = blk
        Ls = jnp.concatenate(lrows, axis=0)              # [IB, h]
        Sel = jnp.concatenate(onehots, axis=0)           # [IB, h]
        # strip pivot rows' pre-strip values in every subpanel column,
        # accumulated over lane chunks so only one [W, H_CHUNK] value
        # is live at a time (the full [W, h] copy would double the
        # kernel's VMEM footprint)
        nch = max(1, -(-h // H_CHUNK))
        praw = jnp.zeros((W, IB), jnp.float32)
        for cc in range(nch):
            lo = cc * H_CHUNK
            wd = min(H_CHUNK, h - lo)
            praw = praw + lax.dot_general(               # [W, IB]
                out_ref[:, pl.ds(lo, wd)], Sel[:, lo:lo + wd],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        # L8[jj, i] = multiplier of strip pivot row jj at strip step i
        L8 = jnp.transpose(lax.dot_general(              # [IB, IB]
            Ls, Sel, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32))
        ii8 = lax.broadcasted_iota(jnp.int32, (IB, IB), 0)
        jj8 = lax.broadcasted_iota(jnp.int32, (IB, IB), 1)
        L8s = jnp.where(ii8 > jj8, L8, 0.0)
        inv = jnp.eye(IB, dtype=jnp.float32)
        for _ in range(1, IB):       # (I+N)⁻¹ exact: N is nilpotent
            inv = jnp.eye(IB, dtype=jnp.float32) - lax.dot_general(
                L8s, inv, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        uT = lax.dot_general(                            # [W, IB]
            praw, inv, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        # only strips BELOW this one take the delayed update
        uT = jnp.where(rowW >= s0 + IB, uT, 0.0)
        for cc in range(nch):
            lo = cc * H_CHUNK
            wd = min(H_CHUNK, h - lo)
            out_ref[:, pl.ds(lo, wd)] = (
                out_ref[:, pl.ds(lo, wd)] - lax.dot_general(
                    uT, Ls[:, lo:lo + wd],
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
        return act, piv, info

    act, piv, info = lax.fori_loop(
        0, W // IB, strip,
        (act_ref[:], jnp.zeros((1, W), jnp.int32),
         jnp.zeros((1, 1), jnp.int32)))
    actout_ref[:] = act
    piv_ref[:] = piv
    info_ref[:] = info


def _plu_kernel_folded(pF_ref, act_ref, out_ref, actout_ref, piv_ref,
                       info_ref, *, h):
    """Folded-layout twin of :func:`_plu_kernel`.

    The flat kernel's per-column ops run on ``[1, h]`` vectors — one
    sublane of each (8, 128) vreg, 7/8 of the VPU idle (measured
    ~6 µs/col at h=16384, trace r4). Here the subpanel is held FOLDED
    ``[8, W, h/8]``: panel column j is the [8, h/8] block ``pF[:, j, :]``
    — all 8 sublanes live — so the search/score/mask sweep ops shrink
    from 128 vregs to 16. Pivot row index r is reconstructed globally
    as s·(h/8) + l, preserving LAPACK lowest-index tie semantics; the
    strip-end MXU algebra contracts the folded axis per-segment (8
    dots — same flop count). A per-column folded RESHAPE was measured
    ~2× slower than the flat ops it replaced (ROADMAP round 3) — the
    fix is to never reshape: the fold IS the storage layout, produced
    by :func:`transpose_fold` outside the kernel.
    """
    L = h // 8
    LCH = min(L, H_CHUNK // 8)         # strip-end chunk on the lane dim
    fold_iota = (lax.broadcasted_iota(jnp.int32, (8, L), 0) * L
                 + lax.broadcasted_iota(jnp.int32, (8, L), 1))
    wlane = lax.broadcasted_iota(jnp.int32, (1, W), 1)
    rowW = lax.broadcasted_iota(jnp.int32, (W, 1), 0)
    row3 = lax.broadcasted_iota(jnp.int32, (1, IB, 1), 1)
    out_ref[:] = pF_ref[:]

    def strip(si, carry):
        act, piv, info = carry
        s0 = pl.multiple_of(si * IB, IB)
        blk = out_ref[:, pl.ds(s0, IB), :]           # [8, IB, L]
        lrows = []
        onehots = []
        for jj in range(IB):
            colv = blk[:, jj, :]                     # [8, L]
            score = jnp.where(act > 0, jnp.abs(colv), -1.0)
            mx = jnp.max(score)
            r = jnp.min(jnp.where(score >= mx, fold_iota, h))
            onehot = (fold_iota == r).astype(colv.dtype)
            # pivot value + in-strip U entries in one masked reduce
            uc0 = jnp.sum(blk * onehot[:, None, :], axis=(0, 2))  # [IB]
            pivval = uc0[jj]
            info = info + (pivval == 0.0).astype(jnp.int32)
            rsafe = jnp.where(pivval == 0.0, 1.0,
                              1.0 / jnp.where(pivval == 0.0, 1.0,
                                              pivval))
            act = act * (1.0 - onehot)
            lvec = colv * act * rsafe                # [8, L]
            blk = jnp.where(
                row3 == jj,
                jnp.where(act > 0, lvec, colv)[:, None, :],
                blk - jnp.where(row3 > jj,
                                uc0[None, :, None] * lvec[:, None, :],
                                0.0))
            piv = jnp.where(wlane == s0 + jj, r, piv)
            lrows.append(lvec)
            onehots.append(onehot)
        out_ref[:, pl.ds(s0, IB), :] = blk
        Ls = jnp.stack(lrows, axis=0)                # [IB, 8, L]
        Sel = jnp.stack(onehots, axis=0)             # [IB, 8, L]
        SelT = jnp.transpose(Sel, (1, 0, 2))         # [8, IB, L]
        nch = max(1, -(-L // LCH))
        praw = jnp.zeros((W, IB), jnp.float32)
        for cc in range(nch):
            lo = cc * LCH
            wd = min(LCH, L - lo)
            # ONE batched contraction over the folded segments instead
            # of 8 tiny [W, wd]x[IB, wd] dots (per-dot MXU setup
            # latency dominated the strip-end at full height)
            valc = out_ref[:, :, pl.ds(lo, wd)]      # [8, W, wd]
            pb = lax.dot_general(
                valc, SelT[:, :, lo:lo + wd],
                dimension_numbers=(((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)  # [8, W, IB]
            praw = praw + jnp.sum(pb, axis=0)
        L8b = lax.dot_general(
            jnp.transpose(Ls, (1, 0, 2)), SelT,
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)      # [8, IB, IB]
        L8 = jnp.transpose(jnp.sum(L8b, axis=0))
        ii8 = lax.broadcasted_iota(jnp.int32, (IB, IB), 0)
        jj8 = lax.broadcasted_iota(jnp.int32, (IB, IB), 1)
        L8s = jnp.where(ii8 > jj8, L8, 0.0)
        inv = jnp.eye(IB, dtype=jnp.float32)
        for _ in range(1, IB):       # (I+N)⁻¹ exact: N is nilpotent
            inv = jnp.eye(IB, dtype=jnp.float32) - lax.dot_general(
                L8s, inv, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        uT = lax.dot_general(
            praw, inv, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        uT = jnp.where(rowW >= s0 + IB, uT, 0.0)
        LsT = jnp.transpose(Ls, (1, 0, 2))           # [8, IB, L]
        uTb = jnp.broadcast_to(uT[None], (8, W, IB))
        for cc in range(nch):
            lo = cc * LCH
            wd = min(LCH, L - lo)
            upd = lax.dot_general(
                uTb, LsT[:, :, lo:lo + wd],
                dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)  # [8, W, wd]
            out_ref[:, :, pl.ds(lo, wd)] = (
                out_ref[:, :, pl.ds(lo, wd)] - upd)
        return act, piv, info

    act, piv, info = lax.fori_loop(
        0, W // IB, strip,
        (act_ref[:], jnp.zeros((1, W), jnp.int32),
         jnp.zeros((1, 1), jnp.int32)))
    actout_ref[:] = act
    piv_ref[:] = piv
    info_ref[:] = info


def _t_kernel(x_ref, o_ref):
    o_ref[:] = jnp.transpose(x_ref[:])


def transpose_tiled(x, interpret: bool = False):
    """[m, k] → [k, m] via a grid-chunked Pallas kernel (m a multiple
    of 128). Functionally jnp.transpose — the point is LAYOUT
    CONTROL: Pallas pins default (row-major) layouts on both sides,
    so XLA cannot "optimize" the transpose by flipping the LAYOUT of
    the surrounding big arrays. Feeding the panel kernels through a
    plain jnp.transpose made layout assignment keep the whole [n, n]
    matrix transposed through the panel phase and convert it back for
    the compaction gathers — two matrix-sized copies per group that
    OOM'd the 45k class (HLO-verified, BASELINE.md round 4)."""
    m, k = x.shape
    CH = 128
    if m % CH != 0 and k % CH != 0:
        # ragged shapes (the kernel contract only needs H % 8 == 0):
        # plain transpose — layout control matters only for the
        # production multiples-of-128 panels
        return jnp.transpose(x)
    if m >= k and m % CH == 0:  # chunk the tall axis
        assert m % CH == 0
        return pl.pallas_call(
            _t_kernel,
            grid=(m // CH,),
            in_specs=[pl.BlockSpec((CH, k), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((k, CH), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((k, m), x.dtype),
            interpret=interpret,
        )(x)
    assert k % CH == 0
    return pl.pallas_call(
        _t_kernel,
        grid=(k // CH,),
        in_specs=[pl.BlockSpec((m, CH), lambda i: (0, i))],
        out_specs=pl.BlockSpec((CH, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((k, m), x.dtype),
        interpret=interpret,
    )(x)


def _tf_kernel(x_ref, o_ref):
    o_ref[0] = jnp.transpose(x_ref[:])


def transpose_fold(x, interpret: bool = False):
    """[h, W] → folded [8, W, h/8] with out[s, w, l] = x[s·(h/8)+l, w].

    The folded kernel's storage producer: one grid step per segment s
    transposes the [h/8, W] row block. Pallas pins layouts on both
    sides (same rationale as transpose_tiled)."""
    h, w = x.shape
    L = h // 8
    return pl.pallas_call(
        _tf_kernel,
        grid=(8,),
        in_specs=[pl.BlockSpec((L, w), lambda s: (s, 0))],
        out_specs=pl.BlockSpec((1, w, L), lambda s: (s, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, w, L), x.dtype),
        interpret=interpret,
    )(x)


def fold_panel(x, interpret: bool = False):
    """[hw, nb] panel → folded [8, nb, hw/8] in column chunks (blocks
    stay under the 16 MB scoped-VMEM default). One fold per PANEL:
    feeding the subpanel kernels [8, W, L] SLICES of this buffer
    measures ~0.29 ms/kernel at h=16384 vs ~0.74 ms when each kernel's
    input is produced by its own per-subpanel transpose (trace-verified
    device timings, BASELINE.md round 4)."""
    hw, nb = x.shape
    L = hw // 8
    CC = 256 if nb % 256 == 0 else 128    # nb is a multiple of 128
    return pl.pallas_call(
        _tf_kernel,
        grid=(8, nb // CC),
        in_specs=[pl.BlockSpec((L, CC), lambda s, c: (s, c))],
        out_specs=pl.BlockSpec((1, CC, L), lambda s, c: (s, c, 0)),
        out_shape=jax.ShapeDtypeStruct((8, nb, L), x.dtype),
        interpret=interpret,
    )(x)


def unfold_panel(xf, interpret: bool = False):
    """Folded [8, nb, L] → flat [8·L, nb]: inverse of fold_panel."""
    _, nb, L = xf.shape
    CC = 256 if nb % 256 == 0 else 128    # nb is a multiple of 128
    return pl.pallas_call(
        _uf_kernel,
        grid=(8, nb // CC),
        in_specs=[pl.BlockSpec((1, CC, L), lambda s, c: (s, c, 0))],
        out_specs=pl.BlockSpec((L, CC), lambda s, c: (s, c)),
        out_shape=jax.ShapeDtypeStruct((8 * L, nb), xf.dtype),
        interpret=interpret,
    )(xf)


def _uf_kernel(x_ref, o_ref):
    o_ref[:] = jnp.transpose(x_ref[0])


def unfold_transpose(xf, interpret: bool = False):
    """Folded [8, W, L] → flat [8·L, W]: inverse of transpose_fold."""
    _, w, L = xf.shape
    return pl.pallas_call(
        _uf_kernel,
        grid=(8,),
        in_specs=[pl.BlockSpec((1, w, L), lambda s: (s, 0, 0))],
        out_specs=pl.BlockSpec((L, w), lambda s: (s, 0)),
        out_shape=jax.ShapeDtypeStruct((8 * L, w), xf.dtype),
        interpret=interpret,
    )(xf)


def plu_call_folded_block(pcf, act_f, sidx, interpret: bool = False):
    """Factor subpanel ``sidx`` of a folded panel buffer IN PLACE.

    pcf: [8, nb, L] folded panel (fold_panel output); act_f: [8, L];
    sidx: which W-column block to factor (traced scalar — scalar-
    prefetched into the BlockSpec index maps). The whole buffer is
    aliased input→output and Pallas DMAs only the addressed block, so
    the driver's per-subpanel ``slice`` + ``.at[].set`` pairs (and the
    XLA memory-space games around them) disappear. Returns
    (pcf', act_f', piv [1, W], info [1, 1])."""
    _, nb, L = pcf.shape
    h = 8 * L
    # only the addressed (8, W, L) block is DMA'd, not the whole pcf
    assert _plu_vmem_footprint(h, W) <= _PLU_VMEM_BUDGET

    def kern(s_ref, pF_ref, act_ref, out_ref, actout_ref, piv_ref,
             info_ref):
        _plu_kernel_folded(pF_ref, act_ref, out_ref, actout_ref,
                           piv_ref, info_ref, h=h)

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(1,),
        in_specs=[
            pl.BlockSpec((8, W, L), lambda g, s: (0, s[0], 0)),
            pl.BlockSpec((8, L), lambda g, s: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((8, W, L), lambda g, s: (0, s[0], 0)),
            pl.BlockSpec((8, L), lambda g, s: (0, 0)),
            pl.BlockSpec((1, W), lambda g, s: (0, 0)),
            pl.BlockSpec((1, 1), lambda g, s: (0, 0)),
        ])
    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=40 * 1024 * 1024)
    return pl.pallas_call(
        kern,
        grid_spec=gs,
        out_shape=(
            jax.ShapeDtypeStruct(pcf.shape, jnp.float32),
            jax.ShapeDtypeStruct(act_f.shape, jnp.float32),
            jax.ShapeDtypeStruct((1, W), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ),
        input_output_aliases={1: 0},
        interpret=interpret,
        **kw,
    )(jnp.asarray(sidx, jnp.int32).reshape(1), pcf, act_f)


def _plu_call_folded(pF, act_f, interpret: bool):
    h = 8 * pF.shape[2]
    # default BlockSpecs: the WHOLE folded [8, nb, L] buffer resides
    assert _plu_vmem_footprint(h, pF.shape[1]) <= _PLU_VMEM_BUDGET
    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=40 * 1024 * 1024)
    return pl.pallas_call(
        partial(_plu_kernel_folded, h=h),
        out_shape=(
            jax.ShapeDtypeStruct(pF.shape, jnp.float32),
            jax.ShapeDtypeStruct(act_f.shape, jnp.float32),
            jax.ShapeDtypeStruct((1, W), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ),
        input_output_aliases={0: 0},
        interpret=interpret,
        **kw,
    )(pF, act_f)


def _plu_call(pT, act, interpret: bool):
    h = pT.shape[1]
    assert _plu_vmem_footprint(h, W) <= _PLU_VMEM_BUDGET
    kw = {}
    if not interpret:
        # Mosaic's stack accounting charges the strip-end chunk
        # temporaries cumulatively; at h=16384 that lands ~0.8 MB over
        # the default 16 MB scoped-VMEM cap (a compiler budget, not
        # the physical limit) — raise it for this kernel
        kw["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=40 * 1024 * 1024)
    return pl.pallas_call(
        partial(_plu_kernel, h=h),
        out_shape=(
            jax.ShapeDtypeStruct((W, h), jnp.float32),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
            jax.ShapeDtypeStruct((1, W), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ),
        input_output_aliases={0: 0},
        interpret=interpret,
        **kw,
    )(pT, act)


def plu_subpanel(sub: jax.Array, act: jax.Array, interpret: bool = False,
                 fold=None):
    """Pivoted LU of one [H, W] subpanel with pivoting-by-index.

    sub: [H, W] f32, H ≤ H_MAX, H % 8 == 0. act: [H] f32 activity mask.
    Returns (sub_factored, piv[W] physical rows in elimination order,
    act_new, info). Rows are NOT moved: pivot row j keeps its U row in
    place, active rows hold multipliers, inactive rows are untouched.

    ``fold`` selects the folded-layout kernel when the height allows;
    traced callers (getrf's jitted group cores) MUST pass it
    explicitly — the ``None`` default falls back to the SLATE_LU_FOLD
    environment read, which inside a trace would be baked into the
    cached executable (ADVICE r4)."""
    h, w = sub.shape
    assert w == W and h <= H_MAX
    if fold is None:
        fold = _fold_enabled()
    if h % 1024 == 0 and fold:
        # folded layout: h/8 lanes stay 128-aligned (h % 1024 == 0);
        # per-column sweep ops run on [8, h/8] blocks — all sublanes
        # live — instead of [1, h] single-sublane vectors
        pF = transpose_fold(sub, interpret)
        out, actout, piv, info = _plu_call_folded(
            pF, act.reshape(8, h // 8), interpret)
        return (unfold_transpose(out, interpret), piv[0],
                actout.reshape(h), info[0, 0].astype(jnp.int32))
    pT = transpose_tiled(sub, interpret)
    out, actout, piv, info = _plu_call(pT, act.reshape(1, h), interpret)
    return (transpose_tiled(out, interpret), piv[0], actout[0],
            info[0, 0].astype(jnp.int32))


def plu_panel(sub: jax.Array, act: jax.Array, interpret: bool = False,
              fold=None):
    """Pivoted LU of an [H, W] subpanel for any H: single kernel shot
    when the transposed block fits VMEM, else a CALU tournament
    (reference src/getrf_tntpiv.cc) over H_MAX-row chunks:

    1. each chunk elects W winner rows with the same kernel;
    2. the winners' ORIGINAL rows meet in a final round whose LU fixes
       the pivot order and the [W, W] diagonal factor;
    3. all other active rows get their multipliers from one MXU
       triangular solve L = A·U₁₁⁻¹, and the winners' LU rows are
       scattered back by a one-hot matmul (no row movement).
    """
    h, w = sub.shape
    if h <= H_MAX:
        return plu_subpanel(sub, act, interpret, fold=fold)

    nch = -(-h // H_MAX)
    hp = nch * H_MAX
    subp = jnp.pad(sub, ((0, hp - h), (0, 0)))
    actp = jnp.pad(act, (0, hp - h))
    winners = []
    for c in range(nch):
        s = subp[c * H_MAX:(c + 1) * H_MAX]
        a = actp[c * H_MAX:(c + 1) * H_MAX]
        _, piv_c, _, _ = plu_subpanel(s, a, interpret, fold=fold)
        winners.append(piv_c + c * H_MAX)
    wins = jnp.concatenate(winners)                      # [nch*W]
    cand = jnp.take(subp, wins, axis=0)                  # original rows
    candh = nch * W
    pad_to = max(candh, 8)
    final, piv_f, _, info = plu_subpanel(
        jnp.pad(cand, ((0, pad_to - candh), (0, 0))),
        jnp.pad(jnp.ones(candh, sub.dtype), (0, pad_to - candh)),
        interpret, fold=fold)
    piv = jnp.take(wins, piv_f)                          # global rows
    lu_rows = jnp.take(final, piv_f, axis=0)             # [W, W] LU
    u11 = jnp.triu(lu_rows)
    safe_u = u11 + jnp.diag(jnp.where(jnp.diagonal(u11) == 0.0,
                                      jnp.ones(W, u11.dtype),
                                      jnp.zeros(W, u11.dtype)))
    is_piv = jnp.zeros(hp, sub.dtype).at[piv].set(1.0)
    act_new = actp * (1.0 - is_piv)
    # multipliers for every still-active row: L = A·U₁₁⁻¹; columns
    # whose diagonal was patched from 0 get ZERO multipliers — same
    # singular-panel semantics as the in-VMEM kernel and LAPACK
    # (ADVICE r3: the patched 1.0 otherwise leaks garbage into L)
    lall = lax.linalg.triangular_solve(safe_u, subp, left_side=False,
                                       lower=False)
    lall = jnp.where((jnp.diagonal(u11) == 0.0)[None, :],
                     jnp.zeros_like(lall), lall)
    out = jnp.where((act_new > 0)[:, None], lall, subp)
    out = out.at[piv].set(lu_rows)                       # pivot rows' LU
    return out[:h], piv, act_new[:h], info
