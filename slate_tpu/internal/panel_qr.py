"""Pallas Householder QR panel kernel — the geqrf fast-path engine.

Reference analog: the dedicated QR panel machinery of
``src/internal/internal_geqrf.cc:24-450`` (thread-team Householder
panel; the Devices variant at ``:163`` keeps the panel on the GPU).
XLA's built-in ``geqrf`` pays the same ~6 µs/column latency floor as
its ``lu`` (BASELINE.md cost model — ~25 ms of the 57 ms at
[16384, 4096] is panel time).

Same TPU redesign as the pivoted-LU twin (panel_plu.py), minus the
pivot search — which makes this kernel strictly simpler:

* the subpanel is held **transposed** ``[W, h]`` (panel height along
  lanes, one [128, 16384] f32 block = 8 MB resident in VMEM);
* the DIAGONAL LANE OFFSET ``d0`` arrives as a scalar operand, so one
  kernel shape serves every subpanel of a panel (the inert lanes
  above the diagonal ride along — ≤ (nb−W)/2 of 16k lanes, noise);
* per column: masked norm + head extraction (two lane reductions),
  LAPACK-convention larfg, one eager [IB, h] rank-1 on the strip;
* at strip boundaries the remaining subpanel rows take one blocked
  compact-WY update C ← C − (C·Vᵀ)·Tᵀ·V with T built in-kernel from
  the strip Gram matrix (chunked MXU contractions, VMEM-bounded).

Output: LAPACK ?geqrf layout — R on/above the diagonal, reflector
tails below, v₀ = 1 implicit — plus ``tau[W]``, drop-in for the
existing Gram-based blocked-T and trailing updates of
linalg/geqrf.py.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

W = 128          # subpanel width (one lane tile)
IB = 8           # strip width for the in-kernel blocked update
H_MAX = 16384    # tallest subpanel: [128, H] f32 (8 MB) + strip-end
                 # chunk temporaries must fit scoped VMEM
H_CHUNK = 4096   # strip-end update processed in lane chunks

# the ceiling the panel-QR pallas_call compiles against
# (vmem_limit_bytes below)
_QR_VMEM_BUDGET = 100 * 1024 * 1024


def _qr_vmem_footprint(h: int) -> int:
    """Resident VMEM estimate (bytes) for one panel-QR kernel call at
    subpanel height ``h``: the aliased [W, h] panel window, the
    strip-end chunk temporaries (~2× the window, cf. panel_plu), the
    d0 row in and out, and the tau tile pair. Asserted against
    _QR_VMEM_BUDGET at the call site so a new window must be added
    HERE to compile."""
    return (W * h + 2 * W * h + 2 * h + 2 * W) * 4


def _qr_kernel(pT_ref, d0_ref, out_ref, tau_ref, *, h):
    """Householder QR of a transposed subpanel.

    pT_ref:  [W, h] f32 — subpanel, columns as sublanes (transposed).
    d0_ref:  [1, 1] i32 — lane of column 0's diagonal element.
    out_ref: [W, h] f32 — factored subpanel (aliased onto pT_ref).
    tau_ref: [1, W] f32 — reflector scalars.
    """
    lane = lax.broadcasted_iota(jnp.int32, (1, h), 1)
    wlane = lax.broadcasted_iota(jnp.int32, (1, W), 1)
    rowW = lax.broadcasted_iota(jnp.int32, (W, 1), 0)
    row8 = lax.broadcasted_iota(jnp.int32, (IB, 1), 0)
    d0 = d0_ref[0, 0]
    out_ref[:] = pT_ref[:]

    def strip(si, tau):
        s0 = pl.multiple_of(si * IB, IB)
        blk = out_ref[pl.ds(s0, IB), :]                  # [IB, h]
        vrows = []
        taus_s = []
        for jj in range(IB):
            dj = d0 + s0 + jj                            # diagonal lane
            colv = blk[jj:jj + 1, :]                     # [1, h]
            below = (lane > dj).astype(colv.dtype)
            head = (lane == dj).astype(colv.dtype)
            # both column statistics in ONE MXU contraction (VPU
            # reduction trees over 16k lanes profiled as the kernel's
            # hot loop): [2,h]·[2,h]ᵀ gives Σ(colv·below)² and
            # Σ colv·head on the diagonal
            lhs = jnp.concatenate([colv * below, colv], axis=0)
            rhs = jnp.concatenate([colv * below, head], axis=0)
            stat = lax.dot_general(
                lhs, rhs, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            xnorm2 = stat[0, 0]
            alpha = stat[1, 1]
            trivial = xnorm2 == 0.0
            sgn = jnp.where(alpha != 0.0, jnp.sign(alpha), 1.0)
            beta = jnp.where(trivial, alpha,
                             -sgn * jnp.sqrt(alpha * alpha + xnorm2))
            denom = jnp.where(trivial, 1.0, beta)
            tau_j = jnp.where(trivial, 0.0, (beta - alpha) / denom)
            vden = jnp.where(trivial, 1.0, alpha - beta)
            v = colv * below / vden + head               # v[dj] = 1
            # eager reflector on the strip's remaining rows (MXU)
            wv = lax.dot_general(                        # [IB, 1]
                blk, v, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            blk = jnp.where(
                row8 == jj,
                jnp.where(lane == dj, beta, jnp.where(
                    lane > dj, v, colv)),                # store beta|v|R
                blk - jnp.where(row8 > jj, tau_j * wv * v, 0.0))
            tau = jnp.where(wlane == s0 + jj, tau_j, tau)
            vrows.append(v)
            taus_s.append(tau_j)
        out_ref[pl.ds(s0, IB), :] = blk
        V = jnp.concatenate(vrows, axis=0)               # [IB, h]
        # strip-end blocked update of the remaining subpanel rows:
        # C ← C − (C·Vᵀ)·Tᵀ·V, T from the strip Gram (forward larft)
        nch = max(1, -(-h // H_CHUNK))
        G = jnp.zeros((IB, IB), jnp.float32)
        cv = jnp.zeros((W, IB), jnp.float32)
        for cc in range(nch):
            lo = cc * H_CHUNK
            wd = min(H_CHUNK, h - lo)
            Vc = V[:, lo:lo + wd]
            G = G + lax.dot_general(
                Vc, Vc, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            cv = cv + lax.dot_general(
                out_ref[:, pl.ds(lo, wd)], Vc,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        # T recurrence (unrolled, IB=8): T[:j, j] = −τⱼ·T[:j,:j]·G[:j,j]
        ii8 = lax.broadcasted_iota(jnp.int32, (IB, IB), 0)
        jj8 = lax.broadcasted_iota(jnp.int32, (IB, IB), 1)
        T = jnp.zeros((IB, IB), jnp.float32)
        for j in range(IB):
            tj = taus_s[j]
            gcol = jnp.where((ii8 < j) & (jj8 == j), G, 0.0)
            tcol = -tj * lax.dot_general(
                T, gcol, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            T = T + jnp.where(jj8 == j, tcol, 0.0) \
                + tj * ((ii8 == j) & (jj8 == j)).astype(jnp.float32)
        # row-vector form of x ← (I − VᵀTᵀV̄)x is C ← C − (C·Vᵀ)·T·V
        cvt = lax.dot_general(                           # [W, IB]
            cv, T, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        cvt = jnp.where(rowW >= s0 + IB, cvt, 0.0)       # rows below
        for cc in range(nch):
            lo = cc * H_CHUNK
            wd = min(H_CHUNK, h - lo)
            out_ref[:, pl.ds(lo, wd)] = (
                out_ref[:, pl.ds(lo, wd)] - lax.dot_general(
                    cvt, V[:, lo:lo + wd],
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
        return tau

    tau = lax.fori_loop(0, W // IB, strip, jnp.zeros((1, W),
                                                     jnp.float32))
    tau_ref[:] = tau


def _qr_call(pT, d0, interpret: bool):
    h = pT.shape[1]
    assert _qr_vmem_footprint(h) <= _QR_VMEM_BUDGET
    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024)
    return pl.pallas_call(
        partial(_qr_kernel, h=h),
        out_shape=(
            jax.ShapeDtypeStruct((W, h), jnp.float32),
            jax.ShapeDtypeStruct((1, W), jnp.float32),
        ),
        input_output_aliases={0: 0},
        interpret=interpret,
        **kw,
    )(pT, d0)


def qr_subpanel(sub: jax.Array, d0, interpret: bool = False):
    """Householder QR of one [H, W] subpanel whose diagonal sits at
    row ``d0`` (column j's pivot row is d0 + j; rows above d0 carry
    already-finished R rows and are untouched).

    Returns (sub_factored in LAPACK geqrf layout, tau[W])."""
    h, w = sub.shape
    assert w == W and h <= H_MAX
    # plain transposes here: at geqrf's panel sizes XLA's layout
    # flips are cheaper than explicit tiled-transpose kernels
    # (measured 49.7 vs 52.6 ms at [16384, 4096]); the LU path, whose
    # matrix is the whole [n, n] array, needs the tiled form
    # (panel_plu.transpose_tiled) to avoid matrix-sized conversions
    pT = jnp.transpose(sub)
    d0a = jnp.full((1, 1), d0, jnp.int32)
    out, tau = _qr_call(pT, d0a, interpret)
    return jnp.transpose(out), tau[0]


def qr_panel_blocked(pan: jax.Array, interpret: bool = False):
    """Blocked Householder QR of a full [h, nb] panel (nb a multiple
    of W): W-column subpanels through the kernel, inter-subpanel
    compact-WY updates as three MXU matmuls at the XLA level. Output
    matches XLA ``geqrf``: (factored panel, taus[nb])."""
    h, nb = pan.shape
    sb = nb // W
    taus = []
    rows = jnp.arange(h, dtype=jnp.int32)[:, None]
    for s in range(sb):
        c0 = s * W
        sub = pan[:, c0:c0 + W]
        subf, tau_s = qr_subpanel(sub, c0, interpret)
        pan = pan.at[:, c0:c0 + W].set(subf)
        taus.append(tau_s)
        if c0 + W < nb:
            # V of this subpanel (unit diagonal at row c0+j)
            diag = c0 + jnp.arange(W, dtype=jnp.int32)[None, :]
            V = jnp.where(rows > diag, subf, 0.0) \
                + (rows == diag).astype(pan.dtype)
            G = V.T @ V
            from ..linalg.geqrf import _blocked_T
            T = _blocked_T(G, tau_s, W, base=8)
            C = pan[:, c0 + W:]
            W1 = V.T @ C
            W2 = T.T @ W1
            pan = pan.at[:, c0 + W:].add(-(V @ W2))
    return pan, jnp.concatenate(taus)


# (the forward-larft T build is shared with linalg/geqrf._blocked_T —
# base-8 recurrence + pairwise combines, no O(W) sequential fori)
