"""VMEM-resident Pallas wavefront bulge chaser for tb2bd — the SVD
twin of band_wave_vmem.py (upper triangular band → real bidiagonal).

Reference analog: ``src/tb2bd.cc:272-294`` pipelines the bidiagonal
band stage with an OpenMP taskloop over the same (sweep, chase) DAG as
hb2st (``internal_gebr.cc`` gebr1/2/3 task types). The XLA wavefront
(band_bulge_wave_bd.py) pays the same per-wave HBM segment traffic as
its eig twin (~0.37 ms/wave at n=8192/b=128); this module keeps the
whole ribbon in VMEM across the ``(G, 2)`` Pallas grid with the
chunked-slot body of band_wave_vmem.py (U_SLOTS tasks unrolled,
``fori_loop`` over chunks — the compile-size fix).

Differences from the Hermitian twin, mirroring the XLA pair:

* the ribbon holds the UPPER band only (R[j, off + d] = ub[d, j], no
  conjugate mirror) with the same off = 2b-1 / width-4b layout — the
  in-flight bulge footprint spans c - r ∈ [-(b-1), 2b-1];
* each task emits TWO reflectors — the right/V-side v (annihilating a
  row tail) and the left/U-side u (annihilating a column); only u
  chains across tasks, v is consumed inside its own task;
* the task body is gebr's: [left-apply prev u to the B block → new v
  from B row 0 → right-apply v to B and to the diagonal block → new u
  from the diagonal block's column 0 → left-apply u]. The B block
  (rows [i0-b, i0)) sits where the eig twin's mirror-U block sits
  (slab rows 0..b, col0 = off+b); the diagonal block matches the eig
  twin's D (slab rows b..2b, col0 = off). The seed task reads the
  CONTIGUOUS row tail (slab row b-1, lanes [off+1, off+1+L2)) instead
  of a sheared column.

Numerics match band_bulge.tb2bd's task order and larfg convention up
to f32 summation association; tests/test_band_wave.py asserts twin
agreement and singular-value residuals. The packed output
(d, e, Vu, tauu, Vv, tauv, phase0) drops into
linalg/bulge.apply_bulge_reflectors unchanged.
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import obs
from .band_bulge import max_chase
from .band_wave_vmem import (TAUP, U_SLOTS, _active_chunk_range,
                             _antishear_sum, _ceil8, _col2row, _fw,
                             _geometry, _larfg_f32, _row2col,
                             _shear_lanes, _shear_rowvec, vmem_applies)


def _wave_kernel_bd(base8_ref, delta_ref, clo_ref, chi_ref, rib_ref,
                    out_rib_ref,
                    vv_out_ref, tv_out_ref, vu_out_ref, tu_out_ref,
                    u0_scr, u1_scr, t0_scr, t1_scr,
                    *, n, b, P, PP, NCH, CH, PAD):
    g = pl.program_id(0)
    par = pl.program_id(1)
    W4 = 4 * b
    off = 2 * b - 1
    stride = 2 * b - 1
    U = U_SLOTS
    FRAMES = (b % 128 == 0)
    FW = _fw(b)
    # bd's B block sits where the eig twin's mirror-U sits (urows,
    # global col0 = off+b over lanes [2b, 4b)); D matches (brows,
    # off over [b, 3b)) — both collapse to local col0 = b-1 in frames
    c0B = b - 1 if FRAMES else off + b
    c0D = b - 1 if FRAMES else off
    c0Sr = 0 if FRAMES else off + 1      # seed-row k=0 lane

    @pl.when((g == 0) & (par == 0))
    def _init():
        out_rib_ref[:] = rib_ref[:]
        u0_scr[:] = jnp.zeros_like(u0_scr)
        u1_scr[:] = jnp.zeros_like(u1_scr)
        t0_scr[:] = jnp.zeros_like(t0_scr)
        t1_scr[:] = jnp.zeros_like(t1_scr)

    b8 = pl.multiple_of(base8_ref[g], 8)
    delta = delta_ref[g]

    li1 = lax.broadcasted_iota(jnp.int32, (b, 1), 0)
    lcF = lax.broadcasted_iota(jnp.int32, (b, FW), 1)
    liF = lax.broadcasted_iota(jnp.int32, (b, FW), 0)
    colB = lcF - c0B + liF               # B block (urows frame)
    colD = lcF - c0D + liF               # diagonal block (brows frame)
    shl = _shear_lanes(b, FW, c0B)       # c0B == c0D where not None
    E = (lcF == li1).astype(jnp.float32)    # [b, FW] one-hot
    rowPP = lax.broadcasted_iota(jnp.int32, (PP, 1), 0)
    ohu = lax.broadcasted_iota(jnp.int32, (U, PP), 0)
    ohr = lax.broadcasted_iota(jnp.int32, (U, PP), 1)
    ohtl = lax.broadcasted_iota(jnp.int32, (U, TAUP), 1)
    ohtu = lax.broadcasted_iota(jnp.int32, (U, TAUP), 0)
    laneT = lax.broadcasted_iota(jnp.int32, (1, TAUP), 1)

    uprev_all = jnp.where(par == 0, u1_scr[:], u0_scr[:])   # [PP, FW]
    tprev_all = jnp.where(par == 0, t1_scr[:], t0_scr[:])   # [1, TAUP]

    def chunk(c, carry):
        vv_all, tv_all, vu_all, tu_all = carry
        cU = c * U
        cbase = pl.multiple_of(b8 + par * b + cU * stride, 8)
        win = out_rib_ref[pl.ds(cbase, CH), :]
        up_sh = jnp.where(delta == 0, 0, CH - delta)
        win = pltpu.roll(win, shift=up_sh, axis=0)
        # local row 0 == matrix row (g+1-b) + par*b + cU*stride

        previdx = cU - 1 + par + ohu
        ohp = (ohr == previdx).astype(jnp.float32)
        Up = lax.dot_general(ohp, uprev_all,
                             dimension_numbers=(((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ohpt = (ohtl == (cU - 1 + par + ohtu)).astype(jnp.float32)
        Tp = lax.dot_general(ohpt, tprev_all,
                             dimension_numbers=(((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)

        deltas = []
        for uu in range(U):
            u_idx = cU + uu
            r_u = uu * stride
            s_u = g - u_idx
            t_u = par + 2 * u_idx
            i0 = s_u + 1 + t_u * b
            is_chase = ((s_u >= 0) & (s_u < n - 1) & (t_u >= 1)
                        & (t_u * b <= n - 2 - s_u) & (i0 <= n - 1))
            if uu == 0:
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
                urowsB = slab[:b, 2 * b:4 * b]
                browsD = slab[b:, b:3 * b]
            else:
                urowsB = slab[:b, :]
                browsD = slab[b:, :]

            mrow2 = liF < L2
            mB = (colB >= 0) & (colB < L2) & (liF < L1)
            mD = (colD >= 0) & (colD < L2) & mrow2
            e0D = (colD == 0) & mrow2

            B0 = jnp.where(mB, urowsB, 0.0)
            D0 = jnp.where(mD, browsD, 0.0)

            # ---------------- chase branch -----------------------
            up_row = Up[uu:uu + 1, :]              # [1, FW]
            tp = Tp[uu, 0]
            up_col = _row2col(up_row, E)           # [b, 1]
            # wl[k] = sum_i up[i] B0[i, k] (left-apply fill-in)
            wl_at0 = pltpu.roll(
                _antishear_sum(B0 * up_col, b, FW),
                shift=FW - c0B, axis=1)
            WLs = jnp.where(mB, _shear_rowvec(wl_at0, c0B, b, FW, shl),
                            0.0)
            B1 = B0 - tp * up_col * WLs
            # right/V reflector from B1 row 0 (zero the row tail)
            y_row = jnp.sum(jnp.where((liF == 0) & mB, B1, 0.0),
                            axis=0, keepdims=True)
            y_at0 = pltpu.roll(y_row, shift=FW - c0B, axis=1)
            v_ch, tauv_ch, betav = _larfg_f32(y_at0, L2, FW)
            VBs = jnp.where(mB, _shear_rowvec(v_ch, c0B, b, FW, shl),
                            0.0)
            wr = jnp.sum(B1 * VBs, axis=1, keepdims=True)   # [b, 1]
            B2 = B1 - tauv_ch * wr * VBs
            rowB0 = (liF == 0) & (colB >= 0) & (colB < L2)
            B2 = jnp.where(rowB0,
                           jnp.where(colB == 0, betav, 0.0), B2)
            # diagonal block: deferred right-apply of v, then new u
            VDs = jnp.where(mD, _shear_rowvec(v_ch, c0D, b, FW, shl), 0.0)
            wd = jnp.sum(D0 * VDs, axis=1, keepdims=True)
            D1 = D0 - tauv_ch * wd * VDs
            x_col = jnp.sum(jnp.where(e0D, D1, 0.0), axis=1,
                            keepdims=True)                  # [b, 1]
            u_ch, tauu_ch, betau = _larfg_f32(
                _col2row(x_col, E), L2, FW)
            u_col = _row2col(u_ch, E)
            Qu = jnp.where(mD & (colD >= 1), D1, 0.0) * u_col
            wu_at0 = pltpu.roll(_antishear_sum(Qu, b, FW),
                                shift=FW - c0D, axis=1)
            WUs = jnp.where(mD & (colD >= 1), _shear_rowvec(
                wu_at0, c0D, b, FW, shl), 0.0)
            D2 = D1 - tauu_ch * u_col * WUs
            D2 = jnp.where(e0D,
                           jnp.where(li1 == 0, betau, 0.0), D2)

            dB_ch = jnp.where(mB | rowB0, B2 - urowsB, 0.0)
            dD_ch = jnp.where(mD, D2 - browsD, 0.0)

            # ---------------- seed branch ------------------------
            if uu == 0:
                # seed row tail lives on the urows frame's row b-1 at
                # k = colB (c - r = 1 + k)
                eS = (liF == b - 1) & (colB >= 0) & (colB < L2)
                x_row = jnp.sum(jnp.where(eS, urowsB, 0.0), axis=0,
                                keepdims=True)
                if c0Sr == 0:
                    x_at0 = x_row
                else:
                    x_at0 = pltpu.roll(x_row, shift=FW - c0Sr, axis=1)
                v_sd, tauv_sd, betav_s = _larfg_f32(x_at0, L2, FW)
                dB_sd = jnp.where(
                    eS, jnp.where(colB == 0, betav_s, 0.0) - urowsB,
                    0.0)
                VDsd = jnp.where(mD, _shear_rowvec(v_sd, c0D, b, FW, shl),
                                 0.0)
                ws = jnp.sum(D0 * VDsd, axis=1, keepdims=True)
                Bs1 = D0 - tauv_sd * ws * VDsd
                xs_col = jnp.sum(jnp.where(e0D, Bs1, 0.0), axis=1,
                                 keepdims=True)
                u_sd, tauu_sd, betau_s = _larfg_f32(
                    _col2row(xs_col, E), L2, FW)
                usd_col = _row2col(u_sd, E)
                Qus = jnp.where(mD & (colD >= 1), Bs1, 0.0) * usd_col
                wus_at0 = pltpu.roll(_antishear_sum(Qus, b, FW),
                                     shift=FW - c0D, axis=1)
                WUSs = jnp.where(mD & (colD >= 1), _shear_rowvec(
                    wus_at0, c0D, b, FW, shl), 0.0)
                Bs2 = Bs1 - tauu_sd * usd_col * WUSs
                Bs2 = jnp.where(e0D,
                                jnp.where(li1 == 0, betau_s, 0.0), Bs2)
                dD_sd = jnp.where(mD, Bs2 - browsD, 0.0)

                dB = jnp.where(is_seed, dB_sd, dB_ch)
                dD = jnp.where(is_seed, dD_sd, dD_ch)
                vv_task = jnp.where(is_seed, v_sd, v_ch)
                tv_task = jnp.where(is_seed, tauv_sd, tauv_ch)
                vu_task = jnp.where(is_seed, u_sd, u_ch)
                tu_task = jnp.where(is_seed, tauu_sd, tauu_ch)
            else:
                dB, dD = dB_ch, dD_ch
                vv_task, tv_task = v_ch, tauv_ch
                vu_task, tu_task = u_ch, tauu_ch

            if FRAMES:
                zb = jnp.zeros((b, b), jnp.float32)
                d_up = jnp.concatenate([zb, zb, dB], axis=1)
                d_dn = jnp.concatenate([zb, dD, zb], axis=1)
            else:
                d_up, d_dn = dB, dD
            d_slab = jnp.concatenate(
                [jnp.where(do_any, d_up, 0.0),
                 jnp.where(do_any, d_dn, 0.0)], axis=0)
            deltas.append(d_slab)
            vv_task = jnp.where(do_any, vv_task, 0.0)
            tv_task = jnp.where(do_any, tv_task, 0.0)
            vu_task = jnp.where(do_any, vu_task, 0.0)
            tu_task = jnp.where(do_any, tu_task, 0.0)
            vv_all = jnp.where(rowPP == u_idx, vv_task, vv_all)
            tv_all = jnp.where(laneT == u_idx, tv_task, tv_all)
            vu_all = jnp.where(rowPP == u_idx, vu_task, vu_all)
            tu_all = jnp.where(laneT == u_idx, tu_task, tu_all)

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
        win = win + jnp.pad(comp, ((0, CH - rows_used), (0, 0)))
        win = pltpu.roll(win, shift=delta, axis=0)
        out_rib_ref[pl.ds(cbase, CH), :] = win
        return vv_all, tv_all, vu_all, tu_all

    z_v = jnp.zeros((PP, _fw(b)), jnp.float32)
    z_t = jnp.zeros((1, TAUP), jnp.float32)
    i2 = g * 2 + par
    vv_all, tv_all, vu_all, tu_all = lax.fori_loop(
        clo_ref[i2], chi_ref[i2] + 1, chunk, (z_v, z_t, z_v, z_t))

    @pl.when(par == 0)
    def _store0():
        u0_scr[:] = vu_all
        t0_scr[:] = tu_all

    @pl.when(par == 1)
    def _store1():
        u1_scr[:] = vu_all
        t1_scr[:] = tu_all

    vv_out_ref[0, 0] = vv_all[:, :b]
    tv_out_ref[0, 0] = jnp.broadcast_to(tv_all, (8, TAUP))
    vu_out_ref[0, 0] = vu_all[:, :b]
    tu_out_ref[0, 0] = jnp.broadcast_to(tu_all, (8, TAUP))


# The bd chaser keeps the eig twin's resident set (ribbon + rolled
# chunk window + the two reflector-chain scratch pairs) PLUS four
# per-step output windows of its own: two PP×b V packs and two
# 8×TAUP tau packs, each double-buffered across the parity phases.
# Reusing the eig twin's gate undercounted exactly those windows
# right at the 96 MB boundary (r5 advisor, band_wave_vmem_bd.py:339)
# — so the bd path carries its own budget and gate.
_VMEM_RIBBON_BUDGET_BD = 96 * 1024 * 1024


def vmem_applies_bd(n: int, band: int, dtype) -> bool:
    """True when the VMEM-resident bd chaser supports (n, band,
    dtype) — the gate for tb2bd_wave_vmem and the ge2tb dispatch."""
    if not vmem_applies(n, band, dtype):
        return False
    _G, _P, PP, _NCH, CH, _PAD, ROWS = _geometry(n, band)
    W4 = 4 * band
    resident = (ROWS * W4 + 2 * CH * W4 + 2 * (PP * W4 + TAUP)
                + 2 * (2 * PP * band + 2 * 8 * TAUP)) * 4
    return resident <= _VMEM_RIBBON_BUDGET_BD


@partial(jax.jit, static_argnames=("band", "n", "interpret"))
def _tb2bd_vmem_jit(ub, band, n, interpret=False):
    b = band
    W4 = 4 * b
    off = 2 * b - 1
    S = n - 1
    T = max_chase(n, b)
    G, P, PP, NCH, CH, PAD, ROWS = _geometry(n, b)
    # trace-time witness of the tau-tile capacity the packed
    # read-back below relies on: uu = tt//2 <= (T-1)//2 < P <= TAUP
    assert P <= TAUP, (
        f"tb2bd_vmem: {P} chase slots exceed the {TAUP}-lane tau "
        "tile; vmem_applies_bd must reject this shape")

    R = jnp.zeros((ROWS, W4), jnp.float32)
    # upper band: R[j, off + d] = ub[d, j] = A[j, j+d]
    for d in range(b + 1):
        rr = jnp.arange(n - d)
        R = R.at[rr + PAD, off + d].set(ub[d, : n - d])

    gi = jnp.arange(G, dtype=jnp.int32)
    base = gi + 8
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
    Rf, Vv_all, tv_all, Vu_all, tu_all = pl.pallas_call(
        partial(_wave_kernel_bd, n=n, b=b, P=P, PP=PP, NCH=NCH, CH=CH,
                PAD=PAD),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((ROWS, W4), jnp.float32),
            jax.ShapeDtypeStruct((G, 2, PP, b), jnp.float32),
            jax.ShapeDtypeStruct((G, 2, 8, TAUP), jnp.float32),
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
    e_out = Rf[re + PAD, off + 1]

    ss, tt = jnp.meshgrid(jnp.arange(S), jnp.arange(T), indexing="ij")
    gg = jnp.clip(ss + tt // 2, 0, G - 1)
    uu = tt // 2
    Vv = Vv_all[gg, tt % 2, uu]
    tauv = tv_all[gg, tt % 2, 0, uu]
    Vu = Vu_all[gg, tt % 2, uu]
    tauu = tu_all[gg, tt % 2, 0, uu]
    return d_out, e_out, Vu, tauu, Vv, tauv


def tb2bd_wave_vmem(ub, interpret=None):
    """VMEM-resident wavefront tb2bd: contract of band_bulge.tb2bd
    (upper band storage ub[d, j] = A[j, j+d], d = 0..band), f32 real
    only; returns (d, e, Vu, tauu, Vv, tauv, phase0) — d/e as numpy
    (one blocking read, ``tb2bd.bidiagonal``: the bidiagonal solve's
    merges walk them on the host), the reflector packs as DEVICE
    arrays in the shared packed format of
    linalg/bulge.apply_bulge_reflectors (the wave path returns numpy
    packs; consumers accept both). A shape the kernel does not take
    (``vmem_applies_bd``) goes to the XLA wavefront: ``linalg/ge2tb
    .tb2bd`` never sends one (its ladder's ``vmem`` probe is the same
    gate, and a demotion is counted there), so that branch serves
    direct callers alone.
    ``interpret=None`` compiles on TPU and interprets elsewhere."""
    ub = np.asarray(ub)
    band = ub.shape[0] - 1
    n = ub.shape[1]
    if not vmem_applies_bd(n, band, ub.dtype):
        from .band_bulge_wave_bd import tb2bd_wave
        return tb2bd_wave(ub)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    phase0 = ub.dtype.type(1)        # real f32: no column-0 phase
    d, e, Vu, tauu, Vv, tauv = _tb2bd_vmem_jit(jnp.asarray(ub), band,
                                               n, interpret=interpret)
    # the reflector packs stay device arrays (see
    # band_wave_vmem.hb2st_wave_vmem)
    d, e = obs.sync_read("tb2bd.bidiagonal", jax.device_get, (d, e))
    return d, e, Vu, tauu, Vv, tauv, phase0
