"""Two-stage Hermitian eigensolver, stage 1: he2hb (full → band), with
its back-transform unmtr_he2hb and the band gather.

Reference: src/he2hb.cc (798 LoC — GPU-heavy SBR panel + two-sided
trailing updates, 10 queues), src/unmtr_he2hb.cc, HermitianBandMatrix
::he2hbGather (HermitianBandMatrix.hh:316), wired in src/heev.cc:104-111.

TPU redesign — one jitted ``shard_map`` fori-loop over block columns:

1. panel QR of the sub-diagonal tile column (XLA-native geqrf via the
   same roll-trick as linalg/geqrf.py; the gather collapses the
   reference's per-rank panel + tree),
2. Y = A₂₂·V with the Hermitian matrix read only from its lower
   triangle: a lower-masked einsum (psum over mesh cols, row-indexed)
   plus a mirrored strict-lower einsum (psum over mesh rows,
   col-indexed), both all-gathered — the analog of the reference's
   he2hb_hemm internal kernel,
3. replicated small ops: X = Y·T, W = X − ½·V·(Tᴴ·(Vᴴ·X))  (the SBR
   symmetric update vector, LAPACK xHETRD convention),
4. Hermitian rank-2 block update A₂₂ ← A₂₂ − W·Vᴴ − V·Wᴴ as two local
   einsums (the analog of he2hb_her2k_offdiag_ranks + he2hb_gemm).

After the loop the storage holds the band (diagonal tiles + upper-
triangular sub-diagonal tiles) with the Householder V blocks below —
exactly the reference's in-place layout — plus the T stack.

Stage 2 (band → tridiagonal) is ``hb2st``: the band is gathered to
the host (2·nt tiles; the reference gathers it to rank 0 and chases
there, src/heev.cc:108-131) and chased by the ``robust.ladder`` rung
that takes it, on a TPU in f32 the VMEM-resident Pallas kernel. Stage 3
is ``linalg/stedc.py`` / ``stein.py``; the two back-transforms
(``unmtr_hb2st``, ``unmtr_he2hb``) run on the device, the second
distributed. ``heev_two_stage`` strings them together under
``eig.heev``'s root span.
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
from ..matrix import Matrix, HermitianMatrix, cdiv
from ..types import Op, Side, Uplo
from ..errors import slate_error_if
from ..internal import comm, masks
from ..internal.precision import resolve_tier, trailing_dot_kwargs
from ..internal.tile_kernels import panel_qr_factor, extract_v, larft
from ..utils import trace


def he2hb(A: HermitianMatrix, opts=None):
    """Reduce Hermitian A (lower) to band form: A = Q·B·Qᴴ with B of
    bandwidth nb. Returns (Aband, T): Aband's storage holds the band +
    the V blocks (in place, reference layout); T is [nt-1, nb, nb].
    """
    slate_error_if(A.m != A.n, "he2hb needs square")
    slate_error_if(A.uplo != Uplo.Lower, "he2hb v1: lower storage")
    tier = resolve_tier(opts)
    with trace.block("he2hb", routine="he2hb", n=A.n, nb=A.nb,
                     precision=tier):
        data, T = _he2hb_jit(A, tier)
    out = HermitianMatrix(data=data, m=A.m, n=A.n, nb=A.nb, grid=A.grid,
                          uplo=Uplo.Lower)
    return out, T


@partial(cached_jit, static_argnames=("tier",))
def _he2hb_jit(A, tier=None):
    g = A.grid
    p, q, nb = g.p, g.q, A.nb
    n, nt = A.n, A.nt
    mtl, ntl = A.data.shape[2], A.data.shape[3]
    mt_p = mtl * p
    N = mt_p * nb
    kt = max(nt - 1, 0)
    cplx = jnp.issubdtype(A.dtype, jnp.complexfloating)
    pk = trailing_dot_kwargs(tier, A.dtype)

    def body(a):
        a = a[0, 0]
        r, c = comm.coords()
        gi = masks.local_tile_rows(mtl, p)
        gj = masks.local_tile_cols(ntl, q)
        er = masks.local_elem_rows(mtl, nb, p)       # [mtl, nb] global rows
        ec = masks.local_elem_cols(ntl, nb, q)       # [ntl, nb] global cols
        low_el = er[:, None, :, None] >= ec[None, :, None, :]
        strict_el = er[:, None, :, None] > ec[None, :, None, :]
        valid_el = (er[:, None, :, None] < n) & (ec[None, :, None, :] < n)
        gj_clip = jnp.clip(gj, 0, mt_p - 1)

        def step(k, carry):
            a, Ts = carry
            start = (k + 1) * nb

            # ---- 1. panel QR of sub-diagonal block column k ---------
            pcol = lax.dynamic_index_in_dim(a, k // q, axis=1,
                                            keepdims=False)
            full = comm.allgather_panel_rows(pcol, p, k % q)
            panel2d = full.reshape(N, nb)
            panel2d, taus = panel_qr_factor(panel2d, start, n)
            V = extract_v(panel2d, start, n)         # [N, nb]
            T = larft(V, taus)
            Ts = Ts.at[k].set(T)
            ptiles = panel2d.reshape(mt_p, nb, nb)
            newcol = jnp.take(ptiles, gi, axis=0)
            a = jnp.where(
                c == k % q,
                lax.dynamic_update_index_in_dim(a, newcol, k // q, axis=1),
                a)

            # ---- 2. Y = A₂₂·V (Hermitian from lower triangle) ------
            vt = V.reshape(mt_p, nb, nb)
            v_rows = jnp.take(vt, gi, axis=0)        # [mtl, nb, nb]
            v_cols = jnp.take(vt, gj_clip, axis=0)   # [ntl, nb, nb]
            trail_el = ((er[:, None, :, None] >= start)
                        & (ec[None, :, None, :] >= start))
            a_low = jnp.where(low_el & trail_el & valid_el, a,
                              jnp.zeros_like(a))
            y1 = jnp.einsum("abij,bjv->aiv", a_low, v_cols, **pk)
            y1 = comm.psum_cols(y1)                # [mtl, nb, nb] by row
            a_strict = jnp.where(strict_el & trail_el & valid_el, a,
                                 jnp.zeros_like(a))
            if cplx:
                a_strict_h = jnp.conj(a_strict)
            else:
                a_strict_h = a_strict
            z1 = jnp.einsum("abij,aiv->bjv", a_strict_h, v_rows, **pk)
            z1 = comm.psum_rows(z1)                # [ntl, nb, nb] by col
            y_full = comm.allgather_cyclic(y1, p, AXIS_P)   # [mt_p,...]
            z_full = comm.allgather_cyclic(z1, q, AXIS_Q)   # [nt_p,...]
            z_fit = jnp.zeros_like(y_full)
            L = min(z_full.shape[0], mt_p)
            z_fit = z_fit.at[:L].set(z_full[:L])
            Y = (y_full + z_fit).reshape(N, nb)

            # ---- 3. W = X − ½·V·(Tᴴ·(Vᴴ·X)),  X = Y·T --------------
            X = Y @ T
            VHX = jnp.conj(V.T) @ X                  # [nb, nb]
            W = X - 0.5 * (V @ (jnp.conj(T.T) @ VHX))

            # ---- 4. A₂₂ ← A₂₂ − W·Vᴴ − V·Wᴴ ------------------------
            wt = W.reshape(mt_p, nb, nb)
            w_rows = jnp.take(wt, gi, axis=0)
            w_cols = jnp.take(wt, gj_clip, axis=0)
            upd = (jnp.einsum("aiv,bjv->abij", w_rows, jnp.conj(v_cols),
                              **pk)
                   + jnp.einsum("aiv,bjv->abij", v_rows,
                                jnp.conj(w_cols), **pk))
            keep = ((gi < nt)[:, None, None, None]
                    & (gj < nt)[None, :, None, None])
            a = a - jnp.where(keep, upd, jnp.zeros_like(upd))
            return a, Ts

        Ts0 = jnp.zeros((max(kt, 1), nb, nb), A.dtype)
        if kt > 0:
            a, Ts = lax.fori_loop(0, kt, step, (a, Ts0))
        else:
            Ts = Ts0
        return a[None, None], Ts

    data, T = jax.shard_map(
        body, mesh=g.mesh, in_specs=(P(AXIS_P, AXIS_Q),),
        out_specs=(P(AXIS_P, AXIS_Q), P()), check_vma=False)(A.data)
    return data, T


def he2hb_gather(Aband: HermitianMatrix) -> np.ndarray:
    """Gather the band to host LAPACK lower-banded storage
    ``band[d, j] = A[j+d, j]``, d = 0..nb (reference he2hbGather,
    HermitianBandMatrix.hh:316 — band stage runs on one host there
    too).  Fetches only the 2·nt band tiles, never the dense matrix.
    """
    from .bulge import gather_band_lower
    return gather_band_lower(Aband)


def unmtr_he2hb(trans: Op, Aband: HermitianMatrix, T, C: Matrix,
                opts=None) -> Matrix:
    """Apply Q from he2hb to C (reference src/unmtr_he2hb.cc):
    Q·C (NoTrans, reverse panel order) or Qᴴ·C (forward order)."""
    with trace.block("unmtr_he2hb"):
        return _unmtr_he2hb_jit(Aband, T, C, trans == Op.NoTrans)


@partial(cached_jit, static_argnames=("notrans",))
def _unmtr_he2hb_jit(AV, T, C, notrans):
    g = C.grid
    p, q, nb = g.p, g.q, AV.nb
    n = AV.n
    kt = T.shape[0]
    ntt = AV.nt
    mtl, ntl = C.data.shape[2], C.data.shape[3]
    mt_p = AV.data.shape[2] * p
    N = mt_p * nb

    def body(av, cdat, T):
        av, cdat = av[0, 0], cdat[0, 0]
        gi = masks.local_tile_rows(mtl, p)

        def apply_one(k, cdat):
            start = (k + 1) * nb
            pcol = lax.dynamic_index_in_dim(av, k // q, axis=1,
                                            keepdims=False)
            full = comm.allgather_panel_rows(pcol, p, k % q)
            panel2d = full.reshape(N, nb)
            V = extract_v(panel2d, start, n)
            vt = V.reshape(mt_p, nb, nb)
            vloc = jnp.take(vt, gi, axis=0)
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


def hb2st(band: np.ndarray):
    """Hermitian band → real symmetric tridiagonal via band-limited
    bulge chasing, O(n²·nb) work and O(n·nb) live storage — never
    materializing a dense n×n matrix (reference src/hb2st.cc +
    internal_hebr.cc task types; C++ kernel with numpy fallback, see
    internal/band_bulge.py).

    Returns (d, e, V, tau): the tridiagonal plus the packed
    Householder reflectors; apply them with
    ``bulge.apply_bulge_reflectors`` (Q = H_1ᴴ·…·H_Kᴴ satisfies
    A_band = Q·T·Qᴴ).

    Backend dispatch (the reference pins this stage to rank 0 and
    scales it with an OpenMP task pipeline, src/hb2st.cc:150-260; here
    the same pipeline parallelism runs ON DEVICE as batched waves):

    * ``vmem`` — VMEM-resident Pallas chaser (internal/band_wave_vmem
      .py): the whole ribbon lives in VMEM across the wave grid so a
      wave touches no HBM (the XLA wave's ~0.37 ms/wave was segment
      HBM traffic — BASELINE.md r4). Auto-selected on TPU when the
      shape qualifies (f32, band a power of two in [8, 256], ribbon
      fits VMEM); falls back to ``wave`` otherwise.
    * ``wave`` — device wavefront chaser (internal/band_bulge_wave.py),
      one fused XLA step per anti-diagonal wave of the (sweep, chase)
      task DAG. Auto-selected when an accelerator is the default
      backend and the problem is big enough to amortize dispatch.
    * ``native`` — single-thread C++ kernel (host), the default on CPU.
    * ``numpy`` — pure-numpy twin (reference implementation for tests).

    Override with ``SLATE_HB2ST=vmem|wave|native|numpy`` — the
    override pins the STARTING rung of the ``robust.ladder`` hb2st
    ladder; a rung that cannot take the problem (failed probe, raise,
    non-finite output) still demotes to the next one, with the
    demotion logged in ``robust.ladder.demotion_log()``.
    """
    from ..robust.ladder import hb2st_ladder
    from .bulge import chase
    return chase(hb2st_ladder(), "SLATE_HB2ST", band)


def unmtr_hb2st(V, tau, C, band, trans: Op = Op.NoTrans, grid=None):
    """Apply Q from hb2st to the rows of C (reference
    src/unmtr_hb2st.cc): Q·C for NoTrans, Qᴴ·C otherwise.  A sweep's
    reflectors span disjoint row blocks and apply as one batched
    einsum on device; columns of C may be mesh-sharded (row-wise
    reflectors need no communication)."""
    from .bulge import apply_bulge_reflectors
    notrans = trans == Op.NoTrans
    return apply_bulge_reflectors(V, tau, C, band, forward=not notrans,
                                  conj_tau=notrans, grid=grid)


def two_stage_chase_band(n: int, nb: int, band_nb: int) -> int:
    """Band width the two-stage pipeline will ACTUALLY chase at:
    heev_two_stage re-blocks an nb-tiled matrix to the preferred
    band_nb only when nb > band_nb and n > 2*band_nb; otherwise the
    chase runs at the matrix's own block size. Every decision keyed
    on the chase band (eig.py's lowered dense/two-stage threshold,
    the VMEM-gate tests) must call THIS, not assume band_nb — gating
    on the preferred band when the pipeline keeps nb was the r5
    advisor's eig.py:92 finding."""
    return band_nb if (nb > band_nb and n > 2 * band_nb) else nb


def heev_two_stage(A: HermitianMatrix, opts=None, want_vectors=True,
                   root=None):
    """Full two-stage pipeline (reference src/heev.cc:104-172):
    he2hb (distributed) → band gather (2·nt tiles) → hb2st bulge
    chasing (the ``robust.ladder`` rung that takes the band) →
    sterf/steqr/stedc on the tridiagonal → back-transforms
    unmtr_hb2st (device, column-sharded) and unmtr_he2hb
    (distributed).  ``root`` is ``slate.heev``'s span (``eig.heev``),
    labelled here with what the pipeline chose: ``method`` (the
    tridiagonal solver), ``band``, ``chase_backend``."""
    from .eig import sterf, steqr, stedc
    from ..robust.ladder import hb2st_ladder
    from ..types import Option, MethodEig, get_option
    method = get_option(opts, Option.MethodEig, MethodEig.Auto)
    # Re-block to the two-stage band width: stage 2's bulge chase and
    # the unmtr_hb2st back-transform are O(n²·band), so a gemm-sized
    # tile (nb ≥ 512) as band makes stage 2 dominate; 256 balances
    # stage-1 MXU batches against chase volume (reference keeps a
    # separate inner band for the same reason, src/he2hb.cc). When the
    # VMEM Pallas chaser can take the problem at band 128 (TPU, f32,
    # ribbon fits VMEM), prefer that: the VMEM kernel at 128 beats the
    # XLA wave at 256 by a wide margin (PERF.md section 6, PR 41; the
    # wave's cost grows with band).
    from ..internal.band_wave_vmem import preferred_eig_band
    band_nb = get_option(opts, Option.EigBand,
                         preferred_eig_band(A.n, A.dtype))
    if two_stage_chase_band(A.n, A.nb, band_nb) == band_nb \
            and A.nb != band_nb:
        if A.nb % band_nb == 0:
            # tile-level re-block: no replicated dense round trip
            # (ADVICE r3 — to_dense materialized n² on every chip)
            A = A.retile(band_nb)
        else:
            A = HermitianMatrix.from_dense(A.to_dense(), nb=band_nb,
                                           grid=A.grid, uplo=A.uplo)
    qr = method == MethodEig.QR or (method != MethodEig.DC
                                    and A.n <= 128)
    rdt = np.zeros(1, A.dtype).real.dtype
    with trace.block("heev.stage1", phase="he2hb", n=A.n):
        Aband, T = he2hb(A, opts)
    with trace.block("heev.gather", phase="band_gather", n=A.n):
        band = he2hb_gather(Aband)
    with trace.block("heev.stage2", phase="hb2st", n=A.n):
        d, e, V2, tau2 = hb2st(band)
    if root is not None:
        root.label(method=(MethodEig.QR if qr else MethodEig.DC).name,
                   band=A.nb, chase_backend=hb2st_ladder().last_rung)
    if not want_vectors:
        with trace.block("heev.tridiag", phase="sterf", n=A.n):
            return np.asarray(sterf(d, e)).astype(rdt), None
    with trace.block("heev.tridiag", phase="eig_solve", n=A.n):
        if qr and A.n > 512:
            # device-Z steqr: values by host QR iteration, vectors by
            # batched device inverse iteration (stein.py) — the
            # QR-with-vectors path never holds dense Z on host
            # (VERDICT r3 #9, reference dsteqr2.f semantics)
            lam, ztri = steqr(d, e, grid=A.grid, dtype=rdt)
        elif qr:
            lam, ztri = steqr(d, e)     # host QR (tiny n)
            ztri = np.ascontiguousarray(ztri)
        else:
            # D&C with device-accumulated, row-sharded Z and the
            # merges solved on the device — host memory stays
            # O(n·nmin) (reference stedc + steqr2 semantics)
            lam, ztri = stedc(d, e, grid=A.grid, dtype=rdt)
    with trace.block("heev.back.hb2st", phase="unmtr_hb2st", n=A.n):
        zb = unmtr_hb2st(V2, tau2, jnp.asarray(ztri).astype(A.dtype),
                         A.nb, Op.NoTrans, A.grid)
    with trace.block("heev.back.he2hb", phase="unmtr_he2hb", n=A.n):
        Zb = Matrix.from_dense(zb, nb=A.nb, grid=A.grid)
        Z = unmtr_he2hb(Op.NoTrans, Aband, T, Zb, opts)
    return np.asarray(lam).astype(rdt), Z


def san_cases(grid, opts=None, n=64, nb=16):
    """slatesan sweep entry: (label, thunk) pairs running this
    driver's jitted surface once at a small shape on ``grid`` (see
    tools/slatesan; armed by SLATE_TPU_SAN=1 + an armed store)."""
    import numpy as np

    def run():
        rng = np.random.default_rng(12)
        a = rng.standard_normal((n, n)).astype(np.float32)
        a = (a + a.T) / 2 + n * np.eye(n, dtype=np.float32)
        A = HermitianMatrix.from_dense(a, nb=nb, grid=grid)
        Aband, T = he2hb(A, opts=opts)
        return Aband.data.block_until_ready()
    return [("he2hb", run)]
