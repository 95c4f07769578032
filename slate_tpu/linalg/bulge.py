"""Stage-2 bulge-chase support: compact band gathers (no dense n×n),
the device-side packed-reflector back-transform, and bidiagonal SVD.

Reference: src/hb2st.cc / src/tb2bd.cc produce the reflector sets;
src/unmtr_hb2st.cc applies them tile-batched; src/bdsqr.cc wraps the
bidiagonal QR iteration.  TPU redesign:

* ``gather_band_lower/upper`` pull ONLY the 2·nt band tiles of the
  distributed stacked-tile array (one jitted gather, O(n·nb) bytes) —
  the analog of he2hbGather (HermitianBandMatrix.hh:316) without the
  round-1 dense materialization.
* ``chase`` is the one dispatch of both chases (``he2hb.hb2st``,
  ``ge2tb.tb2bd``) through their ``robust.ladder`` ladders, with the
  span and the counters a caller reads the rung back from.
* ``apply_bulge_reflectors`` applies a packed (sweep, chase) reflector
  family (internal/band_bulge.py format) to the rows of a device
  array.  Within a sweep the reflectors span disjoint row blocks, so a
  sweep applies as ONE batched einsum; a ``lax.fori_loop`` walks
  sweeps.  This is the whole-matrix analog of the reference's
  per-tile unmtr_hb2st batching, with columns free to be sharded
  across the mesh (row-wise reflectors need no communication).
* ``bdsdc`` computes the SVD of a real bidiagonal matrix ON THE
  DEVICE via the Golub-Kahan-tridiagonal eigenproblem (the LAPACK
  ?bdsvdx form, solved by divide & conquer as ?bdsdc is): eigenpairs
  of the (2n)×(2n) perfect-shuffle TGK matrix give σ and interleaved
  (v, u) vectors; ``linalg/stedc.py`` solves it with Z, the secular
  solves and the merge products on the device, and U_B, V_B are cut
  out of Z there.  The host keeps the O(n) scalar work of a merge.
* ``bdsqr`` is the same form on the host in float64 (scipy's
  ``eigh_tridiagonal``): the values-only solve, the branch that
  repairs a rank-deficient B (σ = 0: the ± spaces collide), and the
  float64 answer the tests hold ``bdsdc`` to.
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P, NamedSharding

from ..cache.jitcache import cached_jit
from ..grid import AXIS_P, AXIS_Q
from ..matrix import cdiv
from ..utils import trace
from .. import obs


# ---------------------------------------------------------------------------
# Compact band gathers
# ---------------------------------------------------------------------------

def _tile_flat_index(i, j, g, mtl, ntl):
    return ((i % g.p) * g.q + (j % g.q)) * mtl * ntl \
        + (i // g.p) * ntl + (j // g.q)


@partial(cached_jit, static_argnames=("idx",))
def _gather_tiles_jit(data, idx):
    flat = data.reshape((-1,) + data.shape[-2:])
    return jnp.take(flat, jnp.array(idx), axis=0)


def _band_tiles(A, super_diag: bool):
    """Fetch diagonal tiles + the first sub/super-diagonal tiles."""
    g = A.grid
    mtl, ntl = A.data.shape[2], A.data.shape[3]
    nt = min(A.mt, A.nt)
    diag = tuple(_tile_flat_index(k, k, g, mtl, ntl) for k in range(nt))
    if super_diag:
        off = tuple(_tile_flat_index(k, k + 1, g, mtl, ntl)
                    for k in range(nt - 1))
    else:
        off = tuple(_tile_flat_index(k + 1, k, g, mtl, ntl)
                    for k in range(nt - 1))
    tiles = obs.sync_read("band.gather", np.asarray,
                          _gather_tiles_jit(A.data, diag + off))
    return tiles[:nt], tiles[nt:], nt


def gather_band_lower(A) -> np.ndarray:
    """Compact lower band ``ab[d, j] = A[j+d, j]`` (d = 0..nb) from a
    he2hb output — gathers only the 2·nt band tiles."""
    nb, n = A.nb, A.n
    Td, Ts, nt = _band_tiles(A, super_diag=False)
    ab = np.zeros((nb + 1, n), Td.dtype)
    j = np.arange(n)
    k, c = j // nb, j % nb
    for d in range(nb + 1):
        sel = j + d < n
        js, ks, cs = j[sel], k[sel], c[sel]
        same = cs + d < nb
        ab[d, js[same]] = Td[ks[same], cs[same] + d, cs[same]]
        cross = ~same
        if cross.any():
            ab[d, js[cross]] = Ts[ks[cross], cs[cross] + d - nb, cs[cross]]
    return ab


def gather_band_upper(A) -> np.ndarray:
    """Compact upper band ``ub[d, j] = A[j, j+d]`` (d = 0..nb) from a
    ge2tb output — gathers only the 2·nt band tiles."""
    nb = A.nb
    n = min(A.m, A.n)
    Td, Ts, nt = _band_tiles(A, super_diag=True)
    ub = np.zeros((nb + 1, n), Td.dtype)
    j = np.arange(n)
    k, c = j // nb, j % nb
    for d in range(nb + 1):
        sel = j + d < n
        js, ks, cs = j[sel], k[sel], c[sel]
        same = cs + d < nb
        ub[d, js[same]] = Td[ks[same], cs[same], cs[same] + d]
        cross = ~same
        if cross.any():
            ub[d, js[cross]] = Ts[ks[cross], cs[cross], cs[cross] + d - nb]
    return ub


# ---------------------------------------------------------------------------
# The chase itself: one dispatch for hb2st and tb2bd
# ---------------------------------------------------------------------------

def chase(ladder, env: str, band):
    """Run the band bulge chase ``ladder`` (``robust.ladder``'s
    ``hb2st_ladder()`` or ``tb2bd_ladder()``) on the compact ``band``
    and report it under the ladder's name: the span ``<name>``
    (``routine``, ``n``, ``b``; at its end ``rung`` and, where the
    ``vmem`` rung answered, ``shear``), the counter
    ``<name>.backend{rung}`` (the rung whose answer was used) and
    ``<name>.demotion{from,to}`` for every rung stepped past on the way
    (a demotion is silent to the caller and the answer is still right,
    so it is counted where a caller of heev / gesvd can read it; the
    log is ``robust.ladder.demotion_log()``). The environment variable
    ``env`` = ``vmem|wave|native|numpy`` pins the STARTING rung only: a
    rung that cannot take the problem (failed probe, raise, non-finite
    output) still demotes to the next one."""
    import os
    from ..robust.ladder import demotion_log
    band = np.asarray(band)
    choice = os.environ.get(env, "")
    start = (choice if choice in ("vmem", "wave", "native", "numpy")
             else None)
    logged = len(demotion_log())
    with trace.block(ladder.name, routine=ladder.name,
                     n=band.shape[1], b=band.shape[0] - 1) as span:
        out = ladder.run(band, start=start)
        span.label(rung=ladder.last_rung)
        if ladder.last_rung == "vmem":
            from ..internal.band_wave_vmem import chase_shear_form
            span.label(shear=chase_shear_form(band.shape[0] - 1))
    obs.count(ladder.name + ".backend", 1, rung=ladder.last_rung)
    for d in demotion_log()[logged:]:
        if d.ladder == ladder.name:
            obs.count(ladder.name + ".demotion", 1, to=d.to_rung,
                      **{"from": d.from_rung})
    return out


# ---------------------------------------------------------------------------
# Device-side packed-reflector application
# ---------------------------------------------------------------------------

def _skew(x):
    """[..., K, w] → [..., K, w + K - 1], row i shifted right by i
    (zeros elsewhere): a pad and two reshapes, no gather."""
    *lead, K, w = x.shape
    x = jnp.pad(x, [(0, 0)] * (len(lead) + 1) + [(0, K)])
    flat = x.reshape(*lead, K * (w + K))[..., :K * (w + K - 1)]
    return flat.reshape(*lead, K, w + K - 1)


@partial(cached_jit, static_argnames=("band", "forward", "conj_tau"))
def _apply_bulge_jit(V, tau, Z, band, forward, conj_tau):
    """The reflector family applied in blocks (the reference's
    unmtr_hb2st applies its reflectors in blocks with T factors for the
    same reason).  K = band consecutive sweeps at one task t are K
    reflectors on the band + K rows from (g·K + t·band): their product
    is I − U·Θ·Uᴴ (compact WY: Θ⁻¹ = diag(1/θ) + the strict triangle
    of UᴴU on the side of the ones applied first), three products on
    the MXU and one aligned window of Z a block.  Reflector (s, t)
    meets (s', t') only for t' = t or t + 1 at s' < s, so a group of K
    sweeps goes task by task (ascending t when the later sweeps apply
    first), group after group; a task wholly past row n is not read.
    One sweep at a time, this was 4.4 ms a sweep at n=8192 (four
    passes over a 256 MiB window off the sublane grid): PERF.md
    section 6, PR 41."""
    S, T = tau.shape
    n, m = Z.shape
    K = band
    G = cdiv(S, K)
    Lw = band + K
    high = lax.Precision.HIGHEST
    theta = jnp.conj(tau) if conj_tau else tau
    # a reflector with tau = 0 is the identity: u = 0 under theta = 1
    dead = theta == 0
    Vz = jnp.where(dead[:, :, None], 0, V)
    Vz = jnp.pad(Vz, ((0, G * K - S), (0, 0), (0, 0)))
    theta = jnp.pad(jnp.where(dead, 1, theta), ((0, G * K - S), (0, 0)),
                    constant_values=1)
    Zp = jnp.zeros((G * K + T * band, m), Z.dtype).at[:n].set(Z)
    eye = jnp.eye(K, dtype=Z.dtype)

    def group(i, Zp):
        g = i if forward else G - 1 - i
        Vg = lax.dynamic_slice(Vz, (g * K, 0, 0), (K, T, band))
        th = lax.dynamic_slice(theta, (g * K, 0), (K, T)).T     # [T, K]
        # row i of U[t] is sweep g·K + i: rows (g·K + t·band) + i + 1 on
        U = _skew(jnp.pad(Vg.transpose(1, 0, 2),
                          ((0, 0), (0, 0), (1, 0))))            # [T, K, Lw]
        gram = jnp.einsum("til,tjl->tij", jnp.conj(U), U, precision=high)
        first = jnp.tril(gram, -1) if forward else jnp.triu(gram, 1)
        theta_inv = first + eye / th[:, :, None]
        Theta = lax.linalg.triangular_solve(
            theta_inv, jnp.broadcast_to(eye, theta_inv.shape),
            left_side=True, lower=forward)
        live = jnp.clip((n - 1 - g * K + band - 1) // band, 0, T)

        def task(j, Zp):
            t = live - 1 - j if forward else j
            r0 = g * K + t * band
            Zw = lax.dynamic_slice(Zp, (r0, 0), (Lw, m))
            Ut = lax.dynamic_index_in_dim(U, t, 0, keepdims=False)
            Tt = lax.dynamic_index_in_dim(Theta, t, 0, keepdims=False)
            W = jnp.matmul(jnp.conj(Ut), Zw, precision=high)
            W = jnp.matmul(Tt, W, precision=high)
            Zw = Zw - jnp.matmul(Ut.T, W, precision=high)
            return lax.dynamic_update_slice(Zp, Zw, (r0, 0))

        return lax.fori_loop(0, live, task, Zp)

    return lax.fori_loop(0, G, group, Zp)[:n]


def apply_bulge_reflectors(V, tau, Z, band, forward=False, conj_tau=True,
                           grid=None):
    """Apply the packed reflector product to the rows of Z [n, m].

    Default (forward=False, conj_tau=True) computes H_1ᴴ·…·H_Kᴴ·Z —
    the band→(tri/bi)diagonal back-transform direction for hb2st Q,
    tb2bd U2 and tb2bd V2 alike.  Columns of Z are sharded over the
    whole mesh when ``grid`` is given (reflectors act on rows: no
    communication).
    """
    if tau.size == 0:
        return jnp.asarray(Z)
    Z = jnp.asarray(Z)
    V = jnp.asarray(V)
    tau = jnp.asarray(tau)
    m = Z.shape[1]
    if grid is not None and grid.size > 1:
        m_pad = cdiv(m, grid.size) * grid.size
        if m_pad != m:
            Z = jnp.pad(Z, ((0, 0), (0, m_pad - m)))
        sh = NamedSharding(grid.mesh, P(None, (AXIS_P, AXIS_Q)))
        Z = jax.device_put(Z, sh)
    with trace.block("unmtr_bulge"):
        out = _apply_bulge_jit(V, tau, Z, band, forward, conj_tau)
    return out[:, :m] if out.shape[1] != m else out


# ---------------------------------------------------------------------------
# Bidiagonal SVD (reference src/bdsqr.cc slot)
# ---------------------------------------------------------------------------

def golub_kahan(d, e):
    """Off-diagonal (d₁, e₁, d₂, e₂, …, dₙ) of the Golub-Kahan form
    T = Π·[0 Bᵀ; B 0]·Πᵀ of the upper bidiagonal B = (d, e): symmetric
    tridiagonal of order 2n with zero diagonal, eigenpairs (±σⱼ, zⱼ),
    zⱼ = (v₁ⱼ, u₁ⱼ, v₂ⱼ, u₂ⱼ, …)/√2."""
    d = np.asarray(d, np.float64)
    off = np.zeros(2 * d.shape[0] - 1)
    off[0::2] = d
    off[1::2] = np.asarray(e, np.float64)
    return off


@partial(cached_jit, static_argnames=("n",))
def _gk_halves_jit(Z, n):
    """U_B, V_B out of the Golub-Kahan eigenvectors Z [2n, 2n]
    (ascending): the n columns of the positive half, reversed to σ
    descending, rows 1::2 and 0::2, each column made unit. Also the
    norms the halves had (1/√2 each where +σ and −σ are apart; a
    σ = 0 pair shares a plane and its halves need not be)."""
    # reversed first, then split by a reshape: the strided form
    # Z[0::2, :n - 1:-1] of the same read 3.7 s a call at 2n = 16384
    # on the chip where this one is under 0.05 (PERF.md section 6, PR 48)
    pos = Z[:, n:][:, ::-1].reshape(n, 2, n)
    V, U = pos[:, 0, :], pos[:, 1, :]
    nu = jnp.sqrt(2.0) * jnp.linalg.norm(U, axis=0)
    nv = jnp.sqrt(2.0) * jnp.linalg.norm(V, axis=0)
    root2 = jnp.sqrt(jnp.asarray(2.0, Z.dtype))
    U = U * (root2 / jnp.where(nu > 0.5, nu, 1).astype(Z.dtype))
    V = V * (root2 / jnp.where(nv > 0.5, nv, 1).astype(Z.dtype))
    return U, V, jnp.stack([nu, nv])


def bdsdc(d, e, grid, dtype=None):
    """SVD B = U_B·diag(σ)·V_Bᵀ of the real upper bidiagonal (d, e)
    with everything O(n²) and up on the device (the reference slot is
    src/bdsqr.cc; the method is LAPACK ?bdsdc's, a divide & conquer,
    on ?bdsvdx's Golub-Kahan form): ``stedc`` of the order-2n
    tridiagonal with Z on ``grid`` in ``dtype``, then U_B and V_B cut
    out of Z by one program. The host holds d, e, σ and the O(k)
    vectors of a merge.

    Returns (σ [n] descending float64 on the host, U_B [n, n], V_B
    [n, n] device arrays), or None when the Golub-Kahan form does not
    answer in ``dtype``: the u and v halves of an eigenvector are
    each 1/√2 long while +σ and −σ are apart, and drift from that by
    about eps·‖B‖/σ as a σ nears 0, where the pair shares a plane and
    the columns of U_B (and of V_B) lose their orthogonality at the
    same rate. Past √eps (half the working digits; B rank deficient
    to working precision is the limit case) the caller takes
    ``bdsqr``, which repairs it on the host in float64. The 2n norms
    are the one read (``gesvd.values``)."""
    from .stedc import stedc
    n = np.shape(d)[0]
    lam, Z = stedc(np.zeros(2 * n), golub_kahan(d, e), True, grid=grid,
                   dtype=dtype)
    U, V, norms = _gk_halves_jit(jnp.asarray(Z), n=n)
    norms = obs.sync_read("gesvd.values", np.asarray, norms)
    drift = np.abs(norms - 1.0).max() if n else 0.0
    if not drift <= np.sqrt(np.finfo(norms.dtype).eps):
        return None
    return np.maximum(lam[n:][::-1], 0.0), U, V


def bdsqr(d, e, want_uv: bool = False):
    """SVD of the real upper bidiagonal B = diag(d) + superdiag(e).

    Values-only: σ descending.  With ``want_uv``: (σ, U, VT) with
    B = U·diag(σ)·VT.  Implemented via the Golub-Kahan tridiagonal
    (perfect-shuffle) eigenproblem — LAPACK ?bdsvdx's method — since
    scipy exposes neither bdsqr nor bdsdc; O(n²) values, O(n²)–O(n³)
    vectors through LAPACK stemr under scipy.
    """
    from scipy.linalg import eigh_tridiagonal, eigvalsh_tridiagonal
    d = np.asarray(d, np.float64)
    e = np.asarray(e, np.float64)
    n = d.shape[0]
    if n == 0:
        z = np.zeros((0, 0))
        return (np.zeros(0), z, z) if want_uv else np.zeros(0)
    if n == 1:
        s = np.abs(d[:1])
        if not want_uv:
            return s
        sign = 1.0 if d[0] >= 0 else -1.0
        return s, np.ones((1, 1)) * sign, np.ones((1, 1))
    # TGK: 2n×2n, zero diagonal, off-diag [d0, e0, d1, e1, …, d_{n-1}];
    # eigenvector z for +σ interleaves z = (v0, u0, v1, u1, …)/√2.
    off = golub_kahan(d, e)
    diag = np.zeros(2 * n)
    if not want_uv:
        w = eigvalsh_tridiagonal(diag, off)
        return np.maximum(w[n:], 0.0)[::-1].copy()
    w, Zt = eigh_tridiagonal(diag, off, select="i",
                             select_range=(n, 2 * n - 1))
    order = np.argsort(w)[::-1]
    s = np.maximum(w[order], 0.0)
    Zt = Zt[:, order]
    V = np.ascontiguousarray(Zt[0::2, :]) * np.sqrt(2.0)
    U = np.ascontiguousarray(Zt[1::2, :]) * np.sqrt(2.0)
    # For σ = 0 the ± TGK eigenspaces collide and a zero-eigenvalue
    # vector's u/v halves need not be unit (B·v = 0 and Bᵀ·u = 0 hold
    # separately).  Renormalize, and complete any degenerate column to
    # an orthonormal basis of the complement of the good columns —
    # which is exactly null(B) for V and null(Bᵀ) for U, so
    # B = U·Σ·Vᵀ and orthogonality both survive rank deficiency.
    for M in (U, V):
        norms = np.linalg.norm(M, axis=0)
        good = norms > 0.5
        M[:, good] /= norms[good]
        if not good.all():
            bad = np.where(~good)[0]
            full = np.concatenate([M[:, good], np.eye(n)], axis=1)
            Qf, _ = np.linalg.qr(full)
            g = int(good.sum())
            M[:, bad] = Qf[:, g:g + bad.size]
    return s, U, V.T.copy()
