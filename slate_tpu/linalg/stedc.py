"""Divide & conquer symmetric tridiagonal eigensolver.

Reference: src/stedc.cc + the six kernel files
stedc_{sort,deflate,secular,solve,merge,z_vector}.cc (which follow
LAPACK dlaed0-dlaed4 / Gu-Eisenstat), plus the ◆Fortran steqr2
distributed-Z variant (src/dsteqr2.f:19-25).

TPU redesign — with a ``grid`` the host does only the O(k) scalar
work of a merge (sort, deflation walk), while everything O(k²) and up
lives on the device: the secular solve, the Gu-Eisenstat z-vector, the
merge factor G and the product with Z.  The merges run a level of the
tree at a time, from the leaves up: those of one level touch disjoint
diagonal blocks of Z, so a level is three programs (``_zrows_jit``,
``_secular_jit``, ``_merge_jit``) over its m merges, each widened to
the level's widest k (the children of a level differ by at most a row),
compiled once per (m, k), and two blocking reads (``stedc.zrow``: the
two rows of Z that make each z; ``stedc.roots``: the roots, as a pole
index and an offset).  A merge across an exactly zero off-diagonal
rides its level with no pole to solve for.  At n = 8192 with 256 leaves
that is 8 levels and 16 reads for 255 merges.  Without a grid
(``grid=None``: rank-0 semantics, Z a host array) the same steps run
merge by merge in numpy float64:

* Z is accumulated on device, **row-sharded** over the mesh — each
  merge is ``Z[lo:hi, lo:hi] @ G``, the m of a level one batched
  product (the reference redistributes Z 2D→1D to keep that product
  local, heev.cc:163-170; here a block lies where its rows are stored
  while k ≤ n/grid.size and crosses devices above that).
* The merge orthogonal factor G is *assembled on device*, entry by
  entry in its final row and column order from O(k) vectors: secular
  columns ẑ/(dᵢ-λⱼ) by broadcast, deflated unit columns, then the
  deflation Givens rotations.  No k×k gather or scatter, and the host
  never holds a k×k matrix: its memory stays O(n·nmin).
* The merge z-vector needs two rows of Z (Q1ᵀe_last, Q2ᵀe_first) —
  fetched from device, O(k) bytes.
* The leaves are solved on the host (scipy, float64, O(n·nmin) in all)
  and made Z's block diagonal by one program.

The secular equation is solved by vectorized safeguarded bisection in
the shifted variable μ = λ - dⱼ (monotone g ⇒ no failure modes), and
eigenvector data uses the Gu-Eisenstat recomputed ẑ so column
orthogonality holds to machine precision even for clustered
eigenvalues.  On the device the working precision is Z's dtype (f32
on the chip): every difference of two poles is taken from the poles
split into a high and a low part (``_split``), so dᵢ-dⱼ keeps its
relative accuracy however close the float64 poles are, the offset μ
is carried on its own and never added to a pole, and the ẑ product is
a sum of ``log1p`` terms, which neither overflows nor underflows at
k=8192 and loses eps·Σ|xⱼ| where a product of ratios loses eps·√k.
The deflation tolerance is taken in that working precision.

What a call with a grid reports (docs/observability.md): the blocking
reads above as ``obs.sync_read`` spans, and the counters
:data:`COUNTERS`.
"""

from __future__ import annotations

import math
from functools import partial

import numpy as np

from .. import obs
from ..cache.jitcache import cached_jit

_EPS = np.finfo(np.float64).eps

# what a device D&C counts (``/metrics``): merges that solved a secular
# equation, the poles they merged (Σ k) and the poles they deflated
# (Σ deflated): deflated / poles says how much of the O(k²) work the
# matrix let the merges skip, i.e. whether the work hangs on the seed;
# and the levels of the tree, each one batched step (three programs,
# two reads): merges / levels says how far the batching engaged
COUNTERS = ("stedc.merges", "stedc.poles", "stedc.deflated",
            "stedc.levels")


# ---------------------------------------------------------------------------
# Secular equation (reference stedc_secular.cc / dlaed4 slot)
# ---------------------------------------------------------------------------

def _secular(dd, zz, rho, iters=64, chunk=2048):
    """Roots of 1 + rho·Σ zᵢ²/(dᵢ-λ) = 0 for ascending dd, rho > 0.

    Returns (base, off) with λⱼ = dd[baseⱼ] + offⱼ, the shift taken
    from the *closer* interval endpoint (dlaed4 convention) so
    dᵢ-λⱼ = (dᵢ-dd[baseⱼ]) - offⱼ keeps full relative precision on
    both sides — bisection on the monotone shifted g never fails."""
    k = dd.shape[0]
    z2 = zz * zz
    gaps = np.empty(k)
    gaps[:-1] = np.diff(dd)
    gaps[-1] = rho * z2.sum()
    base = np.arange(k)
    off = np.empty(k)
    for j0 in range(0, k, chunk):
        j1 = min(j0 + chunk, k)
        cols = np.arange(j0, j1)
        gp = gaps[cols]
        # decide the closer endpoint with one evaluation at mid-gap
        deltaL = dd[:, None] - dd[None, cols]      # dᵢ - dⱼ
        gm = 1.0 + rho * np.sum(
            z2[:, None] / (deltaL - 0.5 * gp[None, :]), axis=0)
        right = (gm < 0) & (cols < k - 1)          # root in right half
        # last root has no right pole: keep left base, full bracket
        widen = (gm < 0) & (cols == k - 1)
        base[j0:j1] = np.where(right, cols + 1, cols)
        delta = dd[:, None] - dd[base[j0:j1]][None, :]
        lo = np.where(right, -0.5 * gp, np.where(widen, 0.5 * gp, 0.0))
        hi = np.where(right, 0.0, np.where(widen, gp, 0.5 * gp))
        for _ in range(iters):
            mid = 0.5 * (lo + hi)
            g = 1.0 + rho * np.sum(z2[:, None] / (delta - mid[None, :]),
                                   axis=0)
            pos = g > 0
            hi = np.where(pos, mid, hi)
            lo = np.where(pos, lo, mid)
        # Pole-solve refinement: bisection resolves off only to
        # ~gap·2⁻ᵗ absolute, but a tiny-z root sits at
        # off ≈ rho·z_p²/P — far below that floor.  Solving the
        # dominant pole exactly against the smooth part P and
        # clamping to the final bracket recovers full *relative*
        # precision for such roots without risking the others.
        ofj = 0.5 * (lo + hi)
        zp = z2[base[j0:j1]]
        pole = np.arange(k)[:, None] == base[j0:j1][None, :]
        zsafe = np.where(pole, 0.0, z2[:, None])
        with np.errstate(divide="ignore", invalid="ignore"):
            for _ in range(3):
                Ps = 1.0 + rho * np.sum(zsafe / (delta - ofj[None, :]),
                                        axis=0)
                cand = rho * zp / Ps
                ofj = np.clip(np.where(np.isfinite(cand), cand, ofj),
                              lo, hi)
        off[j0:j1] = ofj
    return base, off


def _z_vector(dd, base, off, zz, rho, chunk=2048):
    """Gu-Eisenstat recomputed ẑ (reference stedc_z_vector.cc):
    ẑᵢ² = (1/rho)·Π_j (λⱼ-dᵢ) / Π_{j≠i} (dⱼ-dᵢ), sign of zz, with
    λⱼ-dᵢ = (dd[baseⱼ]-dᵢ) + offⱼ evaluated cancellation-free."""
    k = dd.shape[0]
    db = dd[base]
    zhat2 = np.empty(k)
    for i0 in range(0, k, chunk):
        i1 = min(i0 + chunk, k)
        rows = np.arange(i0, i1)
        num = (db[None, :] - dd[rows, None]) + off[None, :]   # λⱼ-dᵢ
        den = dd[None, :] - dd[rows, None]                    # dⱼ-dᵢ
        loc = np.arange(i1 - i0)
        den_safe = den.copy()
        den_safe[loc, rows] = 1.0                             # j = i
        ratio = num / den_safe
        ratio[loc, rows] = num[loc, rows]                     # bare λᵢ-dᵢ
        zhat2[i0:i1] = np.prod(ratio, axis=1) / rho
    return np.sign(zz) * np.sqrt(np.maximum(zhat2, 0.0))


# ---------------------------------------------------------------------------
# Deflation (reference stedc_deflate.cc / dlaed2 slot)
# ---------------------------------------------------------------------------

class _MergeSpec:
    """Host-side O(k) description of one merge's orthogonal factor."""
    __slots__ = ("order", "rots", "uidx", "fidx", "Ds", "dd", "zz",
                 "base", "off", "zhat", "col_sort", "vals")


def _deflate(D, z, rho, eps=_EPS):
    """Sort + deflation walk.  D, z in child-concat order; ``eps`` is
    the unit roundoff the tolerance is taken in.  Returns a _MergeSpec
    with the poles and weights that survive (``dd``, ``zz``); the
    secular solve fills the rest (:func:`_close`)."""
    spec = _MergeSpec()
    k = D.shape[0]
    order = np.argsort(D, kind="stable")
    Ds = D[order].tolist()          # python floats: the walk is O(k)
    zs = z[order].tolist()          # interpreter steps
    zmax = max(map(abs, zs), default=0.0)
    dmax = max(map(abs, Ds), default=0.0)
    tol = 8.0 * eps * max(dmax, zmax)
    rots = []
    deflated = np.zeros(k, bool)
    surv = -1
    for j in range(k):
        if rho * abs(zs[j]) <= tol:
            deflated[j] = True
            continue
        if surv >= 0:
            r = math.hypot(zs[surv], zs[j])
            c, s = zs[surv] / r, zs[j] / r
            if abs((Ds[j] - Ds[surv]) * c * s) <= tol:
                # Givens on (surv, j) zeroes z_j; the rotated 2×2
                # diagonal is kept and only the ≤ tol off-diagonal is
                # dropped (dlaed2 convention) — the deflated
                # eigenvalue is the *rotated* diagonal entry
                rots.append((surv, j, c, s))
                zs[surv], zs[j] = r, 0.0
                t = c * c * Ds[surv] + s * s * Ds[j]
                Ds[j] = s * s * Ds[surv] + c * c * Ds[j]
                Ds[surv] = t
                deflated[j] = True
                continue
        surv = j
    spec.order, spec.rots = order, rots
    spec.uidx = np.where(~deflated)[0]
    spec.fidx = np.where(deflated)[0]
    spec.Ds = np.asarray(Ds)
    spec.dd = spec.Ds[spec.uidx]
    spec.zz = np.asarray(zs)[spec.uidx]
    return spec


def _close(spec, base, off, zhat=None):
    """The roots λⱼ = dd[baseⱼ] + offⱼ into the spec: the merged
    eigenvalues ascending and the column order that sorts them."""
    spec.base, spec.off, spec.zhat = base, off, zhat
    vals = np.concatenate([spec.dd[base] + off, spec.Ds[spec.fidx]])
    spec.col_sort = np.argsort(vals, kind="stable")
    spec.vals = vals[spec.col_sort]
    return spec


def _merge_spec(D, z, rho):
    """Deflation walk + secular solve, on the host in float64.  D, z
    in child-concat order; returns a _MergeSpec (all O(k) memory)."""
    spec = _deflate(D, z, rho)
    if spec.uidx.size:
        base, off = _secular(spec.dd, spec.zz, rho)
        zhat = _z_vector(spec.dd, base, off, spec.zz, rho)
    else:
        off = zhat = np.zeros(0)
        base = np.zeros(0, int)
    return _close(spec, base, off, zhat)


def _secular_columns(spec):
    """The k1×k1 undeflated eigenvector block, columns normalized:
    G[i, j] = ẑᵢ/(dᵢ-λⱼ) with dᵢ-λⱼ = (dᵢ-dd[baseⱼ])-offⱼ."""
    denom = (spec.dd[:, None] - spec.dd[spec.base][None, :]) \
        - spec.off[None, :]
    cols = spec.zhat[:, None] / denom
    return cols / np.linalg.norm(cols, axis=0, keepdims=True)


def _assemble_g(spec, k):
    """Full k×k orthogonal merge factor in child-concat row order, on
    the host: G = P1·R·[secular | unit]·P2 (see module docstring;
    :func:`_merge_jit` builds the same matrix on the device)."""
    k1 = spec.uidx.size
    G = np.zeros((k, k))
    if k1:
        G[np.ix_(spec.uidx, np.arange(k1))] = _secular_columns(spec)
    if spec.fidx.size:
        G[spec.fidx, k1 + np.arange(spec.fidx.size)] = 1.0
    # rotations: Z·R1·R2·… ⇒ left-multiply G by R_m … R_1 (reverse)
    for (i, j, c, s) in reversed(spec.rots):
        gi, gj = G[i, :].copy(), G[j, :].copy()
        G[i, :], G[j, :] = c * gi - s * gj, s * gi + c * gj
    # column sort then row permutation back to child-concat order
    G = np.take(G, spec.col_sort, axis=1)
    out = np.empty_like(G)
    out[spec.order, :] = G
    return out


# ---------------------------------------------------------------------------
# The same merge on the device (the grid path)
# ---------------------------------------------------------------------------

def _split(x, dt):
    """float64 ``x`` as high + low parts in ``dt`` (low is zero where
    ``dt`` holds x): a difference of two poles taken part by part
    keeps its relative accuracy in ``dt``."""
    hi = x.astype(dt)
    return hi, (x - hi.astype(np.float64)).astype(dt)


@partial(cached_jit, static_argnames=("k",))
def _zrows_jit(Z, mid, lo, k):
    """For each merge of a level, rows mid-1 and mid of Z over the
    columns [lo, lo+k): the last row of Q1 and the first row of Q2 of
    the merge at ``mid``.  [m, 2, k]; a merge narrower than ``k`` reads
    its neighbour's first column past its own."""
    import jax
    from jax import lax
    return jax.vmap(
        lambda mid, lo: lax.dynamic_slice(Z, (mid - 1, lo), (2, k)))(mid, lo)


@partial(cached_jit, static_argnames=("iters",))
def _secular_jit(poles, rho, k1, iters):
    """:func:`_secular_one` for the m merges of a level: ``poles``
    [m, 3, kp], ``rho`` and ``k1`` [m].  Returns (base, off, zhat),
    [m, kp] each."""
    import jax
    return jax.vmap(partial(_secular_one, iters=iters))(poles, rho, k1)


def _secular_one(poles, rho, k1, iters):
    """:func:`_secular` + :func:`_z_vector` for the first ``k1`` of
    the padded ``poles`` = (dh, dl, z): poles dh + dl ascending with
    weights z, in their dtype.  Returns (base, off, zhat), zero past
    k1."""
    import jax.numpy as jnp
    from jax import lax
    dh, dl, z = poles
    kp = dh.shape[0]
    idx = jnp.arange(kp)
    valid = idx < k1
    last = idx == k1 - 1
    nxt = jnp.minimum(idx + 1, kp - 1)
    z2 = jnp.where(valid, z * z, 0)
    one = jnp.ones((), dh.dtype)
    gaps = (dh[nxt] - dh) + (dl[nxt] - dl)
    gaps = jnp.where(last, rho * jnp.sum(z2), gaps)
    gaps = jnp.where(valid, gaps, one)

    def g(bh, bl, mu, w):
        """1 + rho·Σᵢ wᵢ/((dᵢ - bⱼ) - μⱼ) for every column j."""
        delta = (dh[:, None] - bh[None, :]) + (dl[:, None] - bl[None, :])
        terms = jnp.where(valid[:, None], w / (delta - mu[None, :]), 0)
        return one + rho * jnp.sum(terms, axis=0)

    # the closer endpoint, from one evaluation at mid-gap
    w_all = jnp.broadcast_to(z2[:, None], (kp, kp))
    gm = g(dh, dl, 0.5 * gaps, w_all)
    right = (gm < 0) & ~last & valid
    widen = (gm < 0) & last     # no right pole: left base, upper half
    base = jnp.where(right, nxt, idx)
    # slatelint: disable-next-line=SL002 -- base is idx or nxt = min(idx + 1, kp - 1): inside [0, kp)
    bh, bl = dh[base], dl[base]
    zero = jnp.zeros_like(gaps)
    lo = jnp.where(right, -0.5 * gaps, jnp.where(widen, 0.5 * gaps, zero))
    hi = jnp.where(right, zero, jnp.where(widen, gaps, 0.5 * gaps))

    def bisect(_, bracket):
        lo, hi = bracket
        mid = 0.5 * (lo + hi)
        pos = g(bh, bl, mid, w_all) > 0
        return jnp.where(pos, lo, mid), jnp.where(pos, mid, hi)

    lo, hi = lax.fori_loop(0, iters, bisect, (lo, hi))
    # pole-solve refinement (see _secular): the dominant pole solved
    # against the smooth part, clamped to the final bracket
    off = 0.5 * (lo + hi)
    pole = idx[:, None] == base[None, :]
    w_smooth = jnp.where(pole, 0, w_all)
    for _ in range(3):
        # slatelint: disable-next-line=SL002 -- base is inside [0, kp) (above)
        cand = rho * z2[base] / g(bh, bl, off, w_smooth)
        keep = jnp.isfinite(cand)  # slatelint: disable=SL007 -- a root's own safeguard (0/0 at a pole), no factorization's info
        off = jnp.clip(jnp.where(keep, cand, off), lo, hi)
    off = jnp.where(valid, off, 0)

    # Gu-Eisenstat ẑ: log ẑᵢ² = log|λᵢ-dᵢ| - log rho
    #   + Σ_{j≠i} log((λⱼ-dᵢ)/(dⱼ-dᵢ)),  the ratio being 1 + xᵢⱼ with
    # xᵢⱼ = (λⱼ-dⱼ)/(dⱼ-dᵢ): log1p(x) where |x| is small (nearly every
    # term), the quotient itself where it is not (the neighbours)
    num = (bh[None, :] - dh[:, None]) + (bl[None, :] - dl[:, None]) \
        + off[None, :]                                  # λⱼ - dᵢ
    den = (dh[None, :] - dh[:, None]) + (dl[None, :] - dl[:, None])
    own = (bh - dh) + (bl - dl) + off                   # λⱼ - dⱼ
    diag = idx[:, None] == idx[None, :]
    den = jnp.where(diag, one, den)
    x = own[None, :] / den
    terms = jnp.where(jnp.abs(x) < 0.5, _log1p_small(x),
                      _log(jnp.abs(num / den)))
    terms = jnp.where(diag | ~valid[None, :], 0, terms)
    logz2 = jnp.sum(terms, axis=1) + _log(jnp.abs(own)) - _log(rho)
    zhat = jnp.where(valid, jnp.sign(z) * _exp_half(logz2), 0)
    return base, off, zhat


def _log1p_small(x):
    """log(1 + x) for |x| < 1/2 by its series in y = x/(2 + x),
    2y(1 + y²/3 + y⁴/5 + …), |y| < 1/3: adds, multiplies and one
    divide, so it is right to the working precision *relative to x*
    (3.7 units of 2⁻²⁴ on the chip, where ``jnp.log1p`` is up to 5,401
    off, ``jnp.log`` 4,367 and ``jnp.exp`` 84: summed over k = 8192
    terms they were the vectors' loss of orthogonality, 2,669 units of
    ‖ZᵀZ − I‖_F/√n against 16 with these; PERF.md section 6, PR 41)."""
    import jax.numpy as jnp
    y = x / (2 + x)
    y2 = y * y
    terms = 9 if jnp.finfo(x.dtype).bits <= 32 else 18
    acc = jnp.full_like(x, 1.0 / (2 * terms + 1))
    for i in range(terms - 1, -1, -1):
        acc = acc * y2 + 1.0 / (2 * i + 1)
    return 2 * y * acc


def _log(v):
    """log v for v ≥ 0 as e·ln 2 + log(1 + (m - 1)), v = m·2ᵉ with m
    in [√½, √2), the second term by :func:`_log1p_small`."""
    import jax.numpy as jnp
    m, e = jnp.frexp(v)
    low = m < math.sqrt(0.5)
    m = jnp.where(low, 2 * m, m)
    e = jnp.where(low, e - 1, e).astype(v.dtype)
    out = e * _LN2_HI + (_log1p_small(m - 1) + e * _LN2_LO)
    return jnp.where(v > 0, out, -jnp.inf)


def _exp_half(L):
    """exp(L/2) as 2ⁿ·exp(f), |f| ≤ ln2/2: ln 2 in two parts so that
    f keeps its last bits (Cody-Waite), exp(f) by its series."""
    import jax.numpy as jnp
    h = 0.5 * L
    n = jnp.round(h * (1.0 / math.log(2.0)))
    f = (h - n * _LN2_HI) - n * _LN2_LO
    terms = 11 if jnp.finfo(L.dtype).bits <= 32 else 19
    acc = jnp.ones_like(f)
    for i in range(terms, 0, -1):
        acc = 1 + acc * f / i
    return jnp.ldexp(acc, n.astype(jnp.int32))


# ln 2 = _LN2_HI + _LN2_LO; the high part has eleven trailing zero bits
# in f32, so n·_LN2_HI is exact for |n| < 2048
_LN2_HI = 0.693145751953125
_LN2_LO = math.log(2.0) - _LN2_HI


def _secular_block(poles, base, off, zhat, where):
    """One merge's G before its rotations, entry by entry in its final
    order.  ``where`` = (row_u, row_c, col_j): row r is the undeflated
    pole ``row_u[r]`` (-1: a deflated one, or a row past the merge's
    own k, whose unit entry sits in column ``row_c[r]``), column c is
    the root ``col_j[c]`` (-1: a deflated column)."""
    import jax.numpy as jnp
    dh, dl, _ = poles
    row_u, row_c, col_j = where
    k = dh.shape[0]
    u, j = jnp.maximum(row_u, 0), jnp.maximum(col_j, 0)
    bj = base[j]
    denom = (dh[u][:, None] - dh[bj][None, :]) \
        + (dl[u][:, None] - dl[bj][None, :]) - off[j][None, :]
    live = (row_u >= 0)[:, None] & (col_j >= 0)[None, :]
    cols = jnp.where(live, zhat[u][:, None] / jnp.where(live, denom, 1), 0)
    norm = jnp.sqrt(jnp.sum(cols * cols, axis=0, keepdims=True))
    G = cols / jnp.where(norm > 0, norm, 1)
    return G + (row_c[:, None] == jnp.arange(k)[None, :]).astype(G.dtype)


@partial(cached_jit, donate_argnums=0)
def _merge_jit(Z, lo, poles, base, off, zhat, where, turn, rot, nrot):
    """Z[lo:lo+k, lo:lo+k] @ G in place for the m merges of a level
    (``lo`` [m] ascending, the rest stacked on a leading m), each G as
    :func:`_assemble_g` makes it: :func:`_secular_block`, then the
    level's ``nrot`` deflation rotations, one after the other as a
    single merge's are (a row at a time, in place): cosines and sines
    ``rot`` on the rows ``turn`` of the m G's stacked [m·k, k], each
    merge's already in the order they apply."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    G = jax.vmap(_secular_block)(poles, base, off, zhat, where)
    m, k = lo.shape[0], G.shape[-1]

    def rotate(t, G):
        gi = lax.dynamic_index_in_dim(G, turn[0, t], 0, keepdims=False)
        gj = lax.dynamic_index_in_dim(G, turn[1, t], 0, keepdims=False)
        c, s = rot[0, t], rot[1, t]
        G = lax.dynamic_update_index_in_dim(G, c * gi - s * gj,
                                            turn[0, t], 0)
        return lax.dynamic_update_index_in_dim(G, s * gi + c * gj,
                                               turn[1, t], 0)

    G = lax.fori_loop(0, nrot, rotate, G.reshape(m * k, k)).reshape(m, k, k)
    blocks = jax.vmap(lambda at: lax.dynamic_slice(Z, (at, at), (k, k)))(lo)
    blocks = jnp.matmul(blocks, G, precision=lax.Precision.HIGHEST)

    # in ascending order: a merge one short of k carries its
    # neighbour's first row and column through untouched (G is the
    # identity there), and the neighbour's own block then lands on them
    def put(t, Z):
        return lax.dynamic_update_slice(Z, blocks[t], (lo[t], lo[t]))

    return lax.fori_loop(0, m, put, Z)


@partial(cached_jit, static_argnames=("n",))
def _leaves_jit(rows, leaf, pos, n):
    """Z's block diagonal from the leaves' eigenvectors: ``rows[r]`` is
    row r of its leaf's Q (padded to the widest leaf), ``leaf[c]`` /
    ``pos[c]`` the leaf and the column in it that column c is.  The
    one-hot product only moves entries (exact at ``highest``)."""
    import jax.numpy as jnp
    from jax import lax
    hot = (jnp.arange(rows.shape[1])[:, None] == pos[None, :])
    Z = jnp.matmul(rows, hot.astype(rows.dtype),
                   precision=lax.Precision.HIGHEST)
    return jnp.where(leaf[:, None] == leaf[None, :], Z, 0)[:, :n]


# ---------------------------------------------------------------------------
# Recursion driver (reference stedc.cc / dlaed0 slot)
# ---------------------------------------------------------------------------

def _tear(d, e, lo, hi, nmin, leaves, levels, depth=0):
    """The rank-one tears of the whole tree, into ``d`` in place
    (T = blockdiag + |rho|·v·vᵀ, v = [e_l; sgn·e_f] at every split),
    the leaf ranges in order, and the merges (lo, mid, hi) by their
    depth in the tree, ``levels[0]`` the top one, each level in
    ascending order.  The nodes of one depth hold ⌊n/2ᵈ⌋ or ⌈n/2ᵈ⌉ rows
    and the last holds the most, so where it is a leaf all are: every
    merge of a level, widened to the level's widest, stays inside n."""
    n = hi - lo
    if n <= nmin:
        leaves.append((lo, hi))
        return
    mid = lo + n // 2
    arho = abs(e[mid - 1])
    d[mid - 1] -= arho
    d[mid] -= arho
    if depth == len(levels):
        levels.append([])
    levels[depth].append((lo, mid, hi))
    _tear(d, e, lo, mid, nmin, leaves, levels, depth + 1)
    _tear(d, e, mid, hi, nmin, leaves, levels, depth + 1)


def _stedc_rec(e, lo, hi, leaf_vals, merge_fn, nmin):
    """Eigenvalues of [lo, hi) ascending, the leaves' from
    ``leaf_vals[lo]``; ``merge_fn(lo, mid, hi, D, rho)`` merges two
    children with eigenvalues D (child-concat order), updates Z and
    returns the merged eigenvalues."""
    n = hi - lo
    if n <= nmin:
        return leaf_vals[lo]
    mid = lo + n // 2
    v1 = _stedc_rec(e, lo, mid, leaf_vals, merge_fn, nmin)
    v2 = _stedc_rec(e, mid, hi, leaf_vals, merge_fn, nmin)
    return merge_fn(lo, mid, hi, np.concatenate([v1, v2]), e[mid - 1])


def _trivial_sort_spec(D):
    """rho == 0: children are independent; the merge is a column sort."""
    spec = _MergeSpec()
    k = D.shape[0]
    spec.order = np.argsort(D, kind="stable")
    spec.rots = []
    spec.uidx = np.zeros(0, int)
    spec.fidx = np.arange(k)
    spec.Ds = D[spec.order]
    spec.dd = spec.zz = np.zeros(0)
    return _close(spec, np.zeros(0, int), np.zeros(0), np.zeros(0))


def stedc(d, e, want_vectors: bool = True, grid=None, dtype=None,
          nmin: int = 48):
    """Eigendecomposition of the symmetric tridiagonal (d, e) by
    divide & conquer.  Returns (lam ascending, Z | None).

    With ``grid`` (and want_vectors), Z is accumulated **on device**,
    row-sharded over the grid's mesh, every merge is solved there in
    Z's dtype, host memory stays O(n·nmin) and the function returns a
    jax array.  Without a grid, Z is a host numpy array (reference
    semantics of rank-0 stedc).
    """
    from scipy.linalg import eigh_tridiagonal, eigvalsh_tridiagonal
    d = np.asarray(d, np.float64).copy()
    e = np.asarray(e, np.float64).copy()
    n = d.shape[0]
    if n == 0:
        return np.zeros(0), None
    if not want_vectors:
        # values-only D&C degenerates to the O(n²) QR/MRRR path anyway
        return eigvalsh_tridiagonal(d, e), None
    if n <= nmin:
        lam, Z = eigh_tridiagonal(d, e)
        if grid is not None:
            import jax.numpy as jnp
            Z = jnp.asarray(Z if dtype is None else Z.astype(dtype))
        return lam, Z

    leaves, levels = [], []
    _tear(d, e, 0, n, nmin, leaves, levels)
    solved = [eigh_tridiagonal(d[lo:hi], e[lo:hi - 1])
              for lo, hi in leaves]

    if grid is None:
        leaf_vals = {lo: lam for (lo, _), (lam, _) in zip(leaves, solved)}
        Z = np.zeros((n, n))
        for (lo, hi), (_, q) in zip(leaves, solved):
            Z[lo:hi, lo:hi] = q

        def merge_fn(lo, mid, hi, D, rho):
            if rho == 0.0:
                spec = _trivial_sort_spec(D)
            else:
                z = np.concatenate([Z[mid - 1, lo:mid],
                                    np.sign(rho) * Z[mid, mid:hi]])
                spec = _merge_spec(D, z, abs(rho))
            Z[lo:hi, lo:hi] = Z[lo:hi, lo:hi] @ _assemble_g(spec, hi - lo)
            return spec.vals

        return _stedc_rec(e, 0, n, leaf_vals, merge_fn, nmin), Z
    return _stedc_device(e, n, leaves, solved, levels, grid, dtype)


def _stedc_device(e, n, leaves, solved, levels, grid, dtype):
    """The merges of :func:`stedc` with Z on the device, row-sharded, a
    level of the tree at a time from the leaves up: the merges of one
    level touch disjoint diagonal blocks of Z, so one program and one
    read serve them all, each widened to the level's widest k."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding
    from ..grid import AXIS_P, AXIS_Q
    from ..matrix import cdiv
    zdt = np.dtype(dtype) if dtype is not None \
        else jnp.zeros(()).dtype            # float64 only under x64
    eps = float(np.finfo(zdt).eps)
    # a bracket halves each step: past the mantissa nothing moves
    iters = int(np.finfo(zdt).nmant) + 12
    n_pad = cdiv(n, grid.size) * grid.size
    sh = NamedSharding(grid.mesh, P((AXIS_P, AXIS_Q), None))

    width = max(hi - lo for lo, hi in leaves)
    rows = np.zeros((n_pad, width), zdt)
    leaf = np.full(n_pad, -1, np.int32)
    pos = np.zeros(n_pad, np.int32)
    for t, ((lo, hi), (_, q)) in enumerate(zip(leaves, solved)):
        rows[lo:hi, :hi - lo] = q
        leaf[lo:hi] = t
        pos[lo:hi] = np.arange(hi - lo)
    Z = jax.device_put(_leaves_jit(jax.device_put(rows, sh), leaf, pos,
                                   n=n), sh)
    vals = {lo: lam for (lo, _), (lam, _) in zip(leaves, solved)}
    tally = {"levels": len(levels), "merges": 0, "poles": 0,
             "deflated": 0}

    for depth in range(len(levels) - 1, -1, -1):
        level = levels[depth]
        m, k = len(level), max(hi - lo for lo, _, hi in level)
        at = {"level": depth, "k": k, "m": m}
        los = np.array([lo for lo, _, _ in level], np.int32)
        mids = np.array([mid for _, mid, _ in level], np.int32)
        two = obs.sync_read("stedc.zrow", np.asarray,
                            _zrows_jit(Z, mids, los, k=k), **at)
        poles = np.zeros((m, 3, k), zdt)    # dh, dl, z: what survives
        rho = np.ones(m, zdt)
        k1 = np.zeros(m, np.int32)
        specs = []
        for t, (lo, mid, hi) in enumerate(level):
            D = np.concatenate([vals.pop(lo), vals.pop(mid)])
            tear = e[mid - 1]
            if tear == 0.0:             # rides the batch with no pole
                specs.append(_trivial_sort_spec(D))
                continue
            z = np.concatenate([two[t, 0, :mid - lo],
                                np.sign(tear) * two[t, 1, mid - lo:hi - lo]])
            rho[t] = abs(tear)
            spec = _deflate(D, z.astype(np.float64), abs(tear), eps)
            k1[t] = spec.uidx.size
            poles[t, 0, :k1[t]], poles[t, 1, :k1[t]] = _split(spec.dd, zdt)
            poles[t, 2, :k1[t]] = spec.zz
            specs.append(spec)
            tally["merges"] += 1
            tally["poles"] += hi - lo
            tally["deflated"] += hi - lo - k1[t]
        base, off, zhat = _secular_jit(poles, rho, k1, iters=iters)
        roots = obs.sync_read("stedc.roots", jax.device_get, (base, off),
                              **at)
        # G's final order: row order[p] is sorted position p, column c
        # is source column col_sort[c] (a root below k1, else
        # deflated); past a merge's own rows, the identity
        where = np.full((m, 3, k), -1, np.int32)    # row_u, row_c, col_j
        where[:, 1] = np.arange(k)
        turn = np.zeros((2, m * k), np.int32)   # rows of the stacked G's
        rot = np.zeros((2, m * k), zdt)         # cosines, sines
        nrot = 0
        for t, ((lo, _, hi), spec) in enumerate(zip(level, specs)):
            _close(spec, np.asarray(roots[0][t, :k1[t]], int),
                   np.asarray(roots[1][t, :k1[t]], np.float64))
            vals[lo] = spec.vals
            own = hi - lo
            where[t, 0, spec.order[spec.uidx]] = np.arange(k1[t])
            where_col = np.empty(own, np.int32)
            where_col[spec.col_sort] = np.arange(own)
            where[t, 1, spec.order[spec.uidx]] = -1
            where[t, 1, spec.order[spec.fidx]] = where_col[k1[t]:]
            where[t, 2, :own] = np.where(spec.col_sort < k1[t],
                                         spec.col_sort, -1)
            if spec.rots:
                i, j, c, s = zip(*spec.rots[::-1])  # in the order they apply
                upto = nrot + len(spec.rots)
                turn[:, nrot:upto] = t * k + spec.order[[i, j]]
                rot[:, nrot:upto] = c, s
                nrot = upto
        Z = _merge_jit(Z, los, poles, base, off, zhat, where, turn, rot,
                       np.int32(nrot))

    for name, value in tally.items():
        obs.count("stedc." + name, value)
    return vals[0], Z[:n]
