"""Test-matrix generation (reference test/matrix_generator.cc:28-71).

The reference generates 26 matrix kinds × singular/eigenvalue
distributions with a counter-based RNG so results are independent of
the process grid (CHANGELOG.md:8-9). Here the same property comes for
free: each tile's entries are drawn from a ``jax.random`` key folded
with the tile's *global* index, generated directly on the owning chip
inside ``shard_map`` — no gather, no grid dependence.

Kinds (reference matrix_generator.cc:28-54 — full set): zeros, ones,
identity, ij, jordan, chebspec, circul, fiedler, gfpp, kms, orthog,
riemann, ris, zielkeNS, minij, hilb, rand/randu, rands, randn, randb,
randr, diag, svd, poev/spd, heev; geev/geevx raise NotImplementedError
exactly as the reference does (matrix_generator.cc:704-705).
Formula kinds are generated distributed — each chip evaluates the
(i, j) formula on its own tiles, no host matrix.
Distributions (for svd/heev/poev/diag): arith, geo, cluster0,
cluster1, rcluster0, rcluster1, logrand, rarith, rgeo
(matrix_generator.cc:56-71).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..grid import Grid, default_grid, AXIS_P, AXIS_Q
from ..matrix import Matrix, HermitianMatrix, cdiv
from ..internal import masks
from ..errors import SlateError
from .. import obs


def random_matrix(m: int, n: int, nb: int | None = None,
                  grid: Grid | None = None, dtype=jnp.float32,
                  seed: int = 0, kind: str = "randn") -> Matrix:
    """Distributed random matrix; entries depend only on (seed, i, j)."""
    grid = grid or default_grid()
    if nb is None:
        nb = min(256, max(8, m // max(grid.p, grid.q)))
    # the host side only: the device fills the tiles after this returns
    with obs.span("slate.random_matrix", m=m, n=n, nb=nb,
                  grid=f"{grid.p}x{grid.q}", dtype=jnp.dtype(dtype).name):
        mtl = cdiv(cdiv(m, nb), grid.p)
        ntl = cdiv(cdiv(n, nb), grid.q)
        data = _random_bc(grid, mtl, ntl, nb, m, n, seed, kind,
                          jnp.dtype(dtype).name)
        return Matrix(data=data, m=m, n=n, nb=nb, grid=grid)


@partial(jax.jit, static_argnames=("grid", "mtl", "ntl", "nb", "m", "n",
                                   "kind", "dtype"))
def _random_bc(grid, mtl, ntl, nb, m, n, seed, kind, dtype):
    dtype = jnp.dtype(dtype)
    nt = cdiv(n, nb)

    def body():
        gi = masks.local_tile_rows(mtl, grid.p)
        gj = masks.local_tile_cols(ntl, grid.q)

        def tile(i, j):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), i * nt + j)
            if kind == "randn":
                t = jax.random.normal(key, (nb, nb), jnp.float32)
            elif kind == "rand" or kind == "randu":
                t = jax.random.uniform(key, (nb, nb), jnp.float32)
            elif kind == "rands":
                t = jax.random.uniform(key, (nb, nb), jnp.float32,
                                       minval=-1.0, maxval=1.0)
            elif kind == "randb":   # Dist::Binary {0, 1}
                t = jax.random.bernoulli(key, 0.5, (nb, nb)).astype(
                    jnp.float32)
            elif kind == "randr":   # Dist::BinarySigned {-1, 1}
                t = jnp.where(jax.random.bernoulli(key, 0.5, (nb, nb)),
                              1.0, -1.0).astype(jnp.float32)
            else:
                raise SlateError(f"unknown random kind {kind}")
            return t.astype(dtype)

        tiles = jax.vmap(lambda i: jax.vmap(lambda j: tile(i, j))(gj))(gi)
        valid = masks.valid_mask(mtl, ntl, nb, grid.p, grid.q, m, n)
        return jnp.where(valid, tiles, jnp.zeros_like(tiles))[None, None]

    return jax.shard_map(body, mesh=grid.mesh, in_specs=(),
                         out_specs=P(AXIS_P, AXIS_Q),
                         check_vma=False)()


# Gallery kinds as elementwise (i, j) formulas, evaluated distributed:
# each chip computes its own tiles from global indices (the TPU analog
# of the reference's per-tile omp tasks, matrix_generator.cc:1193-1640).
# All formulas use 0-based global i, j in f32; mx = max(m, n).

def _formula(kind, i, j, m, n, sigma, fd=jnp.float32):
    mx = float(max(m, n))
    fi, fj = i.astype(fd), j.astype(fd)
    if kind == "zeros":
        return jnp.zeros_like(fi)
    if kind == "ones":
        return jnp.ones_like(fi)
    if kind == "identity":
        return (i == j).astype(jnp.float32)
    if kind == "jordan":    # ones on diagonal + subdiagonal
        return ((i == j) | (i == j + 1)).astype(jnp.float32)
    if kind == "ij":        # i + j·s with j·s < 1 (matrix_generator.cc:1216)
        s = 10.0 ** (-np.ceil(np.log10(max(n, 2))))
        return fi + fj * s
    if kind == "fiedler":
        return jnp.abs(fi - fj)
    if kind == "circul":    # circulant of 1:mx
        d = fj - fi
        return d + jnp.where(d < 0, mx, 0.0) + 1.0
    if kind == "gfpp":      # growth-factor worst case (gfpp variant)
        return jnp.where(j == n - 1, 1.0,
                         jnp.where(i == j, 1.0,
                                   jnp.where(i > j, -0.5, 0.0)))
    if kind == "kms":       # Kac-Murdock-Szegő, rho = 1/2
        return 0.5 ** jnp.abs(fi - fj)
    if kind == "orthog":    # symmetric orthogonal: sqrt(2/(mx+1))·sin(...)
        c = np.sqrt(2.0 / (mx + 1))
        return c * jnp.sin((fi + 1) * (fj + 1) * (np.pi / (mx + 1)))
    if kind == "riemann":
        # matches reference matrix_generator.cc:1509-1535 exactly
        # (1-based i_global, row-divisible-by-column test) — which
        # itself differs from MATLAB gallery('riemann') by one index
        # and a transpose; parity follows the reference.
        bi, bj = i + 3, j + 3
        return jnp.where(bi % bj == 0, (bi - 1).astype(fd), -1.0)
    if kind == "ris":       # Hankel, eigenvalues cluster at ±π/2
        return 0.5 / (mx - fi - fj - 0.5)
    if kind == "zielkeNS":
        # nonsymmetric Zielke, a = 0; the corner perturbation sits at
        # row max(m,n)-1 per reference matrix_generator.cc:1577-1620
        # (for wide matrices it falls outside, as in the reference)
        return jnp.where(i < j, 1.0,
                         jnp.where((i == max(m, n) - 1) & (j == 0),
                                   -1.0, 0.0))
    if kind == "minij":
        return jnp.minimum(fi, fj) + 1.0
    if kind == "hilb":
        return 1.0 / (fi + fj + 1.0)
    if kind == "chebspec":  # Chebyshev spectral differentiation D(1:,1:)
        xi = jnp.cos((np.pi / mx) * (fi + 1))
        xj = jnp.cos((np.pi / mx) * (fj + 1))
        ci = jnp.where(i + 1 == mx, 2.0, 1.0)
        cj = jnp.where(j + 1 == mx, 2.0, 1.0)
        sgn = jnp.where((i + j) % 2 == 0, 1.0, -1.0)
        off = sgn * ci / (cj * (xi - xj + (i == j).astype(fd)))  # guard /0 on diag
        dlast = -(2.0 * mx * mx + 1.0) / 6.0
        dmid = -0.5 * xi / (1.0 - xi * xi)
        return jnp.where(i != j, off,
                         jnp.where(i + 1 == mx, dlast, dmid))
    if kind == "diag":
        sig = sigma.astype(fd)
        return jnp.where(i == j, sig[jnp.minimum(i, sig.shape[0] - 1)],
                         0.0)
    raise SlateError(f"unknown matrix kind '{kind}'")


FORMULA_KINDS = ("zeros", "ones", "identity", "jordan", "ij", "fiedler",
                 "circul", "gfpp", "kms", "orthog", "riemann", "ris",
                 "zielkeNS", "minij", "hilb", "chebspec", "diag")


@partial(jax.jit, static_argnames=("grid", "mtl", "ntl", "nb", "m", "n",
                                   "kind", "dtype"))
def _formula_bc(grid, mtl, ntl, nb, m, n, kind, dtype, sigma):
    dtype = jnp.dtype(dtype)

    def body(sig):
        gi = masks.local_tile_rows(mtl, grid.p)      # [mtl]
        gj = masks.local_tile_cols(ntl, grid.q)      # [ntl]
        r = jnp.arange(nb)
        i4 = (gi[:, None] * nb + r[None, :])[:, None, :, None]
        j4 = (gj[:, None] * nb + r[None, :])[None, :, None, :]
        i4 = jnp.broadcast_to(i4, (mtl, ntl, nb, nb))
        j4 = jnp.broadcast_to(j4, (mtl, ntl, nb, nb))
        fd = jnp.float64 if dtype in (jnp.float64, jnp.complex128) \
            else jnp.float32
        t = _formula(kind, i4, j4, m, n, sig, fd).astype(dtype)
        valid = masks.valid_mask(mtl, ntl, nb, grid.p, grid.q, m, n)
        return jnp.where(valid, t, jnp.zeros_like(t))[None, None]

    return jax.shard_map(body, mesh=grid.mesh, in_specs=(P(),),
                         out_specs=P(AXIS_P, AXIS_Q),
                         check_vma=False)(sigma)


def _dist_values(dist: str, n: int, cond: float) -> np.ndarray:
    """Singular/eigenvalue distributions (matrix_generator.cc:56-71)."""
    i = np.arange(n)
    if dist == "arith":
        s = 1.0 - i / max(n - 1, 1) * (1.0 - 1.0 / cond)
    elif dist == "geo":
        s = cond ** (-i / max(n - 1, 1))
    elif dist == "cluster0":
        s = np.full(n, 1.0 / cond); s[0] = 1.0
    elif dist == "cluster1":
        s = np.ones(n); s[-1] = 1.0 / cond
    elif dist == "logrand":
        rng = np.random.default_rng(1234)
        s = np.exp(rng.uniform(np.log(1.0 / cond), 0.0, n))
    elif dist == "rarith":
        s = (1.0 - i / max(n - 1, 1) * (1.0 - 1.0 / cond))[::-1].copy()
    elif dist == "rgeo":
        s = (cond ** (-i / max(n - 1, 1)))[::-1].copy()
    elif dist == "rcluster0":
        s = np.full(n, 1.0 / cond); s[-1] = 1.0
    elif dist == "rcluster1":
        s = np.ones(n); s[0] = 1.0 / cond
    else:
        raise SlateError(f"unknown distribution {dist}")
    return s


def generate_matrix(kind: str, m: int, n: int | None = None,
                    nb: int | None = None, grid: Grid | None = None,
                    dtype=jnp.float32, seed: int = 0, cond: float = 1e2,
                    dist: str = "logrand", dominant: bool = False):
    """Named test-matrix kinds (reference matrix_generator.cc:28-54).

    Formula and random kinds are generated distributed. Structured
    kinds (svd/heev/poev/spd) build their orthogonal factors on the
    host — adequate for testing; benchmarks use the distributed kinds.
    ``dominant`` adds n to the diagonal of random kinds (the
    reference's ``_dominant`` modifier).
    """
    n = n if n is not None else m
    grid = grid or default_grid()
    if kind in ("geev", "geevx"):
        # not implemented in the reference either
        # (matrix_generator.cc:704-705 "[not yet implemented]")
        raise NotImplementedError(f"matrix kind '{kind}' — not "
                                  "implemented (matches reference)")
    if kind in ("rand", "randu", "randn", "rands", "randb", "randr"):
        A = random_matrix(m, n, nb, grid, dtype, seed, kind)
        if dominant:
            from ..ops.elementwise import _add_scaled_identity
            A = _add_scaled_identity(A, float(n))
        return A

    if kind in FORMULA_KINDS:
        if nb is None:
            nb = min(256, max(8, m // max(grid.p, grid.q)))
        mtl = cdiv(cdiv(m, nb), grid.p)
        ntl = cdiv(cdiv(n, nb), grid.q)
        sd = (jnp.float64 if jnp.dtype(dtype) in (jnp.float64,
                                                  jnp.complex128)
              else jnp.float32)   # keep the spectrum at full precision
        sigma = (jnp.asarray(_dist_values(dist, min(m, n), cond),
                             dtype=sd)
                 if kind == "diag" else jnp.zeros((1,), sd))
        data = _formula_bc(grid, mtl, ntl, nb, m, n, kind,
                           jnp.dtype(dtype).name, sigma)
        cls = HermitianMatrix if kind in ("kms", "orthog", "ris",
                                          "fiedler", "minij",
                                          "hilb") else Matrix
        if cls is HermitianMatrix and m == n:
            return HermitianMatrix(data=data, m=m, n=n, nb=nb, grid=grid)
        return Matrix(data=data, m=m, n=n, nb=nb, grid=grid)

    if kind in ("svd", "heev", "poev", "spd"):
        rng = np.random.default_rng(seed)
        if kind == "svd":
            s = _dist_values(dist, min(m, n), cond)
            u, _ = np.linalg.qr(rng.standard_normal((m, min(m, n))))
            v, _ = np.linalg.qr(rng.standard_normal((n, min(m, n))))
            a = jnp.asarray((u * s) @ v.T).astype(dtype)
        else:  # heev / poev (spd is the reference's alias for poev)
            lam = _dist_values(dist, m, cond)
            if kind == "heev":
                sgn = np.where(rng.uniform(size=m) < 0.5, -1.0, 1.0)
                lam = lam * sgn
            q, _ = np.linalg.qr(rng.standard_normal((m, m)))
            a = jnp.asarray((q * lam) @ q.T).astype(dtype)
        cls = Matrix if kind == "svd" else HermitianMatrix
        return cls.from_dense(a, nb=nb or 256, grid=grid)

    raise SlateError(f"unknown matrix kind '{kind}'")


def random_spd(n: int, nb: int | None = None, grid: Grid | None = None,
               dtype=jnp.float32, seed: int = 0) -> HermitianMatrix:
    """Distributed SPD matrix: A = G·Gᵀ/n + I, built with distributed
    syrk — scales to benchmark sizes (no host matrix)."""
    from ..ops.blas import syrk
    from ..ops.elementwise import _add_scaled_identity
    grid = grid or default_grid()
    with obs.span("slate.random_spd", m=n, n=n, grid=f"{grid.p}x{grid.q}",
                  dtype=jnp.dtype(dtype).name) as root:
        G = random_matrix(n, n, nb, grid, dtype, seed, "randn")
        root.label(nb=G.nb)
        C = HermitianMatrix.zeros(n, n, G.nb, grid, dtype=dtype)
        C = syrk(1.0 / n, G, 0.0, C)
        C = _add_scaled_identity(C, 1.0)
        return HermitianMatrix(data=C.data, m=n, n=n, nb=G.nb, grid=grid)
