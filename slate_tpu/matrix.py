"""Distributed tiled matrices, 2-D block-cyclic over a TPU mesh.

Design (TPU-first re-expression of the reference's object model,
include/slate/BaseMatrix.hh + internal/MatrixStorage.hh):

* SLATE stores a matrix as a distributed ``map<(i,j) → TileNode>`` of
  heap tiles with MOSI coherency (MatrixStorage.hh:284,33-39). On TPU
  the same information is **one dense stacked-tile array**

      ``data[p, q, mtl, ntl, nb, nb]``

  where global tile ``(i, j)`` lives at ``data[i % p, j % q, i // p,
  j // q]`` — exactly SLATE's 2-D block-cyclic ``tileRank`` map
  (BaseMatrix.hh:879-905) — and dims 0,1 are sharded over the mesh axes
  ``('p','q')``. Each chip therefore holds a ``[mtl, ntl, nb, nb]``
  stack of its local tiles, the layout SLATE builds transiently for
  batched cuBLAS calls (internal_gemm.cc:448-688) made permanent.

* MOSI coherency, workspace tile lives, and ``tileGet*`` transitions
  (BaseMatrix.hh:2772-2911) collapse away: XLA programs are functional,
  so "which step's output is current" replaces cache states, and
  per-step collective outputs replace workspace tiles
  (SURVEY §5.8's recommendation).

* The matrix is padded to whole tiles and to whole p/q multiples of
  tiles; padding is kept **zero** by every op (masks in elementwise
  ops), so BLAS ops need no ragged-edge handling — the analog of
  SLATE's 4 uniform batch shape classes (internal_gemm.cc:480-595)
  becoming "1 class + zero padding". Factorizations place an identity
  on the padded diagonal on the fly (see linalg drivers).

Matrices are registered pytrees: ``data`` is the single array leaf, all
shape/layout metadata is static aux data, so drivers jit cleanly and
recompile only when geometry changes.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .grid import Grid, default_grid, AXIS_P, AXIS_Q
from .types import Op, Uplo, Diag
from .errors import slate_error_if
from . import obs
from .utils import trace


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _relayout_span(name: str, data):
    """Span around re-laying ``data`` out (label ``bytes``: its storage),
    counted as ``matrix.relayout_bytes``."""
    nbytes = data.size * data.dtype.itemsize
    obs.count("matrix.relayout_bytes", nbytes)
    return trace.block(name, bytes=nbytes)


# ---------------------------------------------------------------------------
# Layout conversion helpers (pure jnp; work on global or local views)
# ---------------------------------------------------------------------------

def bc_from_tiles(tiles: jax.Array, p: int, q: int) -> jax.Array:
    """[mt_p, nt_p, nb, nb] global tile array → [p,q,mtl,ntl,nb,nb]."""
    mt_p, nt_p, nb, _ = tiles.shape
    mtl, ntl = mt_p // p, nt_p // q
    return (tiles.reshape(mtl, p, ntl, q, nb, nb)
                 .transpose(1, 3, 0, 2, 4, 5))


def bc_to_tiles(data: jax.Array) -> jax.Array:
    """[p,q,mtl,ntl,nb,nb] → global tile array [mt_p, nt_p, nb, nb]."""
    p, q, mtl, ntl, nb, _ = data.shape
    return (data.transpose(2, 0, 3, 1, 4, 5)
                .reshape(mtl * p, ntl * q, nb, nb))


def dense_to_tiles(a: jax.Array, nb: int, mt_p: int, nt_p: int) -> jax.Array:
    """Dense [m, n] → zero-padded tile array [mt_p, nt_p, nb, nb]."""
    m, n = a.shape
    a = jnp.pad(a, ((0, mt_p * nb - m), (0, nt_p * nb - n)))
    return (a.reshape(mt_p, nb, nt_p, nb).transpose(0, 2, 1, 3))


def tiles_to_dense(tiles: jax.Array, m: int, n: int) -> jax.Array:
    mt_p, nt_p, nb, _ = tiles.shape
    full = tiles.transpose(0, 2, 1, 3).reshape(mt_p * nb, nt_p * nb)
    return full[:m, :n]


# ---------------------------------------------------------------------------
# Base class
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BaseTiledMatrix:
    """Common storage + indexing for all matrix shapes.

    Analog of reference ``BaseMatrix`` (BaseMatrix.hh) minus coherency
    and communication (which live in the drivers / internal ops).
    """
    data: jax.Array          # [p, q, mtl, ntl, nb, nb], sharded ('p','q')
    m: int                   # true global rows
    n: int                   # true global cols
    nb: int                  # tile size
    grid: Grid
    op: Op = Op.NoTrans            # shallow transpose flag (Tile.hh:40-113)
    uplo: Uplo = Uplo.General
    diag: Diag = Diag.NonUnit
    kl: int = 0              # band lower bandwidth (BandMatrix)
    ku: int = 0              # band upper bandwidth

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        aux = (type(self), self.m, self.n, self.nb, self.grid, self.op,
               self.uplo, self.diag, self.kl, self.ku)
        return (self.data,), aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        klass, m, n, nb, grid, op, uplo, diag, kl, ku = aux
        return klass(data=leaves[0], m=m, n=n, nb=nb, grid=grid, op=op,
                     uplo=uplo, diag=diag, kl=kl, ku=ku)

    # -- geometry -----------------------------------------------------------
    @property
    def mt(self) -> int:
        """Block rows (reference BaseMatrix::mt), after op."""
        return cdiv(self.m, self.nb)

    @property
    def nt(self) -> int:
        return cdiv(self.n, self.nb)

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def shape(self) -> tuple[int, int]:
        return (self.m, self.n)

    # storage-side geometry (ignores op flag)
    @property
    def mtl(self) -> int:
        return self.data.shape[2]

    @property
    def ntl(self) -> int:
        return self.data.shape[3]

    def _replace(self, **kw) -> "BaseTiledMatrix":
        return dataclasses.replace(self, **kw)

    # -- conversion ---------------------------------------------------------
    @classmethod
    def from_dense(cls, a, nb: int | None = None, grid: Grid | None = None,
                   **kw) -> "BaseTiledMatrix":
        """Build from a global dense array (analog of ``fromLAPACK``,
        reference Matrix.hh:291). The dense array is tiled, padded with
        zeros, laid out block-cyclically and sharded over the grid."""
        grid = grid or default_grid()
        slate_error_if(np.ndim(a) != 2, "from_dense expects a 2-D array")
        m, n = np.shape(a)
        if nb is None:
            nb = _default_nb(m, n)
        mtl = cdiv(cdiv(m, nb), grid.p)
        ntl = cdiv(cdiv(n, nb), grid.q)
        if isinstance(a, np.ndarray):
            # host ingest path: native OpenMP block-cyclic packer
            # (slate_tpu.runtime — the C++ host-layer analog of the
            # reference's layout conversion), one host->device put.
            from . import runtime
            bc = runtime.pack_block_cyclic(a, nb, grid.p, grid.q, mtl, ntl)
            data = jax.device_put(bc, grid.sharding())
            return cls(data=data, m=m, n=n, nb=nb, grid=grid, **kw)
        a = jnp.asarray(a)
        tiles = dense_to_tiles(a, nb, mtl * grid.p, ntl * grid.q)
        data = bc_from_tiles(tiles, grid.p, grid.q)
        data = jax.device_put(data, grid.sharding())
        return cls(data=data, m=m, n=n, nb=nb, grid=grid, **kw)

    @classmethod
    def zeros(cls, m: int, n: int, nb: int, grid: Grid | None = None,
              dtype=jnp.float32, **kw) -> "BaseTiledMatrix":
        grid = grid or default_grid()
        mtl = cdiv(cdiv(m, nb), grid.p)
        ntl = cdiv(cdiv(n, nb), grid.q)
        data = jnp.zeros((grid.p, grid.q, mtl, ntl, nb, nb), dtype)
        data = jax.device_put(data, grid.sharding())
        return cls(data=data, m=m, n=n, nb=nb, grid=grid, **kw)

    def to_dense(self) -> jax.Array:
        """Gather to a global dense [m, n] array (respecting op/uplo is
        the caller's concern for shaped matrices)."""
        # storage dims are pre-op: (m, n) if NoTrans else (n, m)
        sm, sn = (self.m, self.n) if self.op == Op.NoTrans else (self.n, self.m)
        tiles = bc_to_tiles(self.data)
        d = tiles_to_dense(tiles, tiles.shape[0] * self.nb,
                           tiles.shape[1] * self.nb)[:sm, :sn]
        if self.op == Op.Trans:
            d = d.T
        elif self.op == Op.ConjTrans:
            d = d.T.conj()
        return d

    # -- block-cyclic map (delegates to Grid — the single source of
    # truth for SLATE's tileRank/tileDevice placement) ----------------------
    def tile_owner(self, i: int, j: int):
        """Mesh coordinate (r, c) owning global tile (i, j)."""
        return self.grid.tile_owner(i, j)

    def tile_device(self, i: int, j: int):
        """Device owning global tile (i, j) (reference tileDevice)."""
        return self.grid.tile_device(i, j)

    def tile(self, i: int, j: int) -> jax.Array:
        """Global tile (i, j) fetched through the grid's block-cyclic
        map — ``data[i%p, j%q, i//p, j//q]`` (reference tileRank map,
        BaseMatrix.hh:879-905)."""
        r, c = self.grid.tile_owner(i, j)
        si, sj = self.grid.tile_slot(i, j)
        return self.data[r, c, si, sj]

    # -- views --------------------------------------------------------------
    def sub(self, i1: int, i2: int, j1: int, j2: int) -> "BaseTiledMatrix":
        """Tile-index submatrix [i1..i2] × [j1..j2] inclusive (reference
        ``BaseMatrix::sub``). Returns a **copy** re-laid-out on the same
        grid — functional XLA has no aliasing views; drivers that need
        windows into a matrix use index arithmetic instead."""
        slate_error_if(self.op != Op.NoTrans, "sub() before materialize()")
        tiles = bc_to_tiles(self.data)[i1:i2 + 1, j1:j2 + 1]
        m = min(self.m - i1 * self.nb, (i2 - i1 + 1) * self.nb)
        n = min(self.n - j1 * self.nb, (j2 - j1 + 1) * self.nb)
        g = self.grid
        mt_p = cdiv(i2 - i1 + 1, g.p) * g.p
        nt_p = cdiv(j2 - j1 + 1, g.q) * g.q
        tiles = jnp.pad(tiles, ((0, mt_p - tiles.shape[0]),
                                (0, nt_p - tiles.shape[1]), (0, 0), (0, 0)))
        data = jax.device_put(bc_from_tiles(tiles, g.p, g.q), g.sharding())
        return dataclasses.replace(self, data=data, m=m, n=n)

    def materialize(self) -> "BaseTiledMatrix":
        """Resolve a shallow transpose flag into storage (all-to-all)."""
        if self.op == Op.NoTrans:
            return self
        g = self.grid
        with _relayout_span("matrix.materialize", self.data):
            with trace.block("materialize.to_tiles"):
                tiles = bc_to_tiles(self.data)
            with trace.block("materialize.transpose"):
                tiles = tiles.transpose(1, 0, 3, 2)
                if self.op == Op.ConjTrans:
                    tiles = tiles.conj()
                # crop to the true (after-op) tile counts, then re-pad
                # for the grid
                tiles = tiles[: self.mt, : self.nt]
                mt_p = cdiv(tiles.shape[0], g.p) * g.p
                nt_p = cdiv(tiles.shape[1], g.q) * g.q
                tiles = jnp.pad(tiles, ((0, mt_p - tiles.shape[0]),
                                        (0, nt_p - tiles.shape[1]),
                                        (0, 0), (0, 0)))
            with trace.block("materialize.device_put"):
                data = jax.device_put(bc_from_tiles(tiles, g.p, g.q),
                                      g.sharding())
        uplo = self.uplo
        if uplo in (Uplo.Lower, Uplo.Upper):
            uplo = Uplo.Upper if uplo == Uplo.Lower else Uplo.Lower
        return dataclasses.replace(self, data=data, m=self.m, n=self.n,
                                   op=Op.NoTrans, uplo=uplo,
                                   kl=self.ku, ku=self.kl)

    def redistribute(self, grid: "Grid") -> "BaseTiledMatrix":
        """Re-lay the matrix out on another grid (reference
        ``Matrix::redistribute``, Matrix.hh:831-862 — used by heev to
        go 2D→1D for the back-transform). One XLA all-to-all via the
        canonical tile order."""
        with _relayout_span("matrix.redistribute", self.data):
            A = self.materialize()
            tiles = bc_to_tiles(A.data)[: A.mt, : A.nt]
            return dataclasses.replace(
                A, data=_relayout(tiles, grid), grid=grid)

    def retile(self, new_nb: int) -> "BaseTiledMatrix":
        """Change the tile size to a divisor of ``nb`` (the two-stage
        eig/SVD re-block to Option.EigBand). Tile-level: each [nb, nb]
        tile splits into f×f [new_nb, new_nb] subtiles and the stack
        re-lays block-cyclically as device array ops whose output is
        placed back on the grid's sharding (``device_put``) — the
        HOST never holds the dense matrix, unlike a
        ``to_dense``/``from_dense`` round trip (ADVICE r3). Like
        :meth:`redistribute`, the intermediate tile shuffle is a
        compiler-scheduled relayout, not a hand-placed all-to-all.
        Reference analog: redistribute with a finer blocking,
        Matrix.hh:831."""
        A = self.materialize()
        if new_nb == A.nb:
            return A
        slate_error_if(
            A.nb % new_nb != 0,
            f"retile: new nb {new_nb} must divide the current nb {A.nb}")
        f = A.nb // new_nb
        g = A.grid
        tiles = bc_to_tiles(A.data)                # [mt_p, nt_p, nb, nb]
        mtp, ntp = tiles.shape[0], tiles.shape[1]
        sub = (tiles.reshape(mtp, ntp, f, new_nb, f, new_nb)
                    .transpose(0, 2, 1, 4, 3, 5)
                    .reshape(mtp * f, ntp * f, new_nb, new_nb))
        mt2, nt2 = cdiv(A.m, new_nb), cdiv(A.n, new_nb)
        sub = sub[:mt2, :nt2]
        mt_p = cdiv(mt2, g.p) * g.p
        nt_p = cdiv(nt2, g.q) * g.q
        sub = jnp.pad(sub, ((0, mt_p - mt2), (0, nt_p - nt2),
                            (0, 0), (0, 0)))
        data = jax.device_put(bc_from_tiles(sub, g.p, g.q),
                              g.sharding())
        return dataclasses.replace(A, data=data, nb=new_nb)

    @classmethod
    def from_tile_map(cls, m: int, n: int, nb: int, provider,
                      grid: "Grid" | None = None, dtype=None, **kw):
        """Build from a per-tile provider ``provider(i, j) -> [nb, nb]``
        (reference lambda-distribution ctors, BaseMatrix.hh:793-843:
        the tileRank/tileDevice indirection decides which rank STORES a
        tile; under XLA the compute layout must stay regular, so the
        lambda's role collapses to ingest order — tiles land in the
        canonical block-cyclic placement regardless of which host
        produced them)."""
        grid = grid or default_grid()
        mt, nt = cdiv(m, nb), cdiv(n, nb)
        mt_p = cdiv(mt, grid.p) * grid.p
        nt_p = cdiv(nt, grid.q) * grid.q
        first = np.asarray(provider(0, 0))
        dtype = dtype or first.dtype
        tiles = np.zeros((mt_p, nt_p, nb, nb), dtype)
        for i in range(mt):
            for j in range(nt):
                t = np.asarray(first if (i, j) == (0, 0)
                               else provider(i, j), dtype)
                # crop to the true edge size — tile padding must stay
                # zero (the storage invariant every kernel relies on)
                rr, cc = min(nb, m - i * nb), min(nb, n - j * nb)
                tiles[i, j, :rr, :cc] = t[:rr, :cc]
        data = _relayout(jnp.asarray(tiles[:mt, :nt]), grid)
        return cls(data=data, m=m, n=n, nb=nb, grid=grid, **kw)

    def astype(self, dtype) -> "BaseTiledMatrix":
        return dataclasses.replace(self, data=self.data.astype(dtype))

    def __repr__(self):
        return (f"{type(self).__name__}({self.m}x{self.n}, nb={self.nb}, "
                f"{self.grid}, dtype={self.data.dtype}, op={self.op.name})")


def _relayout(tiles: jax.Array, grid) -> jax.Array:
    """[mt, nt, nb, nb] logical tiles → block-cyclic stacked layout on
    ``grid`` (pads tile counts to grid multiples, places shards)."""
    mt_p = cdiv(tiles.shape[0], grid.p) * grid.p
    nt_p = cdiv(tiles.shape[1], grid.q) * grid.q
    tiles = jnp.pad(tiles, ((0, mt_p - tiles.shape[0]),
                            (0, nt_p - tiles.shape[1]),
                            (0, 0), (0, 0)))
    return jax.device_put(bc_from_tiles(tiles, grid.p, grid.q),
                          grid.sharding())


def _default_nb(m: int, n: int) -> int:
    return min(256, max(32, 1 << (max(m, n) // 8).bit_length()))


# ---------------------------------------------------------------------------
# Shape hierarchy (reference include/slate/{Matrix,…}.hh)
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class Matrix(BaseTiledMatrix):
    """General m×n matrix (reference Matrix.hh:26)."""


@jax.tree_util.register_pytree_node_class
class TrapezoidMatrix(BaseTiledMatrix):
    """Upper/lower trapezoid (reference TrapezoidMatrix.hh). Storage is
    the full tile stack; only the ``uplo`` triangle is significant."""
    def __init__(self, *a, **kw):
        kw.setdefault("uplo", Uplo.Lower)
        super().__init__(*a, **kw)


@jax.tree_util.register_pytree_node_class
class TriangularMatrix(BaseTiledMatrix):
    """Square triangular matrix (reference TriangularMatrix.hh)."""
    def __init__(self, *a, **kw):
        kw.setdefault("uplo", Uplo.Lower)
        super().__init__(*a, **kw)


@jax.tree_util.register_pytree_node_class
class SymmetricMatrix(BaseTiledMatrix):
    """Symmetric: only ``uplo`` half is significant (SymmetricMatrix.hh)."""
    def __init__(self, *a, **kw):
        kw.setdefault("uplo", Uplo.Lower)
        super().__init__(*a, **kw)


@jax.tree_util.register_pytree_node_class
class HermitianMatrix(BaseTiledMatrix):
    """Hermitian: only ``uplo`` half is significant (HermitianMatrix.hh)."""
    def __init__(self, *a, **kw):
        kw.setdefault("uplo", Uplo.Lower)
        super().__init__(*a, **kw)


@jax.tree_util.register_pytree_node_class
class BandMatrix(BaseTiledMatrix):
    """General band matrix, bandwidths (kl, ku) (reference BandMatrix.hh).

    v1 stores the band inside the dense tile stack (out-of-band tiles
    are zero and skipped by band-aware drivers via tile masks); a packed
    band storage is a planned optimization.
    """


@jax.tree_util.register_pytree_node_class
class TriangularBandMatrix(BandMatrix):
    def __init__(self, *a, **kw):
        kw.setdefault("uplo", Uplo.Lower)
        super().__init__(*a, **kw)


@jax.tree_util.register_pytree_node_class
class HermitianBandMatrix(BandMatrix):
    def __init__(self, *a, **kw):
        kw.setdefault("uplo", Uplo.Lower)
        super().__init__(*a, **kw)


# ---------------------------------------------------------------------------
# Shallow transpose ops (reference Tile.hh:40-113 / BaseMatrix swap of dims)
# ---------------------------------------------------------------------------

def transpose(A: BaseTiledMatrix) -> BaseTiledMatrix:
    """Logical transpose — O(1) where possible (flips the op flag and
    swaps m/n); transpose of a ConjTrans view is conj(storage), an
    elementwise op with NO dimension swap relative to storage."""
    if A.op == Op.ConjTrans:
        # X = Sᴴ (dims n×m over storage S m×n); Xᵀ = conj(S), dims m×n.
        return dataclasses.replace(A, data=A.data.conj(), m=A.n, n=A.m,
                                   op=Op.NoTrans)
    new_op = Op.Trans if A.op == Op.NoTrans else Op.NoTrans
    return dataclasses.replace(A, m=A.n, n=A.m, op=new_op)


def conj_transpose(A: BaseTiledMatrix) -> BaseTiledMatrix:
    if A.op == Op.Trans:
        # X = Sᵀ; Xᴴ = conj(S): elementwise conj of storage, dims m×n.
        return dataclasses.replace(A, data=A.data.conj(), m=A.n, n=A.m,
                                   op=Op.NoTrans)
    new_op = Op.ConjTrans if A.op == Op.NoTrans else Op.NoTrans
    return dataclasses.replace(A, m=A.n, n=A.m, op=new_op)
