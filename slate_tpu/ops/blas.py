"""Distributed Level-3 BLAS.

Drivers mirror the reference's routine set (src/gemm.cc, hemm.cc,
herk.cc, her2k.cc, symm.cc, syrk.cc, syr2k.cc, trmm.cc, trsm.cc,
gbmm.cc, hbmm.cc, tbsm.cc) as functional JAX programs:

* ``gemm`` is SUMMA over the 2-D block-cyclic tile grid: for each
  block-step k, the owners of A(:,k) broadcast along mesh rows and the
  owners of B(k,:) broadcast along mesh columns (XLA ``psum``-bcast
  over ICI — replacing the reference's MPI hypercube listBcastMT,
  src/gemmC.cc:84-116), then every chip does one batched tile-GEMM
  (einsum over its local stack — replacing batched cuBLAS,
  internal_gemm.cc:614-687). The k-loop is a ``lax.fori_loop``; XLA
  pipelines collectives against the einsum, which is SLATE's lookahead
  (src/gemmC.cc:20-24) without a host scheduler.

* Ops with transposed/shaped operands are normalized first
  (materialize transposes, mirror Hermitian halves, zero triangles) —
  the analog of SLATE's gemmA/gemmC/hemmA… Method variants collapses
  to data normalization + one SUMMA core. XLA does not pick the
  stationary operand, though: a collective written into a
  ``shard_map`` body moves what it is given. ``trsm(Side.Left)``
  chooses as the reference's trsmA / trsmB do, from the shape of B
  alone (``_moves_x``): a B of one tile column leaves A where it is
  stored and moves block-rows of X. Where A does not travel it is read
  where it is stored, a ``[nb, nb]`` tile at a time: on one device
  column (``_reads_tiles``) a step of the solve visits the tiles of
  column k past the diagonal tile and no other, so the program holds
  no re-laid copy of A and multiplies no masked half.

* A B narrower than its storage (a few right-hand sides in an
  nb-wide tile column, the rest stored zeros) is multiplied by
  ``gemm``, and solved against by ``trsm(Side.Left)``, at the whole
  lanes it holds (``_carried_cols``), not at the stored width.

All routines return the updated output matrix (functional style) —
SLATE mutates C in place; here ``C = gemm(alpha, A, B, beta, C)``.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..cache.jitcache import cached_jit
from ..grid import AXIS_P, AXIS_Q
from ..matrix import (Matrix, BaseTiledMatrix, BandMatrix, cdiv,
                      bc_to_tiles, bc_from_tiles)
from ..types import Op, Uplo, Side, Diag
from .. import obs
from ..errors import slate_error_if
from ..internal import comm, masks
from ..internal.masks import tile_diag_pad_identity
from ..internal.precision import resolve_tier, trailing_dot_kwargs
from ..runtime import dag
from ..utils import trace


def _acc_dtype(dt):
    return jnp.float32 if dt in (jnp.bfloat16, jnp.float16) else dt


def _check_compat(*mats):
    g = mats[0].grid
    nb = mats[0].nb
    for M in mats[1:]:
        slate_error_if(M.grid is not g and M.grid != g,
                       "matrices must share a grid")
        slate_error_if(M.nb != nb, "matrices must share a tile size")


def _shard(fn, mesh, n_in, n_scalar=0):
    """shard_map wrapper: n_in tile stacks (sharded) + scalars (replicated)."""
    in_specs = tuple([P(AXIS_P, AXIS_Q)] * n_in + [P()] * n_scalar)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=P(AXIS_P, AXIS_Q), check_vma=False)


def _local(x):
    """[1,1,mtl,ntl,nb,nb] shard → [mtl,ntl,nb,nb]."""
    return x[0, 0]


def _fit_tiles(t: jax.Array, mt_p: int, nt_p: int) -> jax.Array:
    """Crop/zero-pad a global tile array to [mt_p, nt_p, nb, nb]."""
    t = t[:mt_p, :nt_p]
    return jnp.pad(t, ((0, mt_p - t.shape[0]), (0, nt_p - t.shape[1]),
                       (0, 0), (0, 0)))


# ---------------------------------------------------------------------------
# gemm — SUMMA
# ---------------------------------------------------------------------------

def gemm(alpha, A: Matrix, B: Matrix, beta, C: Matrix,
         opts=None) -> Matrix:
    """C = alpha·op(A)·op(B) + beta·C (reference src/gemm.cc:66-89).
    Method dispatch: bcast-SUMMA (default) or the ring-systolic
    Cannon variant (``Option.MethodGemm: MethodGemm.Ring`` —
    nearest-neighbor ICI hops instead of bcasts, see _gemm_ring_jit).

    The default method multiplies B and accumulates C at the width
    they hold, not the width they are stored at: ``w`` whole lanes of
    real columns a device (``_carried_cols``; the span's ``nrhs`` and
    ``w`` labels and the counter ``gemm.narrow`` say when that is under
    the stored ``ntl·nb``). A one-column operand of the refining
    solvers is a 1,024-wide tile column whose other columns are stored
    zeros: at six passes their product with A is 8× the work of the
    128 lanes that hold the column. The tier and the accumulator are
    the same at either width, and the result's padding is exact zeros.
    """
    from ..types import Option, MethodGemm, get_option
    A = A.materialize()
    B = B.materialize()
    slate_error_if(C.op != Op.NoTrans, "C must not be transposed")
    slate_error_if(A.m != C.m or B.n != C.n or A.n != B.m,
                   f"gemm dims: {A.shape} x {B.shape} -> {C.shape}")
    _check_compat(A, B, C)
    method = get_option(opts, Option.MethodGemm, MethodGemm.Auto)
    tier = resolve_tier(opts)
    # the double-buffered ring schedule is bitwise identical to the
    # single-buffered one, so unlike the factorization lookahead it
    # stays on unless the caller pins PipelineDepth: 0
    double_buffer = bool(get_option(opts, Option.PipelineDepth, 1))
    on_grid = C.grid.size > 1
    ring = method == MethodGemm.Ring and on_grid
    gemm_a = method == MethodGemm.GemmA and on_grid
    with trace.block("gemm", precision=tier) as span:
        # the columns of B a device multiplies: the two explicit
        # methods take B as it is stored
        stored = B.data.shape[3] * B.nb
        w = stored if ring or gemm_a else _carried_cols(
            B.n, B.nb, B.grid.q, B.data.shape[3])
        span.label(nrhs=B.n, w=w)
        if w < stored:
            obs.count("gemm.narrow", 1)

        def _run():
            if ring:
                return _gemm_ring_jit(jnp.asarray(alpha, C.dtype), A,
                                      B, jnp.asarray(beta, C.dtype),
                                      C, tier,
                                      double_buffer=double_buffer)
            if gemm_a:
                return _gemm_a_jit(jnp.asarray(alpha, C.dtype), A, B,
                                   jnp.asarray(beta, C.dtype), C,
                                   tier)
            return _gemm_jit(jnp.asarray(alpha, C.dtype), A, B,
                             jnp.asarray(beta, C.dtype), C, tier)
        from ..robust import abft as _abft
        if not _abft.armed(opts):
            return _run()
        # Option.Abft: verify the output checksum identity
        # eᵀC_out = α·(eᵀA)·B + β·eᵀC_in against every SUMMA variant
        # (the check reads only inputs + output, so bcast/ring/gemmA
        # all share it); one recompute, then SdcDetected
        return _abft.gemm_verified(_run, A, B, C.data, alpha, beta,
                                   tier)


@partial(cached_jit, static_argnames=("tier",))
def _gemm_jit(alpha, A, B, beta, C, tier=None):
    g = C.grid
    p, q, nb = g.p, g.q, C.nb
    kt = cdiv(A.n, nb)
    acc = _acc_dtype(C.dtype)
    pk = trailing_dot_kwargs(tier, A.dtype)
    # B and C ride the product as [·, nb, w]: each local block-row's
    # tiles side by side, cut to the columns that are real on some
    # device. The rest is zero padding in both, whose product is zero.
    ntl = B.data.shape[3]
    w = _carried_cols(B.n, nb, q, ntl)
    narrow = w < ntl * nb

    if g.size == 1:
        # Single-device fast path: no communication, so the SUMMA
        # k-loop collapses into ONE contraction over A's tiles where
        # they lie (no re-laid copy of A in either form). At the
        # stored width XLA tiles it onto the MXU in a single fused
        # pass (~1.5x the looped rate on a v5e; the loop pays one
        # dispatch per block step); at a carried width under it, a
        # convolution whose window runs over the tile columns (one
        # column at n = 16,384, nb = 1,024 on a v5e: 2.29 ms, the
        # program 2.85, where the stored width takes 19.98; a loop
        # over tile columns 2.37, over block-rows 2.62: PERF 6, PR 40).
        a, b, c = A.data[0, 0], B.data[0, 0], C.data[0, 0]
        if narrow:
            b, c = _side_by_side(b, w), _side_by_side(c, w)
        upd = jnp.einsum("acik,ckj->aij" if narrow else "acik,cbkj->abij",
                         a, b, preferred_element_type=acc, **pk)
        out = (beta * c).astype(acc) + alpha.astype(acc) * upd
        out = out.astype(c.dtype)
        if narrow:
            out = _as_tiles(out, ntl)
        return C._replace(data=out[None, None])

    def body(a, b, c, alpha, beta):
        a, b, c = _local(a), _local(b), _local(c)
        if narrow:
            b, c = _side_by_side(b, w), _side_by_side(c, w)
        c_acc = (beta * c).astype(acc)

        def step(k, c_acc):
            if narrow:
                acol = _tile_column(a, k // q)
            else:
                acol = lax.dynamic_index_in_dim(a, k // q, axis=1,
                                                keepdims=False)
            acol = comm.bcast_from_col(acol, k % q)      # [mtl, nb, nb]
            brow = lax.dynamic_index_in_dim(b, k // p, axis=0, keepdims=False)
            brow = comm.bcast_from_row(brow, k % p)      # [ntl, nb, nb]
            upd = jnp.einsum("aik,kj->aij" if narrow else "aik,bkj->abij",
                             acol, brow,                 # ... or [nb, w]
                             preferred_element_type=acc, **pk)
            return c_acc + alpha.astype(acc) * upd

        c_acc = lax.fori_loop(0, kt, step, c_acc)
        out = c_acc.astype(c.dtype)
        if narrow:
            out = _as_tiles(out, ntl)
        return out[None, None]

    data = _shard(body, g.mesh, 3, 2)(A.data, B.data, C.data, alpha, beta)
    return C._replace(data=data)


@partial(cached_jit, static_argnames=("tier", "double_buffer"))
def _gemm_ring_jit(alpha, A, B, beta, C, tier=None,
                   double_buffer=True):
    """Cannon/ring-systolic SUMMA over ICI (the pod-scale plan of
    SURVEY §5.7 — shift operand shards around the mesh with
    nearest-neighbor ``collective_permute`` hops while accumulating C,
    the dense-linear-algebra analog of ring attention).

    Generalized Cannon on the block-cyclic layout, any p×q: pre-skew
    A by r along mesh columns and B by c along mesh rows, then
    L = lcm(p,q) steps; at step s chip (r,c) holds A cols ≡ r+c+s
    (mod q) and B rows ≡ r+c+s (mod p), whose common k-classes are
    exactly one residue K₀ mod L (CRT) — a strided slot subset of
    each shard. Per step every chip moves only its own shard one hop
    (constant buffers, no one-to-many bcast hotspots); total traffic
    matches bcast-SUMMA but every transfer is a neighbor hop on the
    ICI torus. Relies on the storage invariant that padded tiles are
    zero (the same invariant the bcast SUMMA's edge tiles use).

    The step loop runs on :func:`comm.systolic_ring`: with
    ``double_buffer=True`` (default) the ``ppermute`` of block k+1 is
    issued before the local dot of block k consumes its buffer, so
    the shift hides under the MXU work; shift and dot commute, so
    both schedules are bitwise identical (tests/test_pipeline.py
    asserts it).
    """
    g = C.grid
    p, q, nb = g.p, g.q, C.nb
    kt = cdiv(A.n, nb)
    L = p * q // math.gcd(p, q)
    sA, sB = L // q, L // p
    acc = _acc_dtype(C.dtype)
    pk = trailing_dot_kwargs(tier, A.dtype)
    kk = jnp.arange(L, dtype=jnp.int32)

    def body(a, b, c, alpha, beta):
        a, b, c = _local(a), _local(b), _local(c)
        r, cc = comm.coords()
        c_acc = (beta * c).astype(acc)

        # slatetimeline: ring steps land on the same device tracks as
        # the factorization pipelines — the runtime owns the
        # phase→kind map, so `obs overlap` attributes shift-under-dot
        # hiding for ring captures too (identity unless capture is on)
        dev = r * q + cc
        ndev = p * q

        def ring_mark(x, phase, s, edge):
            return dag.mark(x, phase, step=s, device=dev, edge=edge,
                            routine="gemm.ring", ndev=ndev)

        # pre-skew: A(r,c) ← A(r, c+r); B(r,c) ← B(r+c, c) — t
        # conditional nearest-neighbor hops (rotation count differs
        # per row/column, so the skew is t masked ring shifts)
        for t in range(1, p):
            a_rot = comm.rotate_from_next(a, AXIS_Q, q)
            a = jnp.where(r >= t, a_rot, a)
        for t in range(1, q):
            b_rot = comm.rotate_from_next(b, AXIS_P, p)
            b = jnp.where(cc >= t, b_rot, b)

        # pad slot axes so they reshape into [.., K, stride, ..]
        mtl, ktlA = a.shape[0], a.shape[1]
        ktlB, ntl = b.shape[0], b.shape[1]
        Kn = max(-(-ktlA // sA), -(-ktlB // sB))
        a = jnp.pad(a, ((0, 0), (0, Kn * sA - ktlA), (0, 0), (0, 0)))
        b = jnp.pad(b, ((0, Kn * sB - ktlB), (0, 0), (0, 0), (0, 0)))
        a = a.reshape(mtl, Kn, sA, nb, nb)
        b = b.reshape(Kn, sB, ntl, nb, nb)

        def consume(s, bufs, c_acc):
            a, b = bufs
            res = r + cc + s
            a_res = res % q
            b_res = res % p
            k0 = jnp.argmax((kk % q == a_res) & (kk % p == b_res))
            oA = (k0 - a_res) // q          # < sA
            oB = (k0 - b_res) // p          # < sB
            a_sub = lax.dynamic_index_in_dim(a, oA, axis=2,
                                             keepdims=False)
            b_sub = lax.dynamic_index_in_dim(b, oB, axis=1,
                                             keepdims=False)
            a_sub = ring_mark(a_sub, "local_dot", s, "b")
            upd = jnp.einsum("amik,mbkj->abij", a_sub, b_sub,
                             preferred_element_type=acc, **pk)
            upd = ring_mark(upd, "local_dot", s, "e")
            return c_acc + alpha.astype(acc) * upd

        c_acc = comm.systolic_ring(
            L, (a, b), ((AXIS_Q, q), (AXIS_P, p)), consume, c_acc,
            double_buffer=double_buffer, instrument=ring_mark)
        return c_acc.astype(c.dtype)[None, None]

    data = _shard(body, g.mesh, 3, 2)(A.data, B.data, C.data, alpha, beta)
    return C._replace(data=data)


@partial(cached_jit, static_argnames=("tier",))
def _gemm_a_jit(alpha, A, B, beta, C, tier=None):
    """Stationary-A gemm (reference method.hh GemmA, src/gemmA.cc):
    A's shards never move — B is replicated to every chip, each chip
    contracts its LOCAL k-classes of A against it (partial C rows for
    every global tile column), and a reduce-scatter down mesh axis q
    sums the q partial contributions while landing each chip exactly
    its own block-cyclic C columns.  That reduce-scatter is the
    epilogue half of a ring all-reduce at ``(q-1)/q`` payload per
    link — half the wire bytes of the all-reduce a naive stationary-A
    would pay — and it beats broadcasting A when B is a narrow block
    column (the ``select_algo`` heuristic)."""
    g = C.grid
    p, q, nb = g.p, g.q, C.nb
    acc = _acc_dtype(C.dtype)
    pk = trailing_dot_kwargs(tier, A.dtype)
    ntlB = B.data.shape[3]
    mtlC, ntlC = C.data.shape[2], C.data.shape[3]
    ntB_p = ntlB * q                    # replicated global tile cols of B

    def body(a, b, c, alpha, beta):
        a, b, c = _local(a), _local(b), _local(c)
        c_acc = (beta * c).astype(acc)
        # replicate B: gather rows down axis p (cyclic) then columns
        # across axis q (cyclic) — every chip holds global-order B
        b_rows = comm.allgather_cyclic(b, p, AXIS_P)     # [ktB_p,ntlB,..]
        b_full = comm.allgather_cyclic(
            jnp.swapaxes(b_rows, 0, 1), q, AXIS_Q)       # [ntB_p,ktB_p,..]
        b_full = jnp.swapaxes(b_full, 0, 1)              # global (k, j)
        # local k-classes of A: slot m is global k = m·q + cc, which
        # is row m·q + cc of the replicated B
        cc = lax.axis_index(AXIS_Q)
        ktlA = a.shape[1]
        bk = jnp.take(b_full, jnp.clip(
            jnp.arange(ktlA) * q + cc, 0, b_full.shape[0] - 1), axis=0)
        # partial C(i, :) over this chip's k-classes — every global j
        part = jnp.einsum("amik,mbkj->abij", a, bk,
                          preferred_element_type=acc, **pk)
        # reduce-scatter epilogue: sum the q partials and keep the
        # cyclic j-classes this chip owns (class-major scatter order)
        part = (part.reshape(mtlC, ntlB, q, nb, nb)
                    .transpose(2, 1, 0, 3, 4)
                    .reshape(q * ntlB, mtlC, nb, nb))
        mine = comm.psum_scatter_cols(part)              # [ntlB,mtlC,..]
        upd = jnp.swapaxes(mine, 0, 1)                   # [mtlC,ntlB,..]
        upd = upd[:, :ntlC]
        upd = jnp.pad(upd, ((0, 0), (0, ntlC - upd.shape[1]),
                            (0, 0), (0, 0)))
        return (c_acc + alpha.astype(acc) * upd).astype(c.dtype)[None, None]

    data = _shard(body, g.mesh, 3, 2)(A.data, B.data, C.data, alpha, beta)
    return C._replace(data=data)


# ---------------------------------------------------------------------------
# herk / syrk — rank-k update of a Hermitian/symmetric matrix
# ---------------------------------------------------------------------------

def herk(alpha, A: Matrix, beta, C, opts=None):
    """C = alpha·op(A)·op(A)^H + beta·C, C Hermitian (src/herk.cc).

    Implemented as SUMMA where the "B row" is the conj-transposed panel
    column of A, fetched by an all-gather down the mesh column
    (replacing reference internal_herk's symmetric bcast set).
    """
    return _rank_k(alpha, A, beta, C, conj=True, opts=opts)


def syrk(alpha, A: Matrix, beta, C, opts=None):
    """C = alpha·op(A)·op(A)^T + beta·C, C symmetric (src/syrk.cc)."""
    return _rank_k(alpha, A, beta, C, conj=False, opts=opts)


def _rank_k(alpha, A, beta, C, conj: bool, opts=None):
    if A.op != Op.NoTrans:
        # op(A)·op(A)^{H/T}: materialize so storage is the left factor.
        A = A.materialize()
    slate_error_if(A.m != C.m or C.m != C.n, "rank-k dims")
    _check_compat(A, C)
    tier = resolve_tier(opts)
    with trace.block("herk" if conj else "syrk", precision=tier):
        return _rank_k_jit(jnp.asarray(alpha, C.dtype), A,
                           jnp.asarray(beta, C.dtype), C, conj, tier)


@partial(cached_jit, static_argnames=("conj", "tier"))
def _rank_k_jit(alpha, A, beta, C, conj, tier=None):
    g = C.grid
    p, q, nb = g.p, g.q, C.nb
    kt = cdiv(A.n, nb)
    nt = C.nt                       # true tile rows/cols of square C
    acc = _acc_dtype(C.dtype)
    pk = trailing_dot_kwargs(tier, A.dtype)
    mtl, ntl = C.data.shape[2], C.data.shape[3]
    mt_p = A.data.shape[2] * p      # gathered panel length

    def body(a, c, alpha, beta):
        a, c = _local(a), _local(c)
        c_acc = (beta * c).astype(acc)
        irows = masks.local_tile_rows(mtl, p)
        jcols = masks.local_tile_cols(ntl, q)            # global tile cols
        # C's padded tile columns can exceed the gathered panel length —
        # clip the take and zero the result to keep padding zero.
        keep = ((irows < nt)[:, None, None, None]
                & (jcols < nt)[None, :, None, None])

        def step(k, c_acc):
            acol = lax.dynamic_index_in_dim(a, k // q, axis=1, keepdims=False)
            full = comm.allgather_panel_rows(acol, p, k % q)  # [mt_p,nb,nb]
            rows = comm.bcast_from_col(acol, k % q)      # A(i,k), i≡r
            cols = jnp.take(full, jnp.clip(jcols, 0, mt_p - 1), axis=0)
            if conj:
                cols = jnp.conj(cols)
            upd = jnp.einsum("aik,bjk->abij", rows, cols,
                             preferred_element_type=acc, **pk)
            upd = jnp.where(keep, upd, jnp.zeros_like(upd))
            return c_acc + alpha.astype(acc) * upd

        c_acc = lax.fori_loop(0, kt, step, c_acc)
        return c_acc.astype(c.dtype)[None, None]

    data = _shard(body, g.mesh, 2, 2)(A.data, C.data, alpha, beta)
    return C._replace(data=data)


def her2k(alpha, A, B, beta, C, opts=None):
    """C = alpha·A·B^H + conj(alpha)·B·A^H + beta·C (src/her2k.cc)."""
    from ..matrix import conj_transpose
    G = gemm(alpha, A, conj_transpose(B), beta, _as_general(C), opts)
    G = gemm(jnp.conj(jnp.asarray(alpha, C.dtype)), B, conj_transpose(A),
             1.0, G, opts)
    return C._replace(data=G.data)


def syr2k(alpha, A, B, beta, C, opts=None):
    """C = alpha·A·B^T + alpha·B·A^T + beta·C (src/syr2k.cc)."""
    from ..matrix import transpose
    G = gemm(alpha, A, transpose(B), beta, _as_general(C), opts)
    G = gemm(alpha, B, transpose(A), 1.0, G, opts)
    return C._replace(data=G.data)


def _as_general(C):
    return Matrix(data=C.data, m=C.m, n=C.n, nb=C.nb, grid=C.grid)


# ---------------------------------------------------------------------------
# symm / hemm — one operand symmetric/Hermitian
# ---------------------------------------------------------------------------

def hemm(side: Side, alpha, A, B: Matrix, beta, C: Matrix, opts=None):
    """C = alpha·A·B + beta·C with A Hermitian (src/hemm.cc). A's
    significant triangle is mirrored into a general matrix first."""
    Afull = _mirror_full(A, conj=True)
    if side == Side.Left:
        return gemm(alpha, Afull, B, beta, C, opts)
    return gemm(alpha, B, Afull, beta, C, opts)


def symm(side: Side, alpha, A, B: Matrix, beta, C: Matrix, opts=None):
    """C = alpha·A·B + beta·C with A symmetric (src/symm.cc)."""
    Afull = _mirror_full(A, conj=False)
    if side == Side.Left:
        return gemm(alpha, Afull, B, beta, C, opts)
    return gemm(alpha, B, Afull, beta, C, opts)


@partial(cached_jit, static_argnames=("conj",))
def _mirror_full_jit(A, conj):
    g = A.grid
    nb = A.nb
    lower = A.uplo == Uplo.Lower
    mtl, ntl = A.data.shape[2], A.data.shape[3]

    def body(a):
        a = _local(a)
        tri = masks.uplo_mask(mtl, ntl, nb, g.p, g.q, lower=lower)
        return jnp.where(tri, a, jnp.zeros_like(a))[None, None]

    half = _shard(body, g.mesh, 1)(A.data)
    # mirror: full = half + (half)^{T/H} — global tile transpose. The
    # tile grid may be padded differently along rows (multiples of p)
    # and cols (multiples of q); refit the transpose before adding —
    # out-of-range tiles are zero padding, so cropping/padding is exact.
    tiles = bc_to_tiles(half)
    mirr = tiles.transpose(1, 0, 3, 2)
    if conj:
        mirr = jnp.conj(mirr)
    mirr = _fit_tiles(mirr, tiles.shape[0], tiles.shape[1])
    full_tiles = tiles + mirr
    full = bc_from_tiles(full_tiles, g.p, g.q)

    def fix_diag(f):
        f = _local(f)
        er = masks.local_elem_rows(mtl, nb, g.p)[:, None, :, None]
        ec = masks.local_elem_cols(ntl, nb, g.q)[None, :, None, :]
        f = jnp.where(er == ec, f / 2, f)
        return f[None, None]

    data = _shard(fix_diag, g.mesh, 1)(full)
    return Matrix(data=data, m=A.m, n=A.n, nb=nb, grid=g)


def _mirror_full(A, conj: bool) -> Matrix:
    """Fill the insignificant triangle from the significant one."""
    slate_error_if(A.op != Op.NoTrans, "mirror before transpose views")
    return _mirror_full_jit(A, conj)


# ---------------------------------------------------------------------------
# trmm — triangular matrix-matrix multiply
# ---------------------------------------------------------------------------

def trmm(side: Side, alpha, A, B: Matrix, opts=None):
    """B = alpha·op(A)·B or alpha·B·op(A), A triangular (src/trmm.cc).
    A's triangle is extracted to a general matrix, then SUMMA."""
    Atri = _extract_triangle(A)
    if side == Side.Left:
        C = Matrix.zeros(B.m, B.n, B.nb, B.grid, dtype=B.dtype)
        return gemm(alpha, Atri, B, 0.0, C)
    C = Matrix.zeros(B.m, B.n, B.nb, B.grid, dtype=B.dtype)
    return gemm(alpha, B, Atri, 0.0, C)


@cached_jit
def _extract_triangle_jit(A):
    g = A.grid
    nb = A.nb
    lower = A.uplo == Uplo.Lower
    unit = A.diag == Diag.Unit
    mtl, ntl = A.data.shape[2], A.data.shape[3]

    def body(a):
        a = _local(a)
        tri = masks.uplo_mask(mtl, ntl, nb, g.p, g.q, lower=lower)
        out = jnp.where(tri, a, jnp.zeros_like(a))
        if unit:
            er = masks.local_elem_rows(mtl, nb, g.p)[:, None, :, None]
            ec = masks.local_elem_cols(ntl, nb, g.q)[None, :, None, :]
            diag = (er == ec) & (er < A.m)
            out = jnp.where(diag, jnp.ones_like(out), out)
        return out[None, None]

    data = _shard(body, g.mesh, 1)(A.data)
    return Matrix(data=data, m=A.m, n=A.n, nb=nb, grid=g)


def _extract_triangle(A) -> Matrix:
    op = A.op
    base = A if op == Op.NoTrans else A.materialize()
    return _extract_triangle_jit(base)


# ---------------------------------------------------------------------------
# trsm — distributed triangular solve
# ---------------------------------------------------------------------------

def trsm(side: Side, alpha, A, B: Matrix, opts=None):
    """Solve op(A)·X = alpha·B (Left) or X·op(A) = alpha·B (Right),
    A triangular; X overwrites B (reference src/trsm.cc →
    work::trsm DAG, src/work/work_trsm.cc).

    Both sides run natively as a fori_loop of block substitution —
    per step one diag-tile bcast, a batched local triangular solve on
    the owner row (Left) or owner column (Right), an X panel bcast
    along the other mesh axis, and a trailing SUMMA-style update
    (exactly the reference's trsm DAG — work::trsm for Left, the
    trsmA/trsmB right-side bodies — with collectives for listBcast,
    src/work/work_trsm.cc).

    ``Side.Left`` reads op(A) where it lies: a ``Trans``/``ConjTrans``
    view goes to the same program as storage plus two static flags,
    which solves left-looking (the reference's trsmA reduce shape), so
    no transpose materializes. ``Side.Right`` with an op still
    resolves it by ``A.materialize()`` (a re-laid copy of A) first.

    ``Side.Left`` on a grid with q > 1 picks its stationary operand by
    the width of B (``_moves_x``; the span's ``form`` label and the
    counter ``trsm.move_x`` say which): a B of at most one tile column
    (``B.n <= nb``: ``potrs``/``getrs`` with a few right-hand sides)
    leaves A in place and moves ``[nb, w]`` block-rows of X over q
    (work::trsmA); a wider B, whose columns are spread over q, has the
    tile column k of A broadcast over q each step (work::trsm).

    On one device column (q = 1: every one-chip ``potrs``, ``getrs``,
    ``gels`` and refinement step) no operand crosses q, and a step reads
    column k of A tile by tile from where each tile is stored, the slots
    past the diagonal tile only (``_reads_tiles``; the span's ``read``
    label says ``tiles``, else ``column``, and the counter
    ``trsm.read_tiles`` counts the solves that took it): no copy of A in
    front of the loop, no product with the half of the column a mask had
    made zero.
    """
    op = {Op.NoTrans: "N", Op.Trans: "T", Op.ConjTrans: "C"}[A.op]
    with trace.block("trsm", op=op) as span:
        B = B.materialize()   # resolve any lazy op on B too
        if side == Side.Left:
            # the columns a device carries through the solve: a B
            # narrower than its storage is solved at its own width
            ntl = B.data.shape[3]
            w = _carried_cols(B.n, B.nb, B.grid.q, ntl)
            move_x = _moves_x(B.n, B.nb, B.grid.q)
            read_tiles = _reads_tiles(B.grid.q)
            span.label(nrhs=B.n, w=w, form="move_x" if move_x else "move_a",
                       read="tiles" if read_tiles else "column")
            if w < ntl * B.nb:
                obs.count("trsm.narrow", 1, op=op)
            if move_x:
                obs.count("trsm.move_x", 1, op=op)
            if read_tiles:
                obs.count("trsm.read_tiles", 1, op=op)
        else:
            span.label(nrhs=B.m, w=B.data.shape[2] * B.nb)
        flags = {}
        if side == Side.Left and op != "N":
            # in place: A's storage, its op as static flags. The stored
            # triangle is the one op(A) does not show (materialize()
            # flips Lower <-> Upper the same way; no uplo reads as Upper)
            obs.count("trsm.in_place", 1, op=op)
            Am = dataclasses.replace(A, op=Op.NoTrans)
            lower = A.uplo != Uplo.Upper
            # a real Aᴴ is Aᵀ: one program for both
            flags = {"trans": True, "conj": op == "C" and jnp.issubdtype(
                A.dtype, jnp.complexfloating)}
        else:
            Am = A.materialize()  # resolves op into storage, flips uplo
            lower = Am.uplo == Uplo.Lower
        unit = Am.diag == Diag.Unit
        if side == Side.Right:
            # X·op(A) = alpha·B — native column substitution
            slate_error_if(Am.n != B.n, "trsm dims")
            solve = _trsm_right_jit
        else:
            slate_error_if(Am.m != B.m, "trsm dims")
            solve = _trsm_left_jit
        _check_compat(Am, B)
        with trace.block("trsm.launch"):
            return solve(jnp.asarray(alpha, B.dtype), Am, B, lower, unit,
                         **flags)


LANES = 128     # the TPU's lane width: the last dim's tiling


def _carried_cols(n: int, nb: int, q: int, ntl: int) -> int:
    """Leading local columns of an ``n``-column matrix's ``[·, ntl, ·,
    nb]`` storage that hold a real column on some device column (device
    column 0 holds the most), rounded up to whole lanes and capped at
    the stored ``ntl·nb``; the columns past them are zero padding on
    every device. What ``trsm(Side.Left)`` carries of B through a
    solve and what ``gemm`` multiplies of B and accumulates of C."""
    slot = max(n - 1, 0) // (q * nb)          # last local tile slot in use
    real = slot * nb + min(nb, n - slot * q * nb)
    return min(cdiv(max(real, 1), LANES) * LANES, ntl * nb)


def _side_by_side(t: jax.Array, w: int) -> jax.Array:
    """Local tiles ``[mtl, ntl, nb, nb]`` as ``[mtl, nb, w]``: each
    block-row's tiles side by side, cut to the leading ``w`` columns."""
    mtl, ntl, nb, _ = t.shape
    return t.transpose(0, 2, 1, 3).reshape(mtl, nb, ntl * nb)[:, :, :w]


def _tile_column(a: jax.Array, s) -> jax.Array:
    """Local tile column ``a[:, s]`` of ``[mtl, ktl, nb, nb]`` read tile
    by tile, in the order A is stored in. One slice of the column inside
    a loop wants it contiguous, and XLA then re-orders a copy of all the
    local A before the loop (PERF 7, fault 3a). ``gemm``'s form on a
    grid, where the column is sent whole. ``_trsm_left_jit`` on one
    device column builds no column at all: it multiplies each tile as
    it reads it and stops at the diagonal (``_reads_tiles``; feeding
    its masked product from here instead was 5.77 ms a solve against
    3.49 at n = 16,384, by hand, PR 46)."""
    mtl, _, nb, _ = a.shape

    def slot(r, col):
        tile = lax.dynamic_slice(a, (r, s, 0, 0), (1, 1, nb, nb))[0]
        return lax.dynamic_update_slice(col, tile, (r, 0, 0))

    return lax.fori_loop(0, mtl, slot, jnp.zeros((mtl, nb, nb), a.dtype))


def _as_tiles(x: jax.Array, ntl: int) -> jax.Array:
    """``_side_by_side``'s inverse: ``[mtl, nb, w]`` back into
    ``[mtl, ntl, nb, nb]``, exact zeros past column ``w``."""
    mtl, nb, w = x.shape
    x = jnp.pad(x, ((0, 0), (0, 0), (0, ntl * nb - w)))
    return x.reshape(mtl, nb, ntl, nb).transpose(0, 2, 1, 3)


def _moves_x(n: int, nb: int, q: int) -> bool:
    """Whether a left solve against an ``n``-column B on ``q`` device
    columns keeps A where it is stored and moves X over q instead.

    True when there is an axis to move over and B lies in one tile
    column: tile column 0 sits on device column 0 (``Matrix.sub``
    returns a re-laid copy, so no view shifts it), the other device
    columns hold only padding, and a block-row of X (``[nb, w]``) is
    smaller than the column of A (``[mtl, nb, nb]``) it meets. A wider
    B spreads X's columns over q: every device column then has work
    for the A it receives, and X whole would cost q times its memory."""
    return q > 1 and n <= nb


def _reads_tiles(q: int) -> bool:
    """Whether a step of a left solve on ``q`` device columns takes tile
    column k of A one ``[nb, nb]`` tile at a time, each from where it is
    stored, and only the slots past the diagonal tile.

    True when nothing crosses q for the column (one device column). As
    one ``[mtl, nb, nb]`` value the column costs a re-laid copy of all
    the local A in front of the loop (PERF 7, fault 3a) and a product
    with its masked half; tile by tile both go. By hand on a v5e, ms a
    call against the column whole: 3.49 against 7.44 at n = 16,384,
    nb = 1,024, 8 right-hand sides (3.65 / 7.74 under an op), 2.70 / 3.35
    at n = 10,000, nb = 384, 24.1 / 45.1 against 2,048 right-hand sides,
    level at n = 1,024, nb = 256 (0.28 / 0.27): one form at every shape
    measured (PERF 6, PR 46). Over q > 1 the column is what
    ``bcast_from_col`` sends, one value, unless B is one tile column
    (``_moves_x``)."""
    return q == 1


@partial(cached_jit, static_argnames=("lower", "unit", "trans", "conj"))
def _trsm_left_jit(alpha, A, B, lower, unit, trans=False, conj=False):
    """op(A)·X = alpha·B on A's storage: ``lower`` names the stored
    triangle, ``trans``/``conj`` the op. NoTrans is right-looking
    (solve block-row k, subtract its outer product from the rows
    left); an op is left-looking, X(k,:) = op(A(k,k))⁻¹·(α·B(k,:) −
    Σ_i A(i,k)ᴴ·X(i,:)) over the rows i already solved: tile A(i,k)
    and tile row X(i,:) share mesh row i % p, so each device contracts
    its own slots of column k and one reduce down the mesh column
    stands where the right-looking form broadcasts X(k,:).

    Over q one operand has to travel each step. By default it is tile
    column k of A (``bcast_from_col``, ``[mtl, nb, nb]``), which meets
    the columns of X that each device column stores. When B is one
    tile column (``_moves_x``) A stays and X travels: device column
    k % q alone reads column k from its own storage, and what crosses
    q is ``[nb, w]``. NoTrans then keeps on each device column its own
    share of the running right-hand side (device column 0 starts from
    alpha·B, the others from zero; their sum is alpha·B(i,:) −
    Σ_j A(i,j)·X(j,:) over the rows j solved) and sums block-row k over
    q before solving it; an op keeps X whole on every device column,
    contracts column k tile by tile in the order A is stored in, and
    reduces the partial sums over q as well as over p. Either way X
    leaves as it is stored: on device column 0, exact zeros beside.

    On one device column nothing crosses q (``_reads_tiles``), and a
    step reads column k as the op'd form above does, one ``[nb, nb]``
    tile at a time from where it is stored, with the loop's bounds at
    the diagonal tile (a trip count read off k): NoTrans subtracts
    A(s,k)·X(k,:) from the slots s left to update, an op sums
    A(s,k)ᴴ·X(s,:) in f32 over the slots already solved. The slots at
    and before the diagonal are neither read nor multiplied, and the
    program holds no copy of A (what the column as one value cost:
    ``_reads_tiles``). NoTrans gives the values the masked column gave, tile for tile; an
    op's sum runs over the same tiles in slot order."""
    g = B.grid
    p, q, nb = g.p, g.q, B.nb
    mt = cdiv(A.m, nb)
    mtl, ntl = B.data.shape[2], B.data.shape[3]
    # X rides the loop as [mtl, nb, w]: each local block-row's tiles
    # side by side, cut to the columns that are real on some device.
    # The rest is B's zero padding, whose solution is zero.
    w = _carried_cols(B.n, nb, q, ntl)
    move_x = _moves_x(B.n, nb, q)
    read_tiles = _reads_tiles(q)
    # policy (internal/precision.py): triangular solves always bf16_6x
    pk6 = trailing_dot_kwargs("bf16_6x", B.dtype)

    def diag_tile(a, k):
        akk = lax.dynamic_slice(
            a, (k // p, k // q, 0, 0), (1, 1, nb, nb))[0, 0]
        akk = comm.bcast_from_owner(akk, k % p, k % q)
        akk = tile_diag_pad_identity(akk, k, A.m, nb)
        tri = jnp.tril(akk) if lower else jnp.triu(akk)
        if unit:
            tri = tri - jnp.diag(jnp.diag(tri)) + jnp.eye(nb, dtype=tri.dtype)
        return tri

    def body(a, x, alpha):
        a, x = _local(a), _local(x)
        r, c = comm.coords()
        x = _side_by_side(x, w) * alpha
        gi = masks.local_tile_rows(mtl, p)               # [mtl]
        if move_x:
            # alpha·B is device column 0's; the others bring nothing
            x = jnp.where(c == 0, x, jnp.zeros_like(x))
            if trans:
                x = comm.psum_cols(x)        # X whole on every device column

        def past_diag(k):
            """Which local slots of tile column k of A lie past the
            diagonal tile: the rows left to update (NoTrans), the rows
            already solved (op). With A stationary, none but on the
            device column that stores column k."""
            rows = (gi > k) if lower else (gi < k)
            return rows & (c == k % q) if move_x else rows

        def past_diag_slots(k):
            """The same slots as the range [lo, hi) they fill (``gi``
            ascends), for a loop that visits no other."""
            if lower:
                return jnp.sum(gi <= k), mtl
            return 0, jnp.sum(gi < k)

        def tile(s, k):
            """Local slot s of tile column k, from where it is stored."""
            return lax.dynamic_slice(
                a, (s, k // q, 0, 0), (1, 1, nb, nb))[0, 0]

        def column(k):
            """Those slots of tile column k as one value, the rest zero."""
            acol = lax.dynamic_index_in_dim(a, k // q, axis=1, keepdims=False)
            if not move_x:
                acol = comm.bcast_from_col(acol, k % q)      # [mtl, nb, nb]
            return jnp.where(past_diag(k)[:, None, None], acol,
                             jnp.zeros_like(acol))

        def column_h_times(k, x):
            """Σ_i A(i,k)ᴴ · X(i,:) over this device's slots of tile
            column k past the diagonal tile."""
            if not (move_x or read_tiles):
                acol = column(k)
                if conj:
                    acol = jnp.conj(acol)
                return jnp.einsum("aki,akj->ij", acol, x, **pk6)
            # A stationary: read tile by tile, in the order A is stored
            # in. One product over all the slots wants tile column k
            # contiguous, and XLA then re-orders a copy of all the
            # local A before the loop (PERF 7, fault 3a). On one device
            # column the trips are the slots past the diagonal; where X
            # moved they are all mtl, masked
            rows = past_diag(k)

            def slot(s, acc):
                t = tile(s, k)
                if not read_tiles:
                    t = jnp.where(rows[s], t, jnp.zeros_like(t))
                if conj:
                    t = jnp.conj(t)
                return acc + jnp.einsum("ki,kj->ij", t, x[s], **pk6)

            lo, hi = past_diag_slots(k) if read_tiles else (0, mtl)
            return lax.fori_loop(lo, hi, slot, jnp.zeros((nb, w), x.dtype))

        def minus_column_times(k, x, xrow):
            """X(i,:) − A(i,k) · X(k,:) on this device's slots of tile
            column k past the diagonal tile, ``xrow`` the solved X(k,:)."""
            if not read_tiles:
                return x - jnp.einsum("aik,kj->aij", column(k), xrow, **pk6)

            # tile by tile as above: neither the copy of A nor the
            # product with the masked half of the column
            def slot(s, x):
                xs = x[s] - jnp.einsum("ik,kj->ij", tile(s, k), xrow, **pk6)
                return lax.dynamic_update_index_in_dim(x, xs, s, 0)

            return lax.fori_loop(*past_diag_slots(k), slot, x)

        def step(t, x):
            k = t if lower else mt - 1 - t
            with jax.named_scope("diag_solve"):
                tri = diag_tile(a, k)
                # owner row solves its block-row k
                xrow = lax.dynamic_index_in_dim(x, k // p, axis=0, keepdims=False)
                # A stationary: x is this device column's share of the
                # running right-hand side, block-row k the sum of them
                rhs = comm.psum_cols(xrow) if move_x else xrow   # [nb, w]
                solved = lax.linalg.triangular_solve(
                    tri, rhs, left_side=True, lower=lower,
                    unit_diagonal=unit)
                xrow = jnp.where(r == k % p, solved, xrow)
                x = lax.dynamic_update_index_in_dim(x, xrow, k // p, axis=0)
            with jax.named_scope("update"):
                xrow_b = comm.bcast_from_row(xrow, k % p)    # [nb, w]
                # trailing update: B(i,:) -= A(i,k) · X(k,:) for remaining i
                return minus_column_times(k, x, xrow_b)

        def step_op(t, x):
            # op(A) is lower iff the stored triangle is upper: a stored
            # lower triangle solves from the last block-row up
            k = mt - 1 - t if lower else t
            with jax.named_scope("update"):
                # Σ_i A(i,k)ᴴ · X(i,:) over the rows already solved
                acc = column_h_times(k, x)
                acc = comm.psum_rows(acc)                    # [nb, w]
                if move_x:
                    acc = comm.psum_cols(acc)
            with jax.named_scope("diag_solve"):
                tri = diag_tile(a, k)
                xrow = lax.dynamic_index_in_dim(x, k // p, axis=0, keepdims=False)
                solved = lax.linalg.triangular_solve(
                    tri, xrow - acc, left_side=True, lower=lower,
                    unit_diagonal=unit, transpose_a=True, conjugate_a=conj)
                xrow = jnp.where(r == k % p, solved, xrow)
                return lax.dynamic_update_index_in_dim(x, xrow, k // p, axis=0)

        x = lax.fori_loop(0, mt, step_op if trans else step, x)
        if move_x:
            # the solved rows sit on every device column: X is stored
            # on device column 0, exact zeros beside it
            x = jnp.where(c == 0, x, jnp.zeros_like(x))
        return _as_tiles(x, ntl)[None, None]

    data = _shard(body, g.mesh, 2, 1)(A.data, B.data, alpha)
    return B._replace(data=data)


@partial(cached_jit, static_argnames=("lower", "unit"))
def _trsm_right_jit(alpha, A, B, lower, unit):
    """X·A = alpha·B with A triangular (storage uplo): block column
    substitution, the exact mirror of _trsm_left_jit with the mesh
    axes swapped. For lower A the columns solve in reverse order
    (X(:,k) = (B(:,k) − Σ_{j>k} X(:,j)·A(j,k))·A(k,k)⁻¹)."""
    g = B.grid
    p, q, nb = g.p, g.q, B.nb
    nt = cdiv(A.n, nb)
    mtl, ntl = B.data.shape[2], B.data.shape[3]
    # policy (internal/precision.py): triangular solves always bf16_6x
    pk6 = trailing_dot_kwargs("bf16_6x", B.dtype)

    def body(a, x, alpha):
        a, x = _local(a), _local(x)
        r, c = comm.coords()
        x = x * alpha
        gj = masks.local_tile_cols(ntl, q)               # [ntl]

        def step(t, x):
            k = nt - 1 - t if lower else t
            akk = lax.dynamic_slice(
                a, (k // p, k // q, 0, 0), (1, 1, nb, nb))[0, 0]
            akk = comm.bcast_from_owner(akk, k % p, k % q)
            akk = tile_diag_pad_identity(akk, k, A.n, nb)
            tri = jnp.tril(akk) if lower else jnp.triu(akk)
            if unit:
                tri = (tri - jnp.diag(jnp.diag(tri))
                       + jnp.eye(nb, dtype=tri.dtype))
            # owner column solves its slots of block-column k
            xcol = lax.dynamic_index_in_dim(x, k // q, axis=1,
                                            keepdims=False)  # [mtl,nb,nb]
            solved = lax.linalg.triangular_solve(
                jnp.broadcast_to(tri, (mtl, nb, nb)), xcol,
                left_side=False, lower=lower, unit_diagonal=unit)
            xcol = jnp.where(c == k % q, solved, xcol)
            x = lax.dynamic_update_index_in_dim(x, xcol, k // q, axis=1)
            xcol_b = comm.bcast_from_col(xcol, k % q)    # [mtl, nb, nb]
            # trailing update: B(:,j) -= X(:,k) · A(k,j) for remaining j
            arow = lax.dynamic_index_in_dim(a, k // p, axis=0,
                                            keepdims=False)  # [ntl,nb,nb]
            arow = comm.bcast_from_row(arow, k % p)
            rem = (gj < k) if lower else (gj > k)
            arow = jnp.where(rem[:, None, None], arow,
                             jnp.zeros_like(arow))
            upd = jnp.einsum("aik,bkj->abij", xcol_b, arow, **pk6)
            return x - upd

        x = lax.fori_loop(0, nt, step, x)
        return x[None, None]

    data = _shard(body, g.mesh, 2, 1)(A.data, B.data, alpha)
    return B._replace(data=data)


# ---------------------------------------------------------------------------
# Band ops — v1: dense-path fallbacks over band-masked operands
# (reference src/gbmm.cc, hbmm.cc, tbsm.cc). Packed-band storage and
# band-aware loop bounds are a planned optimization; semantics match.
# ---------------------------------------------------------------------------

def gbmm(alpha, A, B: Matrix, beta, C: Matrix, opts=None):
    """C = alpha·op(A)·op(B) + beta·C, A general band (src/gbmm.cc).
    Band-limited: packed-A windowed matmul, O(m·(kl+ku)·n_B) flops
    (linalg/band.py bandmm_packed) instead of the dense O(m·n·n_B).
    The packed path replicates A (band-packed) and B/C dense per
    device; matrices too large to replicate fall back to the
    distributed band-masked SUMMA (old behavior: full flops, O(1)
    extra memory)."""
    from ..linalg import band as _band
    Am = A.materialize()
    Bm = B.materialize()
    kl, ku = Am.kl, Am.ku
    slate_error_if(Am.n != Bm.m, "gbmm dims")
    repl_bytes = (max(Am.m, Am.n) * Bm.n
                  * jnp.result_type(Am.dtype, Bm.dtype).itemsize)
    if repl_bytes > 1 << 28:               # ~256 MB replicated per device
        return gemm(alpha, _band_to_general(Am), Bm, beta, C)
    with trace.block("gbmm"):
        mt = cdiv(Am.m, Am.nb)
        ncols = mt * Am.nb + kl + ku
        ab = _band.pack_tiled(Am, kl, ku, ncols, band=(kl, ku))
        b = _band._b_to_dense(Bm, kl + ncols)
        bpad = jnp.concatenate(
            [jnp.zeros((kl, b.shape[1]), b.dtype), b], axis=0)
        out = _band.bandmm_packed(ab, bpad, Am.m, Am.n, kl, ku, Am.nb)
        cd = _band._b_to_dense(C, out.shape[0])
        if cd.shape[0] > out.shape[0]:
            out = jnp.pad(out, ((0, cd.shape[0] - out.shape[0]), (0, 0)))
        res = (jnp.asarray(alpha, C.dtype) * out[: cd.shape[0]]
               + jnp.asarray(beta, C.dtype) * cd)
        return _band._dense_to_b(res, C)


def hbmm(side: Side, alpha, A, B: Matrix, beta, C: Matrix, opts=None):
    """Hermitian-band × general (src/hbmm.cc): mirror the stored
    triangle to a full band, then the packed band multiply."""
    from ..linalg import band as _band
    kd = A.kl if A.uplo != Uplo.Upper else A.ku
    Af = _mirror_full(A, conj=jnp.issubdtype(A.dtype,
                                             jnp.complexfloating))
    Ab = BandMatrix(data=Af.data, m=A.m, n=A.n, nb=A.nb, grid=A.grid,
                    kl=kd, ku=kd)
    if side == Side.Right:
        # native right multiply C = α·B·A + β·C: packed band windows
        # hit B's columns directly (right-side mirror of gbmm's packed
        # kernel) — no conj-transpose materialization round-trips
        slate_error_if(B.n != Ab.m, "hbmm dims")
        with trace.block("hbmm_right"):
            nbw = Ab.nb
            nt = cdiv(Ab.n, nbw)
            ab = _band.pack_tiled(Ab, kd, kd, nt * nbw + nbw + 2 * kd,
                                  band=(kd, kd))
            bd = _band._b_to_dense(B, 0)
            need = nt * nbw + 2 * kd
            bd = jnp.pad(bd, ((0, 0),
                              (kd, max(0, need - kd - bd.shape[1]))))
            out = _band.bandmm_packed_right(ab, bd, Ab.m, Ab.n, kd, kd,
                                            nbw)
            cd = _band._b_to_dense(C, 0)
            if cd.shape[1] > out.shape[1]:
                out = jnp.pad(out, ((0, 0),
                                    (0, cd.shape[1] - out.shape[1])))
            if cd.shape[0] > out.shape[0]:
                out = jnp.pad(out, ((0, cd.shape[0] - out.shape[0]),
                                    (0, 0)))
            res = (jnp.asarray(alpha, C.dtype)
                   * out[:cd.shape[0], :cd.shape[1]]
                   + jnp.asarray(beta, C.dtype) * cd)
            return _band._dense_to_b(res, C)
    return gbmm(alpha, Ab, B, beta, C)


def tbsm(side: Side, alpha, A, B: Matrix, pivots=None, opts=None):
    """Triangular-band solve, optionally with pivots applied first
    (reference src/tbsm.cc / tbsmPivots.cc). Both sides run packed
    band kernels (O(n·kd·nrhs) — see linalg/band.py tbsm_packed /
    tbsm_packed_right); no transpose materialization round-trips."""
    if pivots is not None:
        from ..linalg.getrf import _apply_pivots_matrix
        B = _apply_pivots_matrix(B, pivots, forward=True)
    if side == Side.Right:
        from ..linalg import band as _band
        Am = A.materialize()      # resolves op; flips uplo and kl/ku
        slate_error_if(Am.m != Am.n,
                       "tbsm needs a square triangular factor")
        slate_error_if(Am.n != B.n, "tbsm dims")
        lower = Am.uplo == Uplo.Lower
        kd = Am.kl if lower else Am.ku
        n = Am.n
        nbw = _band._band_block(n, kd)
        nt = cdiv(n, nbw)
        with trace.block("tbsm_right"):
            ab = _band.pack_tiled(
                Am, kd if lower else 0, 0 if lower else kd,
                nt * nbw + nbw + kd,
                mode="tril" if lower else "triu")
            bd = _band._b_to_dense(B, 0)
            ncols = bd.shape[1]
            need = nt * nbw + kd
            b2 = jnp.pad(bd, ((0, 0),
                              (kd, max(0, need - ncols) + kd)))
            if alpha != 1.0:
                b2 = jnp.asarray(alpha, b2.dtype) * b2
            x = _band.tbsm_packed_right(ab, b2, n, kd, nbw, lower,
                                        Am.diag == Diag.Unit)
            return _band._dense_to_b(x[:, kd:kd + ncols], B)

    from ..linalg import band as _band
    Am = A.materialize()          # resolves op; flips uplo and kl/ku
    slate_error_if(Am.m != Am.n, "tbsm needs a square triangular factor")
    slate_error_if(Am.n != B.m, "tbsm dims")
    _check_compat(Am, B)
    lower = Am.uplo == Uplo.Lower
    kd = Am.kl if lower else Am.ku
    n = Am.n
    nbw = _band._band_block(n, kd)
    pad = cdiv(n, nbw) * nbw + kd
    with trace.block("tbsm"):
        ab = _band.pack_tiled(Am, kd if lower else 0, 0 if lower else kd,
                              cdiv(n, nbw) * nbw + nbw + kd,
                              mode="tril" if lower else "triu")
        b = _band._b_to_dense(B, pad)
        if alpha != 1.0:
            b = jnp.asarray(alpha, b.dtype) * b
        x = _band.tbsm_packed(ab, b, n, kd, nbw, lower,
                              Am.diag == Diag.Unit, False, False)
        return _band._dense_to_b(x, B)


@cached_jit
def _band_to_general_jit(A):
    g = A.grid
    nb = A.nb
    mtl, ntl = A.data.shape[2], A.data.shape[3]

    def body(a):
        a = _local(a)
        bm = masks.band_mask(mtl, ntl, nb, g.p, g.q, A.kl, A.ku)
        return jnp.where(bm, a, jnp.zeros_like(a))[None, None]

    data = _shard(body, g.mesh, 1)(A.data)
    return Matrix(data=data, m=A.m, n=A.n, nb=nb, grid=g)


def _band_to_general(A) -> Matrix:
    Am = A.materialize()
    return _band_to_general_jit(Am)
