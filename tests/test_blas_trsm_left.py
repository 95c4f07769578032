"""The forms of ``trsm(Side.Left)``: op(A) read where A is stored, a B
narrower than its storage solved at the width it holds
(``_carried_cols``), which operand moves over q (``_moves_x``), and the
tile-by-tile read of column k on one device column (``_reads_tiles``).

The three rules are functions of static shape alone and are pinned here
by their truth tables at microseconds a case, beside the lowered
programs' collectives and flops; the numeric cross over the widths is in
tests/test_blas_trsm_narrow_b*.py.
"""

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu.types import Side, Uplo, Diag
from tests.conftest import all_reduce_shapes, rand, tri


@pytest.mark.parametrize("shape,n", [
    ("1x1", 19), ("2x4", 19), ("4x2", 19), ("2x2", 19),
    # one device column, six tile rows: the sum over the tiles of
    # column k stops at the diagonal tile, whatever p deals a device
    ("1x1", 45), ("2x1", 45)])
@pytest.mark.parametrize("diag", [Diag.NonUnit, Diag.Unit])
@pytest.mark.parametrize("op", ["t", "c"])
@pytest.mark.parametrize("uplo", [Uplo.Lower, Uplo.Upper])
def test_trsm_left_reads_op_in_place(uplo, op, diag, shape, n,
                                     materialized_ops):
    """``trsm(Side.Left, op(A), B)`` solves on A's storage: no operand
    with an op is materialized (a re-laid copy of A, an all-to-all),
    on a ragged n with a B narrower than a tile."""
    import jax
    p, q = map(int, shape.split("x"))
    grid = st.Grid(p, q, devices=jax.devices()[:p * q])
    dt = np.complex128 if op == "c" else np.float64
    nrhs, nb = 5, 8
    unit = diag == Diag.Unit
    a = rand(n, n, dt, 30) * 0.3 + (0 if unit else n * np.eye(n))
    t = tri(a, uplo == Uplo.Lower, unit=unit)
    opt = t.T if op == "t" else np.conj(t.T)
    b = rand(n, nrhs, dt, 31)
    A = st.TriangularMatrix.from_dense(a, nb=nb, grid=grid, uplo=uplo,
                                       diag=diag)
    B = st.Matrix.from_dense(b, nb=nb, grid=grid)
    view = st.transpose(A) if op == "t" else st.conj_transpose(A)
    X = st.trsm(Side.Left, 1.5, view, B)
    assert materialized_ops == [], materialized_ops
    np.testing.assert_allclose(opt @ np.asarray(X.to_dense()), 1.5 * b,
                               rtol=1e-10, atol=1e-10)


def test_trsm_left_op_of_a_general_matrix_reads_its_upper_triangle(grid24):
    """A matrix with no uplo is read as upper, op or no op: for op(A)
    that is the stored lower triangle (what materialize() gave)."""
    n, nrhs, nb = 19, 5, 8
    a = rand(n, n, np.float64, 32) + n * np.eye(n)
    b = rand(n, nrhs, seed=33)
    A = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    B = st.Matrix.from_dense(b, nb=nb, grid=grid24)
    X = st.trsm(Side.Left, 1.0, st.transpose(A), B)
    np.testing.assert_allclose(np.triu(a.T) @ np.asarray(X.to_dense()), b,
                               rtol=1e-10, atol=1e-10)


def _lower_trsm_left(grid, n, nb, nrhs, trans):
    import jax
    import jax.numpy as jnp
    from slate_tpu.ops import blas
    A = st.TriangularMatrix.from_dense(
        rand(n, n, np.float32, 42), nb=nb, grid=grid, uplo=Uplo.Lower)
    B = st.Matrix.from_dense(rand(n, nrhs, np.float32, 43), nb=nb,
                             grid=grid)
    return jax.jit(
        blas._trsm_left_jit._fn,
        static_argnames=("lower", "unit", "trans", "conj")).lower(
        jnp.float32(1.0), A, B, lower=True, unit=False,
        trans=trans).compile()


@pytest.mark.parametrize("trans", [False, True])
def test_trsm_left_lowering_collectives(grid22, trans):
    """The lowered 2x2 program for a B of one tile column (A stays, X
    moves): an op adds no all-gather and no all-to-all (the re-layout
    materialize() paid), and no all-reduce carries the local slots of a
    tile column of A."""
    import math
    from slate_tpu.internal import comm
    n, nb, nrhs, mtl, w = 600, 256, 8, 2, 128
    compiled = _lower_trsm_left(grid22, n, nb, nrhs, trans)
    stats = comm.collective_footprint(compiled)
    assert "all-gather" not in stats and "all-to-all" not in stats
    assert set(stats) == {"all-reduce"}
    text = compiled.as_text()
    assert max(math.prod(dims) for _, dims in all_reduce_shapes(text)) \
        < mtl * nb * nb
    # a step moves the diagonal tile over both axes and two [nb, w]
    # terms: the sum of block-row k's shares over q and the solved row
    # over p (NoTrans), the partial sums over p and over q (op); XLA
    # sends two of the four together: three all-reduces a step. An op
    # first makes B whole on every device column, [mtl, nb, w] once.
    assert stats["all-reduce"]["count"] == 3
    step = 2 * nb * nb + 2 * nb * w
    once = mtl * nb * w if trans else 0
    # (the program that moved A: 2 nb nb + mtl nb nb + nb w a step)
    # (the bytes of every member of a combined all-reduce, whatever
    # order XLA combined them in)
    assert stats["all-reduce"]["bytes"] == 4 * (step + once)


# -- a B narrower than its storage rides the solve at its own width --------
# nb=8 never crops (the carried width is whole lanes of 128, capped at
# the stored ntl*nb): these run the narrow shape at nb=256.

@pytest.mark.parametrize("n,nb,q,ntl,w", [
    (8, 1024, 1, 1, 128),       # the benchmark's cells: one chip,
    (8, 1024, 2, 1, 128),       # the 2x2 (device column 1: padding only)
    (8, 384, 1, 1, 128),        # and the tile of 384
    (1, 256, 1, 1, 128), (128, 256, 4, 1, 128), (129, 256, 1, 1, 256),
    (256, 256, 2, 1, 256),      # every stored column real
    (300, 256, 1, 2, 384),      # one whole tile and 44 columns
    (300, 256, 2, 1, 256),      # ... one tile a device column
    (1300, 256, 2, 3, 768),     # slot 2 of device column 0 ends at 276
    (5, 8, 4, 1, 8), (19, 8, 1, 3, 24), (130, 8, 1, 17, 136),
])
def test_trsm_carried_cols(n, nb, q, ntl, w):
    from slate_tpu.ops import blas
    assert blas._carried_cols(n, nb, q, ntl) == w


@pytest.mark.parametrize("n,nb,q,moves", [
    (8, 1024, 2, True),         # the benchmark's 2x2: A stays, X moves
    (8, 1024, 1, False),        # its one-chip cells: no axis to move over
    (8, 384, 1, False),
    (256, 256, 2, True),        # n = nb: still one tile column
    (257, 256, 2, False),       # n = nb + 1: X's columns spread over q
    (8, 256, 4, True), (256, 256, 4, True), (512, 256, 4, False),
    (1024, 256, 4, False), (1, 8, 2, True), (9, 8, 2, False),
])
def test_trsm_moves_x(n, nb, q, moves):
    from slate_tpu.ops import blas
    assert blas._moves_x(n, nb, q) is moves


@pytest.mark.parametrize("q,tiles", [
    (1, True),          # the one-chip cells, and p x 1: nothing crosses q
    (2, False),         # the 2x2: X moves (one tile column) or A's column
    (4, False),
])
def test_trsm_reads_tiles(q, tiles):
    from slate_tpu.ops import blas
    assert blas._reads_tiles(q) is tiles


@pytest.mark.parametrize("trans", [False, True])
@pytest.mark.parametrize("n,nb", [(600, 256), (1200, 1024)])
def test_trsm_left_narrow_b_pays_for_its_lanes(grid22, trans, n, nb):
    """8 right-hand sides cost 128 lanes of the tile's nb: the lowered
    step's flops follow the carried width (a half at nb=256, an eighth
    at nb=1024, and the diagonal block's inversion, which has no width)."""
    narrow = _lower_trsm_left(grid22, n, nb, 8, trans).cost_analysis()
    full = _lower_trsm_left(grid22, n, nb, nb, trans).cost_analysis()
    share = 128 / nb
    assert share * 0.98 <= narrow["flops"] / full["flops"] <= share * 1.06


@pytest.mark.parametrize("trans,flops", [(False, 68813928.0),
                                         (True, 68617312.0)])
def test_trsm_left_full_width_program_is_the_one_it_was(grid22, trans, flops):
    """A B of q whole tile columns (nrhs = 2 nb on the 2x2): every stored
    column real, the crop and the pad the identity, X's columns spread
    over q. The program moves A as it did (numbers read off the parent
    of PR 30, which are those of the parent of PR 28)."""
    from slate_tpu.internal import comm
    compiled = _lower_trsm_left(grid22, 600, 256, 512, trans)
    assert compiled.cost_analysis()["flops"] == flops
    stats = comm.collective_footprint(compiled)
    assert set(stats) == {"all-reduce"}
    assert stats["all-reduce"]["count"] == 3
    # the diagonal tile over both axes, two tiles of column k over q,
    # one tile row of X (or of partial sums) over p
    assert stats["all-reduce"]["bytes"] == 5 * 256 * 256 * 4
