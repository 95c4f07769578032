"""Level-3 BLAS beyond gemm: herk/syrk/her2k/syr2k, symm/hemm, trmm,
trsm (all sides/uplos/ops), band ops (reference test/test_{herk,symm,
trmm,trsm,...}.cc analogs)."""

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu.types import Side, Uplo, Diag, Op
from tests.conftest import all_reduce_shapes, padded_dense, rand


def tri(a, lower, unit=False):
    t = np.tril(a) if lower else np.triu(a)
    if unit:
        np.fill_diagonal(t, 1.0)
    return t


@pytest.mark.parametrize("dt", [np.float64, np.complex128])
def test_herk(grid24, dt):
    n, k, nb = 24, 16, 8
    a = rand(n, k, dt, 1)
    c0 = rand(n, n, dt, 2)
    c0 = (c0 + np.conj(c0.T)) / 2
    A = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    C = st.HermitianMatrix.from_dense(c0, nb=nb, grid=grid24)
    C2 = st.herk(2.0, A, 0.5, C)
    ref = 2.0 * a @ np.conj(a.T) + 0.5 * c0
    got = np.asarray(C2.to_dense())
    # only the lower triangle is significant
    np.testing.assert_allclose(np.tril(got), np.tril(ref), rtol=1e-12,
                               atol=1e-12)


def test_syrk_trans(grid24):
    n, k, nb = 16, 24, 8
    a = rand(k, n, np.float64, 3)
    C = st.SymmetricMatrix.zeros(n, n, nb, grid24, dtype=np.float64)
    C2 = st.syrk(1.0, st.transpose(st.Matrix.from_dense(a, nb=nb,
                                                        grid=grid24)),
                 0.0, C)
    ref = a.T @ a
    np.testing.assert_allclose(np.tril(np.asarray(C2.to_dense())),
                               np.tril(ref), rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("dt", [np.float64, np.complex128])
def test_her2k_syr2k(grid24, dt):
    n, k, nb = 16, 8, 8
    a, b = rand(n, k, dt, 4), rand(n, k, dt, 5)
    C = st.HermitianMatrix.zeros(n, n, nb, grid24, dtype=dt)
    alpha = 1.5 if dt == np.float64 else 1.5 + 0.5j
    C2 = st.her2k(alpha, st.Matrix.from_dense(a, nb=nb, grid=grid24),
                  st.Matrix.from_dense(b, nb=nb, grid=grid24), 0.0, C)
    ref = alpha * a @ np.conj(b.T) + np.conj(alpha) * b @ np.conj(a.T)
    np.testing.assert_allclose(np.tril(np.asarray(C2.to_dense())),
                               np.tril(ref), rtol=1e-12, atol=1e-12)
    assert isinstance(C2, st.HermitianMatrix)

    Cs = st.SymmetricMatrix.zeros(n, n, nb, grid24, dtype=dt)
    C3 = st.syr2k(2.0, st.Matrix.from_dense(a, nb=nb, grid=grid24),
                  st.Matrix.from_dense(b, nb=nb, grid=grid24), 0.0, Cs)
    ref = 2.0 * (a @ b.T + b @ a.T)
    np.testing.assert_allclose(np.tril(np.asarray(C3.to_dense())),
                               np.tril(ref), rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("side", [Side.Left, Side.Right])
@pytest.mark.parametrize("uplo", [Uplo.Lower, Uplo.Upper])
@pytest.mark.parametrize("dt", [np.float64, np.complex128])
def test_hemm_symm(grid24, side, uplo, dt):
    n, nrhs, nb = 16, 24, 8
    afull = rand(n, n, dt, 6)
    afull = (afull + np.conj(afull.T)) / 2
    bdim = (n, nrhs) if side == Side.Left else (nrhs, n)
    b = rand(*bdim, dtype=dt, seed=7)
    A = st.HermitianMatrix.from_dense(afull, nb=nb, grid=grid24, uplo=uplo)
    B = st.Matrix.from_dense(b, nb=nb, grid=grid24)
    C = st.Matrix.zeros(*bdim, nb, grid24, dtype=dt)
    C2 = st.hemm(side, 1.0, A, B, 0.0, C)
    ref = afull @ b if side == Side.Left else b @ afull
    np.testing.assert_allclose(np.asarray(C2.to_dense()), ref,
                               rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("side", [Side.Left, Side.Right])
@pytest.mark.parametrize("uplo", [Uplo.Lower, Uplo.Upper])
@pytest.mark.parametrize("diag", [Diag.NonUnit, Diag.Unit])
def test_trmm(grid24, side, uplo, diag):
    n, nrhs, nb = 16, 12, 8
    a = rand(n, n, np.float64, 8)
    t = tri(a, uplo == Uplo.Lower, diag == Diag.Unit)
    bdim = (n, nrhs) if side == Side.Left else (nrhs, n)
    b = rand(*bdim, seed=9)
    A = st.TriangularMatrix.from_dense(a, nb=nb, grid=grid24, uplo=uplo,
                                       diag=diag)
    B = st.Matrix.from_dense(b, nb=nb, grid=grid24)
    C = st.trmm(side, 2.0, A, B)
    ref = 2.0 * (t @ b) if side == Side.Left else 2.0 * (b @ t)
    np.testing.assert_allclose(np.asarray(C.to_dense()), ref,
                               rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("side", [Side.Left, Side.Right])
@pytest.mark.parametrize("uplo", [Uplo.Lower, Uplo.Upper])
@pytest.mark.parametrize("op", ["n", "t", "c"])
def test_trsm(grid24, side, uplo, op):
    dt = np.complex128 if op == "c" else np.float64
    n, nrhs, nb = 24, 16, 8
    a = rand(n, n, dt, 10) + n * np.eye(n)
    t = tri(a, uplo == Uplo.Lower)
    opf = {"n": lambda x: x, "t": lambda x: x.T,
           "c": lambda x: np.conj(x.T)}[op]
    stopf = {"n": lambda x: x, "t": st.transpose,
             "c": st.conj_transpose}[op]
    bdim = (n, nrhs) if side == Side.Left else (nrhs, n)
    b = rand(*bdim, dtype=dt, seed=11)
    A = st.TriangularMatrix.from_dense(a, nb=nb, grid=grid24, uplo=uplo)
    B = st.Matrix.from_dense(b, nb=nb, grid=grid24)
    X = st.trsm(side, 1.5, stopf(A), B)
    x = np.asarray(X.to_dense())
    if side == Side.Left:
        np.testing.assert_allclose(opf(t) @ x, 1.5 * b, rtol=1e-10,
                                   atol=1e-10)
    else:
        np.testing.assert_allclose(x @ opf(t), 1.5 * b, rtol=1e-10,
                                   atol=1e-10)


def test_trsm_unit_ragged(grid24):
    n, nrhs, nb = 19, 7, 8
    a = rand(n, n, np.float64, 12)
    t = tri(a, True, unit=True)
    b = rand(n, nrhs, seed=13)
    A = st.TriangularMatrix.from_dense(a, nb=nb, grid=grid24,
                                       uplo=Uplo.Lower, diag=Diag.Unit)
    B = st.Matrix.from_dense(b, nb=nb, grid=grid24)
    X = st.trsm(Side.Left, 1.0, A, B)
    np.testing.assert_allclose(t @ np.asarray(X.to_dense()), b,
                               rtol=1e-10, atol=1e-10)


def test_trsm_right_unit_ragged(grid24):
    m, n, nb = 13, 19, 8
    a = rand(n, n, np.float64, 21) * 0.1
    t = tri(a, False, unit=True)
    b = rand(m, n, seed=22)
    A = st.TriangularMatrix.from_dense(a, nb=nb, grid=grid24,
                                       uplo=Uplo.Upper, diag=Diag.Unit)
    B = st.Matrix.from_dense(b, nb=nb, grid=grid24)
    X = st.trsm(Side.Right, 1.0, A, B)
    np.testing.assert_allclose(np.asarray(X.to_dense()) @ t, b,
                               rtol=1e-10, atol=1e-10)


@pytest.mark.parametrize("mkn,nb", [((96, 96, 96), 8),
                                    ((100, 84, 60), 8),
                                    ((40, 130, 70), 16)])
def test_gemm_ring(grid24, mkn, nb):
    """Cannon ring-systolic gemm (MethodGemm.Ring): nearest-neighbor
    collective_permute hops instead of bcasts (SURVEY §5.7 ring-SUMMA;
    generalized to any p×q over lcm(p,q) steps)."""
    from slate_tpu.types import Option, MethodGemm
    m, k, n = mkn
    a = rand(m, k, np.float64, 40)
    b = rand(k, n, np.float64, 41)
    c0 = rand(m, n, np.float64, 42)
    A = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    B = st.Matrix.from_dense(b, nb=nb, grid=grid24)
    C = st.Matrix.from_dense(c0, nb=nb, grid=grid24)
    R = st.gemm(1.5, A, B, 0.5, C,
                opts={Option.MethodGemm: MethodGemm.Ring})
    ref = 1.5 * a @ b + 0.5 * c0
    np.testing.assert_allclose(np.asarray(R.to_dense()), ref,
                               rtol=1e-12, atol=1e-11)


def test_gemm_ring_complex(grid24):
    from slate_tpu.types import Option, MethodGemm
    m, k, n, nb = 48, 56, 40, 8
    a = rand(m, k, np.complex128, 43)
    b = rand(k, n, np.complex128, 44)
    C = st.Matrix.zeros(m, n, nb, grid24, dtype=np.complex128)
    R = st.gemm(1.0 + 0.5j, st.Matrix.from_dense(a, nb=nb, grid=grid24),
                st.Matrix.from_dense(b, nb=nb, grid=grid24), 0.0, C,
                opts={Option.MethodGemm: MethodGemm.Ring})
    np.testing.assert_allclose(np.asarray(R.to_dense()),
                               (1.0 + 0.5j) * a @ b,
                               rtol=1e-12, atol=1e-11)


@pytest.fixture
def materialized_ops(monkeypatch):
    """Class names of the operands with an op that ``materialize()``
    was called on (each one a re-laid copy: all-to-alls)."""
    from slate_tpu.matrix import BaseTiledMatrix
    calls = []
    orig = BaseTiledMatrix.materialize

    def counting(self):
        if self.op != Op.NoTrans:
            calls.append(type(self).__name__)
        return orig(self)

    monkeypatch.setattr(BaseTiledMatrix, "materialize", counting)
    return calls


def test_trsm_right_native_no_transpose(grid24, materialized_ops):
    """The Right-side solve must run natively (reference trsmA/trsmB,
    src/work/work_trsm.cc) — no transpose materializes (all-to-alls)."""
    n, m, nb = 24, 16, 8
    a = rand(n, n, np.float64, 23) + n * np.eye(n)
    A = st.TriangularMatrix.from_dense(a, nb=nb, grid=grid24,
                                       uplo=Uplo.Lower)
    B = st.Matrix.from_dense(rand(m, n, seed=24), nb=nb, grid=grid24)
    st.trsm(Side.Right, 1.0, A, B)
    assert materialized_ops == [], materialized_ops


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


def _all_reduce_bytes(hlo_text):
    """Bytes of every result of every all-reduce in an optimized HLO
    text."""
    import math
    return sum(size * math.prod(dims)
               for size, dims in all_reduce_shapes(hlo_text))


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
    # (collective_footprint's own ``bytes`` reads only the first shape
    # of a combined all-reduce, whose order XLA picks: PERF 7, fault 11.)
    assert stats["all-reduce"]["count"] == 3
    step = 2 * nb * nb + 2 * nb * w
    once = mtl * nb * w if trans else 0
    # (the program that moved A: 2 nb nb + mtl nb nb + nb w a step)
    assert _all_reduce_bytes(text) == 4 * (step + once)


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


def _narrow_b_cases():
    """Every width on the three older shapes; on 1x2 and 4x2, which are
    here for the form that moves X, the widths that take it (nrhs <= nb,
    its edge nrhs = nb included). One device column reads column k of A
    tile by tile from the diagonal on: five tile rows with a ragged
    last one (n = 1100), dealt to one, two and four device rows."""
    widths = [(1, Diag.NonUnit), (5, Diag.NonUnit), (5, Diag.Unit),
              (130, Diag.NonUnit), (256, Diag.NonUnit), (300, Diag.NonUnit)]
    for shape in ["1x1", "2x2", "2x4", "1x2", "4x2"]:
        for nrhs, diag in widths:
            if shape in ("1x2", "4x2") and nrhs not in (5, 256):
                continue
            yield pytest.param(shape, 600, nrhs, diag,
                               id=f"{shape}-{nrhs}-{diag.name}")
    for shape, nrhs, diag in [("1x1", 5, Diag.NonUnit), ("1x1", 5, Diag.Unit),
                              ("1x1", 300, Diag.NonUnit),
                              ("2x1", 5, Diag.NonUnit), ("2x1", 5, Diag.Unit),
                              ("4x1", 5, Diag.NonUnit)]:
        yield pytest.param(shape, 1100, nrhs, diag,
                           id=f"{shape}-n1100-{nrhs}-{diag.name}")


@pytest.mark.parametrize("uplo", [Uplo.Lower, Uplo.Upper])
@pytest.mark.parametrize("op", ["n", "t", "c"])
@pytest.mark.parametrize("shape,n,nrhs,diag", _narrow_b_cases())
def test_trsm_left_narrow_b(shape, n, op, uplo, nrhs, diag):
    """8 right-hand sides in a 256-wide tile: the answer, the stored
    padding, and the same columns out of a B of two or more tile
    columns, which on a grid is the other form (A moves, not X)."""
    import jax
    p, q = map(int, shape.split("x"))
    grid = st.Grid(p, q, devices=jax.devices()[:p * q])
    dt = np.complex64 if op == "c" else np.float32
    nb = 256
    unit = diag == Diag.Unit
    a = rand(n, n, dt, 40) * 0.3 + n * np.eye(n, dtype=dt)
    if unit:
        a = a / n               # off-diagonal small beside the implied 1
    t = tri(a, uplo == Uplo.Lower, unit)
    opt = {"n": t, "t": t.T, "c": np.conj(t.T)}[op]
    view = {"n": lambda x: x, "t": st.transpose,
            "c": st.conj_transpose}[op]
    b = rand(n, nrhs, dt, 41)
    A = st.TriangularMatrix.from_dense(a, nb=nb, grid=grid, uplo=uplo,
                                       diag=diag)
    X = st.trsm(Side.Left, 1.5, view(A),
                st.Matrix.from_dense(b, nb=nb, grid=grid))
    x = np.asarray(X.to_dense())
    ref = np.linalg.solve(opt.astype(np.complex128), 1.5 * b)
    assert np.abs(x - ref).max() <= 2e-6 * np.abs(ref).max()
    # the padding of X is stored as exact zeros, on every device column
    stored = padded_dense(X)
    assert stored.shape[1] >= nrhs and stored.shape[0] >= n
    assert not stored[:, nrhs:].any() and not stored[n:].any()
    np.testing.assert_array_equal(stored[:n, :nrhs], x)
    # B zero-extended to whole tiles of real columns, two at least:
    # nothing to crop, and no tile column that holds all of X
    wide = max(-(-nrhs // nb), 2) * nb
    bw = np.zeros((n, wide), dt)
    bw[:, :nrhs] = b
    Xw = st.trsm(Side.Left, 1.5, view(A),
                 st.Matrix.from_dense(bw, nb=nb, grid=grid))
    xw = np.asarray(Xw.to_dense())
    assert not xw[:, nrhs:].any()
    assert np.abs(x - xw[:, :nrhs]).max() <= 1e-6 * np.abs(xw).max()


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
    assert _all_reduce_bytes(compiled.as_text()) == 5 * 256 * 256 * 4


def test_gbmm(grid24):
    m, n, k, nb = 16, 12, 16, 8
    kl, ku = 2, 3
    a = rand(m, k, seed=14)
    band = np.zeros_like(a)
    for i in range(m):
        for j in range(k):
            if -kl <= j - i <= ku:
                band[i, j] = a[i, j]
    b = rand(k, n, seed=15)
    A = st.BandMatrix.from_dense(a, nb=nb, grid=grid24, kl=kl, ku=ku)
    B = st.Matrix.from_dense(b, nb=nb, grid=grid24)
    C = st.Matrix.zeros(m, n, nb, grid24, dtype=np.float64)
    C2 = st.gbmm(1.0, A, B, 0.0, C)
    np.testing.assert_allclose(np.asarray(C2.to_dense()), band @ b,
                               rtol=1e-12, atol=1e-12)


def test_syrk_padding_stays_zero():
    """Regression: OOB gather in rank-k must not write NaN into
    padding tiles (1x8 grid makes C's padded cols exceed the panel)."""
    import jax
    g = st.Grid(1, 8)
    n, nb = 100, 64
    G = st.random_matrix(n, n, nb, g, np.float64, seed=1)
    C = st.SymmetricMatrix.zeros(n, n, nb, g, dtype=np.float64)
    C2 = st.syrk(1.0, G, 0.0, C)
    assert np.isfinite(np.asarray(C2.data)).all()
    ref = np.asarray(G.to_dense()) @ np.asarray(G.to_dense()).T
    got = np.asarray(C2.to_dense())
    np.testing.assert_allclose(np.tril(got), np.tril(ref), rtol=1e-10,
                               atol=1e-10)


def test_right_side_native_no_transpose(grid24, monkeypatch):
    """tbsm/hbmm/unmqr Side.Right must run natively (reference
    src/tbsm.cc, src/hbmm.cc, src/unmqr.cc right-side task graphs) —
    no op-view materializes (each would cost two all-to-alls)."""
    from slate_tpu.matrix import BaseTiledMatrix
    from slate_tpu.types import Op
    from slate_tpu.linalg.geqrf import geqrf, unmqr
    calls = []
    orig = BaseTiledMatrix.materialize

    def counting(self):
        if self.op != Op.NoTrans:
            calls.append(type(self).__name__)
        return orig(self)

    monkeypatch.setattr(BaseTiledMatrix, "materialize", counting)
    n, m, nb, kd = 24, 16, 8, 3

    # tbsm Right: X·T = B
    t = np.tril(rand(n, n, np.float64, 31)) + n * np.eye(n)
    tb = np.zeros_like(t)
    for i in range(n):
        for j in range(max(0, i - kd), i + 1):
            tb[i, j] = t[i, j]
    T = st.TriangularBandMatrix.from_dense(tb, nb=nb, grid=grid24,
                                           kl=kd, ku=0, uplo=Uplo.Lower)
    B = st.Matrix.from_dense(rand(m, n, seed=32), nb=nb, grid=grid24)
    X = st.tbsm(Side.Right, 1.0, T, B)
    np.testing.assert_allclose(np.asarray(X.to_dense()) @ tb,
                               np.asarray(B.to_dense()), atol=1e-9)

    # hbmm Right: C = B·A + C
    h = rand(n, n, np.float64, 33)
    h = (h + h.T) / 2
    hb = np.where(np.abs(np.arange(n)[:, None]
                         - np.arange(n)[None, :]) <= kd, h, 0.0)
    Ah = st.HermitianBandMatrix.from_dense(np.tril(hb), nb=nb,
                                           grid=grid24, kl=kd, ku=0,
                                           uplo=Uplo.Lower)
    Bh = st.Matrix.from_dense(rand(m, n, seed=34), nb=nb, grid=grid24)
    Ch = st.Matrix.zeros(m, n, nb, grid24, dtype=np.float64)
    R = st.hbmm(Side.Right, 1.0, Ah, Bh, 0.0, Ch)
    np.testing.assert_allclose(np.asarray(R.to_dense()),
                               np.asarray(Bh.to_dense()) @ hb, atol=1e-9)

    # unmqr Right: C·Q
    a = rand(m, m, np.float64, 35)
    QR, Tq = geqrf(st.Matrix.from_dense(a, nb=nb, grid=grid24))
    C2 = st.Matrix.from_dense(rand(m, m, seed=36), nb=nb, grid=grid24)
    unmqr(Side.Right, Op.NoTrans, QR, Tq, C2)

    assert calls == [], calls
