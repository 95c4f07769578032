"""``trsm(Side.Left)`` against a B narrower than its storage, the
numeric cross on the meshes with more than one device column, where the
width of B picks which operand moves (``_moves_x``): every width under
every op, uplo and diag, each against numpy and against the same columns
of a wide B. One device column (``_reads_tiles``) is in
tests/test_blas_trsm_narrow_b_one_column.py, which runs this file's body;
the rules' truth tables are in tests/test_blas_trsm_left.py.

A case compiles its own narrow solve (B's order is static) and that is
what it costs; what its cases have in common is built once a module:
one A a (shape, n, uplo, diag, dtype), one reference solution a (n, op,
uplo, diag), one wide solve a (shape, n, op, uplo, diag).
"""

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu.types import Side, Uplo, Diag
from tests.conftest import padded_dense, rand, tri


def _narrow_b_cases(one_column):
    """The cases on one device column (q = 1) or on more. Every width
    on the three older shapes; on 1x2 and 4x2, which are here for the
    form that moves X, the widths that take it (nrhs <= nb, its edge
    nrhs = nb included). One device column reads column k of A tile by
    tile from the diagonal on: five tile rows with a ragged last one
    (n = 1100), dealt to one, two and four device rows."""
    widths = [(1, Diag.NonUnit), (5, Diag.NonUnit), (5, Diag.Unit),
              (130, Diag.NonUnit), (256, Diag.NonUnit), (300, Diag.NonUnit)]
    for shape in ["1x1", "2x2", "2x4", "1x2", "4x2"]:
        for nrhs, diag in widths:
            if shape in ("1x2", "4x2") and nrhs not in (5, 256):
                continue
            if (shape[-1] == "1") == one_column:
                yield pytest.param(shape, 600, nrhs, diag,
                                   id=f"{shape}-{nrhs}-{diag.name}")
    if not one_column:
        return
    for shape, nrhs, diag in [("1x1", 5, Diag.NonUnit), ("1x1", 5, Diag.Unit),
                              ("1x1", 300, Diag.NonUnit),
                              ("2x1", 5, Diag.NonUnit), ("2x1", 5, Diag.Unit),
                              ("4x1", 5, Diag.NonUnit)]:
        yield pytest.param(shape, 1100, nrhs, diag,
                           id=f"{shape}-n1100-{nrhs}-{diag.name}")


NB_NARROW, WIDEST = 256, 300    # the tile, and the widest B of the cases
_VIEW = {"n": lambda x: x, "t": st.transpose, "c": st.conj_transpose}


@pytest.fixture(scope="module")
def once():
    """``once(key, build)``: ``build()`` the first time a test of this
    module asks for ``key``, its value after; dropped with the module."""
    made = {}

    def get(key, build):
        if key not in made:
            made[key] = build()
        return made[key]

    return get


def _dtype(op):
    return np.complex64 if op == "c" else np.float32


def _matrix(n, diag, dt):
    a = rand(n, n, dt, 40) * 0.3 + n * np.eye(n, dtype=dt)
    # unit: off-diagonal small beside the implied 1
    return a / n if diag == Diag.Unit else a


def _rhs(n, dt):
    """The widest B; a case's is the leading ``nrhs`` of its columns (a
    column of X depends on its own column of B alone)."""
    return rand(n, WIDEST, dt, 41)


def _operand(once, shape, n, uplo, diag, dt):
    """(grid, A): one A a (shape, n, uplo, diag, dtype)."""
    def build():
        import jax
        p, q = map(int, shape.split("x"))
        grid = st.Grid(p, q, devices=jax.devices()[:p * q])
        return grid, st.TriangularMatrix.from_dense(
            _matrix(n, diag, dt), nb=NB_NARROW, grid=grid, uplo=uplo,
            diag=diag)

    return once(("A", shape, n, uplo, diag, np.dtype(dt).name), build)


def _reference(once, n, op, uplo, diag):
    """numpy's complex128 solution for all ``WIDEST`` columns: no grid in
    it, so one LU of the n x n triangle a system (0.4 s at n = 1100)."""
    def build():
        dt = _dtype(op)
        t = tri(_matrix(n, diag, dt), uplo == Uplo.Lower, diag == Diag.Unit)
        opt = {"n": t, "t": t.T, "c": np.conj(t.T)}[op]
        return np.linalg.solve(opt.astype(np.complex128), 1.5 * _rhs(n, dt))

    return once(("ref", n, op, uplo, diag), build)


def _wide(once, shape, n, op, uplo, diag):
    """The dense X of the wide system: B's ``WIDEST`` columns
    zero-extended to two whole tile columns of real columns (nothing to
    crop, no tile column that holds all of X: on a grid the form that
    moves A), solved once a (shape, n, op, uplo, diag)."""
    def build():
        dt = _dtype(op)
        grid, A = _operand(once, shape, n, uplo, diag, dt)
        bw = np.zeros((n, 2 * NB_NARROW), dt)
        bw[:, :WIDEST] = _rhs(n, dt)
        Xw = st.trsm(Side.Left, 1.5, _VIEW[op](A), st.Matrix.from_dense(
            bw, nb=NB_NARROW, grid=grid))
        xw = np.asarray(Xw.to_dense())
        assert not xw[:, WIDEST:].any()
        return xw

    return once(("wide", shape, n, op, uplo, diag), build)


def check_narrow_b(once, shape, n, op, uplo, nrhs, diag):
    """8 right-hand sides in a 256-wide tile: the answer, the stored
    padding, and the same columns out of a B of two tile columns, which
    on a grid is the other form (A moves, not X)."""
    dt = _dtype(op)
    grid, A = _operand(once, shape, n, uplo, diag, dt)
    b = _rhs(n, dt)[:, :nrhs]
    X = st.trsm(Side.Left, 1.5, _VIEW[op](A),
                st.Matrix.from_dense(b, nb=NB_NARROW, grid=grid))
    x = np.asarray(X.to_dense())
    ref = _reference(once, n, op, uplo, diag)[:, :nrhs]
    assert np.abs(x - ref).max() <= 2e-6 * np.abs(ref).max()
    # the padding of X is stored as exact zeros, on every device column
    stored = padded_dense(X)
    assert stored.shape[1] >= nrhs and stored.shape[0] >= n
    assert not stored[:, nrhs:].any() and not stored[n:].any()
    np.testing.assert_array_equal(stored[:n, :nrhs], x)
    xw = _wide(once, shape, n, op, uplo, diag)[:, :nrhs]
    assert np.abs(x - xw).max() <= 1e-6 * np.abs(xw).max()


@pytest.mark.parametrize("uplo", [Uplo.Lower, Uplo.Upper])
@pytest.mark.parametrize("op", ["n", "t", "c"])
@pytest.mark.parametrize("shape,n,nrhs,diag", _narrow_b_cases(False))
def test_trsm_left_narrow_b(once, shape, n, op, uplo, nrhs, diag):
    check_narrow_b(once, shape, n, op, uplo, nrhs, diag)
