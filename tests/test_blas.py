"""Level-3 BLAS beyond gemm: herk/syrk/her2k/syr2k, symm/hemm, trmm,
trsm (all sides/uplos/ops), band ops (reference test/test_{herk,symm,
trmm,trsm,...}.cc analogs). The forms of ``trsm(Side.Left)`` (op in
place, narrow B, which operand moves) are in
tests/test_blas_trsm_left.py and tests/test_blas_trsm_narrow_b*.py."""

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu.types import Side, Uplo, Diag
from tests.conftest import rand, tri


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


def test_right_side_native_no_transpose(grid24, materialized_ops):
    """tbsm/hbmm/unmqr Side.Right must run natively (reference
    src/tbsm.cc, src/hbmm.cc, src/unmqr.cc right-side task graphs) —
    no op-view materializes (each would cost two all-to-alls)."""
    from slate_tpu.types import Op
    from slate_tpu.linalg.geqrf import geqrf, unmqr
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

    assert materialized_ops == [], materialized_ops
