"""gemm + fast residual methodology (reference test/test_gemm.cc —
probabilistic residual check :192-212 plus direct comparison)."""

import numpy as np
import pytest

import slate_tpu as st
from tests.conftest import padded_dense, rand


@pytest.mark.parametrize("m,n,k,nb", [(32, 32, 32, 8), (24, 40, 16, 8),
                                      (17, 23, 11, 4), (8, 8, 8, 8)])
def test_gemm_nn(grid24, m, n, k, nb):
    a, b = rand(m, k, seed=1), rand(k, n, seed=2)
    c = rand(m, n, seed=3)
    A = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    B = st.Matrix.from_dense(b, nb=nb, grid=grid24)
    C = st.Matrix.from_dense(c, nb=nb, grid=grid24)
    C2 = st.gemm(2.0, A, B, -0.5, C)
    ref = 2.0 * a @ b - 0.5 * c
    np.testing.assert_allclose(np.asarray(C2.to_dense()), ref, rtol=1e-12,
                               atol=1e-12)


@pytest.mark.parametrize("opA,opB", [("n", "t"), ("t", "n"), ("t", "t"),
                                     ("c", "n"), ("n", "c")])
def test_gemm_ops(grid24, opA, opB):
    m, n, k, nb = 24, 16, 32, 8
    dt = np.complex128 if "c" in (opA, opB) else np.float64
    a = rand(*( (m, k) if opA == "n" else (k, m) ), dtype=dt, seed=1)
    b = rand(*( (k, n) if opB == "n" else (n, k) ), dtype=dt, seed=2)
    c = rand(m, n, dtype=dt, seed=3)

    def apply(x, op):
        return {"n": x, "t": x.T, "c": x.conj().T}[op]

    A = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    B = st.Matrix.from_dense(b, nb=nb, grid=grid24)
    C = st.Matrix.from_dense(c, nb=nb, grid=grid24)
    opAf = {"n": lambda x: x, "t": st.transpose, "c": st.conj_transpose}
    C2 = st.gemm(1.0, opAf[opA](A), opAf[opB](B), 1.0, C)
    ref = apply(a, opA) @ apply(b, opB) + c
    np.testing.assert_allclose(np.asarray(C2.to_dense()), ref, rtol=1e-12,
                               atol=1e-12)


def test_gemm_fast_residual(grid24):
    """Probabilistic residual: ‖(C_slate − αAB − βC)·x‖ small for
    random x (reference test_gemm.cc:192-212)."""
    m = n = k = 40
    nb = 8
    a, b, c = rand(m, k, seed=4), rand(k, n, seed=5), rand(m, n, seed=6)
    A = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    B = st.Matrix.from_dense(b, nb=nb, grid=grid24)
    C = st.Matrix.from_dense(c, nb=nb, grid=grid24)
    C2 = st.gemm(1.5, A, B, 0.5, C)
    x = rand(n, 1, seed=7)
    lhs = np.asarray(C2.to_dense()) @ x
    rhs = 1.5 * (a @ (b @ x)) + 0.5 * (c @ x)
    err = np.linalg.norm(lhs - rhs) / (
        np.linalg.norm(a) * np.linalg.norm(b) + np.linalg.norm(c))
    assert err < 1e-12


def test_gemm_single_device(grid11):
    a, b = rand(16, 16, seed=1), rand(16, 16, seed=2)
    A = st.Matrix.from_dense(a, nb=8, grid=grid11)
    B = st.Matrix.from_dense(b, nb=8, grid=grid11)
    C = st.Matrix.zeros(16, 16, 8, grid11, dtype=np.float64)
    C2 = st.gemm(1.0, A, B, 0.0, C)
    np.testing.assert_allclose(np.asarray(C2.to_dense()), a @ b,
                               rtol=1e-12, atol=1e-12)


def test_gemm_bf16_accumulates_f32(grid22):
    import jax.numpy as jnp
    a, b = rand(64, 64, np.float32, 1), rand(64, 64, np.float32, 2)
    A = st.Matrix.from_dense(a, nb=16, grid=grid22).astype(jnp.bfloat16)
    B = st.Matrix.from_dense(b, nb=16, grid=grid22).astype(jnp.bfloat16)
    C = st.Matrix.zeros(64, 64, 16, grid22, dtype=jnp.bfloat16)
    C2 = st.gemm(1.0, A, B, 0.0, C)
    ref = a @ b
    got = np.asarray(C2.to_dense()).astype(np.float32)
    # bf16 inputs, f32 accumulation: relative error ~1e-2
    assert np.abs(got - ref).max() / np.abs(ref).max() < 5e-2


# -- a B narrower than its storage is multiplied at its own width ------------
# nb=8 never crops (the carried width is whole lanes of 128, capped at the
# stored ntl*nb): these run the narrow shapes at nb=256.

@pytest.mark.parametrize("dt", [np.float32, np.complex64],
                         ids=["f32", "c64"])
@pytest.mark.parametrize("op", ["n", "c"])
@pytest.mark.parametrize("beta", [0.0, 0.5])
@pytest.mark.parametrize("n", [1, 8, 130, 256, 257, 1024])
@pytest.mark.parametrize("shape", ["1x1", "2x2", "2x4"])
def test_gemm_narrow_b(shape, n, beta, op, dt):
    """One column in a 256-wide tile (``mixed.matvec``: beta = 0 into a
    zeroed C; ``mixed._residual``: a filled C), eight, one over a lane
    boundary, a whole tile, one column over it, and a B that fills its
    storage on every grid: the product, and the stored padding of the
    result, which is exact zeros."""
    import jax
    import jax.numpy as jnp
    p, q = map(int, shape.split("x"))
    grid = st.Grid(p, q, devices=jax.devices()[:p * q])
    m, k, nb = 520, 300, 256
    a = rand(*((m, k) if op == "n" else (k, m)), dt, 50)
    b = rand(k, n, dt, 51)
    c = rand(m, n, dt, 52) if beta else np.zeros((m, n), dt)
    A = st.Matrix.from_dense(a, nb=nb, grid=grid)
    if op == "c":
        A = st.conj_transpose(A)
        a = np.conj(a.T)
    out = st.gemm(-1.5, A, st.Matrix.from_dense(b, nb=nb, grid=grid), beta,
                  st.Matrix.from_dense(c, nb=nb, grid=grid))
    ref = np.asarray(-1.5 * jnp.matmul(a, b, precision="highest") + beta * c)
    got = np.asarray(out.to_dense())
    assert got.dtype == dt
    assert np.abs(got - ref).max() <= 1e-5 * np.abs(ref).max()
    stored = padded_dense(out)
    assert stored.shape[0] >= m and stored.shape[1] >= n
    assert not stored[:, n:].any() and not stored[m:].any()
    np.testing.assert_array_equal(stored[:m, :n], got)


def _lower_gemm(grid, n, nb=256, m=520, k=300):
    import jax.numpy as jnp
    from slate_tpu.ops import blas
    A = st.Matrix.from_dense(rand(m, k, np.float32, 53), nb=nb, grid=grid)
    B = st.Matrix.from_dense(rand(k, n, np.float32, 54), nb=nb, grid=grid)
    C = st.Matrix.from_dense(rand(m, n, np.float32, 55), nb=nb, grid=grid)
    return blas._gemm_jit.lower(jnp.float32(2.0), A, B, jnp.float32(0.5), C,
                                tier="bf16_6x")


# sha256 of _gemm_jit's lowered StableHLO text for a B that fills its
# storage, taken from the parent of PR 40 (8c997dc) with jax 0.9.0
_WIDE_GEMM_TEXT = {
    "1x1": "07c4c4a17d13a527be0f13a04a359d18"
           "0a6ccb699139c375508e33debdbecd4a",
    "2x2": "da88191465779116124442b1d5b6eade"
           "0ea1ad184aef235bb7efe84410c7c8f2",
}


@pytest.mark.parametrize("shape", list(_WIDE_GEMM_TEXT))
def test_gemm_full_width_program_is_the_one_it_was(grid11, grid22, shape):
    """A B of whole tile columns on every device column: nothing to
    cut, and the program is the parent's, text for text."""
    import hashlib
    import jax
    if jax.__version__ != "0.9.0":
        pytest.skip("the digests were taken with jax 0.9.0")
    grid = {"1x1": grid11, "2x2": grid22}[shape]
    text = _lower_gemm(grid, 512).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == _WIDE_GEMM_TEXT[shape]


@pytest.mark.parametrize("n,nb", [(1, 256), (130, 256), (8, 1024)])
@pytest.mark.parametrize("shape", ["1x1", "2x2"])
def test_gemm_narrow_b_pays_for_its_lanes(grid11, grid22, shape, n, nb):
    """A one-column B costs 128 lanes of the tile's nb: the compiled
    program's flops follow the carried width (a half at nb=256, an
    eighth at nb=1024; 130 columns carry 256, which is the whole tile,
    and cost what nb columns cost)."""
    from slate_tpu.ops import blas
    grid = {"1x1": grid11, "2x2": grid22}[shape]
    m = k = 2 * nb + 40
    narrow = _lower_gemm(grid, n, nb, m, k).compile().cost_analysis()
    full = _lower_gemm(grid, nb, nb, m, k).compile().cost_analysis()
    share = blas._carried_cols(n, nb, grid.q, 1) / nb
    assert share * 0.98 <= narrow["flops"] / full["flops"] <= share * 1.06
