"""Cholesky tier-2 tests (reference test/test_potrf.cc / test_posv.cc:
backward error ‖A − L·Lᴴ‖/(n‖A‖) style checks)."""

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu.types import Uplo
from tests.conftest import rand, spd


@pytest.mark.parametrize("n,nb", [(32, 8), (29, 8), (16, 16), (40, 4)])
@pytest.mark.parametrize("dt", [np.float64, np.complex128])
def test_potrf_lower(grid24, n, nb, dt):
    a = spd(n, dt, seed=1)
    A = st.HermitianMatrix.from_dense(a, nb=nb, grid=grid24)
    L, info = st.potrf(A)
    assert int(info) == 0
    l = np.tril(np.asarray(L.to_dense()))
    err = np.linalg.norm(a - l @ np.conj(l.T)) / (n * np.linalg.norm(a))
    assert err < 1e-14


def test_potrf_upper(grid24):
    n = 24
    a = spd(n, np.float64, seed=2)
    A = st.HermitianMatrix.from_dense(a, nb=8, grid=grid24,
                                      uplo=Uplo.Upper)
    U, info = st.potrf(A)
    assert int(info) == 0
    u = np.triu(np.asarray(U.to_dense()))
    err = np.linalg.norm(a - u.T @ u) / (n * np.linalg.norm(a))
    assert err < 1e-14


def test_potrf_not_spd(grid24):
    n = 16
    a = -np.eye(n)
    A = st.HermitianMatrix.from_dense(a, nb=8, grid=grid24)
    L, info = st.potrf(A)
    assert int(info) > 0


@pytest.mark.parametrize("dt", [np.float64, np.complex128])
def test_posv(grid24, dt):
    n, nrhs = 24, 5
    a = spd(n, dt, seed=3)
    b = rand(n, nrhs, dt, 4)
    A = st.HermitianMatrix.from_dense(a, nb=8, grid=grid24)
    B = st.Matrix.from_dense(b, nb=8, grid=grid24)
    X, L, info = st.posv(A, B)
    assert int(info) == 0
    res = np.linalg.norm(a @ np.asarray(X.to_dense()) - b) \
        / np.linalg.norm(b)
    assert res < 1e-12


def test_potri(grid24):
    n = 16
    a = spd(n, np.float64, seed=5)
    A = st.HermitianMatrix.from_dense(a, nb=8, grid=grid24)
    L, info = st.potrf(A)
    Ainv = st.potri(L)
    got = np.asarray(Ainv.to_dense())
    ref = np.linalg.inv(a)
    # potri returns the full inverse via Linv^H Linv
    np.testing.assert_allclose(got, ref, rtol=1e-8, atol=1e-8)


def test_pbsv(grid24):
    n, kd = 24, 3
    a = spd(n, np.float64, seed=6)
    band = np.zeros_like(a)
    for i in range(n):
        for j in range(n):
            if abs(i - j) <= kd:
                band[i, j] = a[i, j]
    band += 2 * n * np.eye(n)  # keep SPD after truncation
    B = rand(n, 2, seed=7)
    Ab = st.HermitianBandMatrix.from_dense(band, nb=8, grid=grid24,
                                           kl=kd, ku=kd)
    Bm = st.Matrix.from_dense(B, nb=8, grid=grid24)
    X, L, info = st.pbsv(Ab, Bm)
    assert int(info) == 0
    res = np.linalg.norm(band @ np.asarray(X.to_dense()) - B) \
        / np.linalg.norm(B)
    assert res < 1e-10


def test_potrf_random_spd_generator(grid24):
    A = st.random_spd(40, nb=8, grid=grid24, dtype=np.float64)
    a = np.asarray(A.to_dense())
    a = np.tril(a) + np.tril(a, -1).T
    L, info = st.potrf(A)
    assert int(info) == 0
    l = np.tril(np.asarray(L.to_dense()))
    err = np.linalg.norm(a - l @ l.T) / (40 * np.linalg.norm(a))
    assert err < 1e-13


def test_potrf_ignores_junk_half(grid24):
    """Only the significant uplo half may be read (regression)."""
    n = 24
    a = spd(n, np.float64, seed=30)
    junk = np.triu(np.full((n, n), np.nan), 1)
    lower_with_junk = np.tril(a) + junk
    A = st.HermitianMatrix.from_dense(lower_with_junk, nb=8, grid=grid24)
    L, info = st.potrf(A)
    assert int(info) == 0
    l = np.tril(np.asarray(L.to_dense()))
    err = np.linalg.norm(a - l @ l.T) / (n * np.linalg.norm(a))
    assert err < 1e-13


def test_potrf_chunked_spmd_path(grid24):
    # nt=12 >= 2*lcm(2,4): exercises the chunked super-step programs
    n, nb = 90, 8
    a = spd(n, np.float64, seed=17)
    A = st.HermitianMatrix.from_dense(np.tril(a), nb=nb, grid=grid24)
    L, info = st.potrf(A)
    assert int(info) == 0
    l = np.tril(np.asarray(L.to_dense()))
    np.testing.assert_allclose(l @ l.T, a, rtol=1e-10, atol=1e-9)


# -- the one-chip body on the stored tiles (_potrf_dense_1dev) --------------

# n a multiple of nb and ragged; nt = 1 (no panel), nt = 2 (one panel,
# a one-tile trailing window), nt = 3..7 (the triangle-aware split:
# rectangles and diagonal windows of one and of several tiles); f32,
# complex64 and bf16 storage (panels factored in f32); upper storage.
TILE_BODY = [
    (16, 16, np.float32, Uplo.Lower), (13, 16, np.float32, Uplo.Lower),
    (32, 16, np.float32, Uplo.Lower), (27, 16, np.float32, Uplo.Lower),
    (96, 32, np.float32, Uplo.Lower), (112, 16, np.float32, Uplo.Lower),
    (107, 16, np.float32, Uplo.Lower), (96, 32, np.complex64, Uplo.Lower),
    (75, 16, np.complex64, Uplo.Lower), (96, 32, "bfloat16", Uplo.Lower),
    (107, 16, "bfloat16", Uplo.Lower), (96, 32, np.float32, Uplo.Upper),
    (75, 16, np.complex64, Uplo.Upper), (107, 16, np.float64, Uplo.Upper),
]


@pytest.mark.parametrize(
    "n,nb,dt,uplo", TILE_BODY,
    ids=[f"{n}-{nb}-{np.dtype(dt).name if dt != 'bfloat16' else dt}-"
         f"{uplo.name}" for n, nb, dt, uplo in TILE_BODY])
def test_potrf_tile_body_against_numpy(grid11, n, nb, dt, uplo):
    import jax.numpy as jnp
    bf16 = dt == "bfloat16"
    wide = np.complex128 if dt == np.complex64 else np.float64
    a = spd(n, np.float32 if bf16 else dt, seed=n + nb)
    half = np.tril(a) if uplo == Uplo.Lower else np.triu(a)
    A = st.HermitianMatrix.from_dense(
        jnp.asarray(half, jnp.bfloat16) if bf16 else half, nb=nb,
        grid=grid11, uplo=uplo)
    F, info = st.potrf(A)
    assert int(info) == 0
    assert F.data.dtype == A.data.dtype and F.data.shape == A.data.shape
    f = np.asarray(F.to_dense().astype(jnp.float32) if bf16
                   else F.to_dense()).astype(wide)
    ref = np.linalg.cholesky(a.astype(wide))
    got = np.tril(f) if uplo == Uplo.Lower else np.conj(np.triu(f).T)
    tol = 3e-2 if bf16 else 1e-12 if dt == np.float64 else 2e-5
    assert np.linalg.norm(got - ref) / np.linalg.norm(ref) < tol
    if n % nb and uplo == Uplo.Lower:
        # the padding of the last diagonal tile keeps its identity and
        # nothing else of the padding is written
        last = np.asarray(F.data[0, 0, -1, -1].astype(jnp.float32)
                          if bf16 else F.data[0, 0, -1, -1])
        r = n % nb
        np.testing.assert_array_equal(last[r:, r:], np.eye(nb - r))
        np.testing.assert_array_equal(last[r:, :r], 0)


@pytest.mark.parametrize("n,nb,bad,block", [
    (64, 16, 0, 1), (64, 16, 37, 3), (61, 16, 60, 4), (16, 16, 5, 1)])
def test_potrf_tile_body_reports_the_first_bad_block_column(
        grid11, n, nb, bad, block):
    a = spd(n, np.float32, seed=bad)
    a[bad, bad] = -50.0
    A = st.HermitianMatrix.from_dense(a, nb=nb, grid=grid11)
    L, info = st.potrf(A)
    assert int(info) == block       # 1-based, as the SPMD body reports it
    assert np.isfinite(np.asarray(L.to_dense())).all()


@pytest.mark.parametrize("case", ["tiles", "tiles_upper", "spmd_one_program",
                                  "spmd_chunked"])
def test_potrf_names_the_body_that_factored(request, observed, case):
    """``potrf.chunk`` of the one-program launch carries ``form``, and
    ``potrf.path{form}`` is counted once a factorization (the twin of
    ``getrf.path{phase}``)."""
    from slate_tpu import obs
    from slate_tpu.obs import metrics
    form = case.split("_")[0]
    grid = request.getfixturevalue("grid11" if form == "tiles" else "grid24")
    n = 90 if case == "spmd_chunked" else 40
    a = spd(n, np.float64, seed=3)
    upper = case == "tiles_upper"
    A = st.HermitianMatrix.from_dense(
        np.triu(a) if upper else np.tril(a), nb=8, grid=grid,
        uplo=Uplo.Upper if upper else Uplo.Lower)
    obs.reset()
    calls = 2
    for _ in range(calls):
        _, info = st.potrf(A)
        assert int(info) == 0
    assert metrics.counter_value("potrf.path", form=form) == calls
    assert metrics.counter_total("potrf.path") == calls
    chunks = [s for s in obs.captured_spans() if s["name"] == "potrf.chunk"]
    one = [s for s in chunks if s["labels"].get("phase") == "one_program"]
    if case == "spmd_chunked":
        assert chunks and not one
    else:
        assert [s["labels"]["form"] for s in one] == [form] * calls


@pytest.mark.parametrize("n,nb", [(48, 16), (43, 16)])
def test_potrf_overwrite_a(n, nb):
    """overwrite_a=True (donated buffer) gives identical results; on
    CPU donation is advisory but the API path must work end to end."""
    import jax
    g1 = st.Grid(1, 1, devices=[jax.devices()[0]])
    a = spd(n, np.float64, seed=21)
    A1 = st.HermitianMatrix.from_dense(np.tril(a), nb=nb, grid=g1)
    A2 = st.HermitianMatrix.from_dense(np.tril(a), nb=nb, grid=g1)
    L1, i1 = st.potrf(A1)
    L2, i2 = st.potrf(A2, overwrite_a=True)
    assert int(i1) == int(i2) == 0
    np.testing.assert_array_equal(np.asarray(L1.to_dense()),
                                  np.asarray(L2.to_dense()))


def test_getrf_overwrite_a():
    import jax
    g1 = st.Grid(1, 1, devices=[jax.devices()[0]])
    n, nb = 40, 8
    a = np.asarray(rand(n, n, np.float64, 22)) + n * np.eye(n)
    A1 = st.Matrix.from_dense(a, nb=nb, grid=g1)
    A2 = st.Matrix.from_dense(a, nb=nb, grid=g1)
    LU1, p1, i1 = st.getrf(A1)
    LU2, p2, i2 = st.getrf(A2, overwrite_a=True)
    assert int(i1) == int(i2) == 0
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    np.testing.assert_array_equal(np.asarray(LU1.to_dense()),
                                  np.asarray(LU2.to_dense()))


def test_potrf_lookahead_drives_chunking(grid24, monkeypatch):
    """Option.Lookahead/ChunkSize control the super-step granularity
    (reference Option::Lookahead, src/potrf.cc:88-107)."""
    from slate_tpu.types import Option
    from slate_tpu.linalg import potrf as potrf_mod
    n, nb = 130, 4                    # nt=33 ≥ 2·lcm(2,4)=8
    a = spd(n, np.float64, seed=18)

    counts = {}
    # the driver picks the sequential chunk body by default and the
    # pipelined one at Option.PipelineDepth ≥ 1 — count invocations
    # of all four so the assertion is depth-agnostic
    for name in ("_potrf_chunk_jit", "_potrf_chunk_jit_overwrite",
                 "_potrf_pipe_chunk_jit",
                 "_potrf_pipe_chunk_jit_overwrite"):
        orig = getattr(potrf_mod, name)

        def counting(*args, __orig=orig, **kw):
            counts["n"] = counts.get("n", 0) + 1
            return __orig(*args, **kw)

        monkeypatch.setattr(potrf_mod, name, counting)
    results = {}
    for label, opts in [
            ("default", None),
            ("la4", {Option.Lookahead: 4}),
            ("chunk16", {Option.ChunkSize: 16})]:
        counts["n"] = 0
        A = st.HermitianMatrix.from_dense(np.tril(a), nb=nb, grid=grid24)
        L, info = st.potrf(A, opts)
        assert int(info) == 0
        l = np.tril(np.asarray(L.to_dense()))
        np.testing.assert_allclose(l @ l.T, a, rtol=1e-10, atol=1e-9)
        results[label] = counts["n"]
    # default la=1 → ~8 chunks; la=4 → ~2 chunks; explicit 16-col
    # chunks (lcm-rounded) → ceil(33/16)=3
    assert results["default"] > results["la4"]
    assert results["la4"] == 2
    assert results["chunk16"] == 3


def test_potrf_dense_inplace(grid24):
    """64k-class dense in-place entry (potrf_dense_inplace): no tiled
    container, donated buffer, peak memory ~ the array itself. Must
    match the tiled potrf's numerics; bf16 storage factors its panels
    in f32."""
    import jax.numpy as jnp
    import numpy as np
    import slate_tpu as st
    rng = np.random.default_rng(61)
    n, nb = 192, 32
    g = rng.standard_normal((n, n)).astype(np.float32)
    a = (g @ g.T / n + 3 * np.eye(n)).astype(np.float32)
    L, info = st.potrf_dense_inplace(jnp.asarray(a), nb=nb)
    assert int(info) == 0
    l = np.tril(np.asarray(L))
    err = np.linalg.norm(l @ l.T - a) / np.linalg.norm(a)
    assert err < 1e-5
    # bf16 storage
    Lb, infob = st.potrf_dense_inplace(jnp.asarray(a, jnp.bfloat16),
                                       nb=nb)
    assert int(infob) == 0
    lb = np.tril(np.asarray(Lb, dtype=np.float32))
    errb = np.linalg.norm(lb @ lb.T - a) / np.linalg.norm(a)
    assert errb < 0.05            # bf16 storage-precision bound
