"""Two-stage SVD stage 1 (reference src/ge2tb.cc, gesvd.cc:77-102)."""

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import obs
from slate_tpu.obs import metrics
from slate_tpu.types import Op, Option, MethodSVD
from slate_tpu.linalg import ge2tb as g2
from slate_tpu.linalg.ge2tb import (ge2tb, ge2tb_gather, gesvd_two_stage,
                                    unmbr_ge2tb_u, unmbr_ge2tb_v)
from tests.conftest import rand


def band_of(Aout):
    """The gathered band as a dense n x n matrix."""
    ub = ge2tb_gather(Aout)
    nb, n = Aout.nb, Aout.n
    assert ub.shape == (nb + 1, n)
    return sum(np.diag(ub[d, :n - d], d) for d in range(nb + 1))


def rebuilt(Aout, Tq, Tl, band):
    """U·[B; 0]·Vᴴ through both back-transforms of stage 1."""
    m, n, nb, grid = Aout.m, Aout.n, Aout.nb, Aout.grid
    B = np.zeros((m, n), band.dtype)
    B[:n] = band
    UB = unmbr_ge2tb_u(Op.NoTrans, Aout, Tq,
                       st.Matrix.from_dense(B, nb=nb, grid=grid))
    X = st.Matrix.from_dense(np.conj(np.asarray(UB.to_dense()).T),
                             nb=nb, grid=grid)
    return np.conj(np.asarray(
        unmbr_ge2tb_v(Op.NoTrans, Aout, Tl, X).to_dense()).T)


def program_label():
    (span,) = [s for s in obs.captured_spans() if s["name"] == "ge2tb"]
    return span["labels"]["program"], span["labels"]["panel"]


# the exact-shape body: one chip, m and n whole tiles, m >= n
@pytest.mark.parametrize("m,n,nb,dt,tol", [
    (48, 32, 8, np.float64, 1e-13), (32, 32, 8, np.float64, 1e-13),
    (48, 32, 8, np.float32, 2e-5), (32, 32, 8, np.float32, 2e-5),
    (48, 32, 8, np.complex128, 1e-13), (8, 8, 8, np.float64, 1e-13),
    (96, 80, 8, np.float64, 1e-13), (512, 384, 128, np.float32, 2e-5)],
    ids=["tall-f64", "square-f64", "tall-f32", "square-f32",
         "tall-c128", "one-tile", "two-stages", "tall-f32-nb128"])
def test_exact_body_reduces_what_is_left(grid11, observed, m, n, nb, dt,
                                         tol):
    """On one chip at whole tiles ``_ge2tb_jit`` runs the exact-shape
    body: a band with A's singular values, Tq and Tl of the SPMD
    body's shapes, and both back-transforms on its output rebuild A
    (ten tile columns: a stage of eight steps on the whole matrix, one
    of one step on its window, the last QR panel alone)."""
    a = rand(m, n, dt, 1)
    A = st.Matrix.from_dense(a, nb=nb, grid=grid11)
    Aout, Tq, Tl = ge2tb(A)
    assert program_label() == ("exact", "xla")
    assert metrics.counter_value("ge2tb.path", program="exact") == 1
    nt = n // nb
    assert Tq.shape == (nt, nb, nb) and Tl.shape == (max(nt - 1, 1), nb, nb)
    assert Aout.data.shape == A.data.shape and Aout.dtype == A.dtype
    band = band_of(Aout)
    s_a = np.linalg.svd(a, compute_uv=False)
    assert np.abs(np.linalg.svd(band, compute_uv=False)
                  - s_a).max() < tol * s_a[0]
    assert np.abs(rebuilt(Aout, Tq, Tl, band) - a).max() < tol * s_a[0]


@pytest.mark.parametrize("shape,grid_name,program", [
    ((48, 32, 8), "grid11", "exact"), ((29, 21, 8), "grid11", "spmd"),
    ((40, 24, 8), "grid24", "spmd"), ((32, 48, 8), "grid11", "spmd")],
    ids=["whole-tiles", "ragged", "grid", "wide"])
def test_the_shape_picks_the_program(request, observed, shape,
                                     grid_name, program):
    """One chip at whole tiles with m >= n takes the exact-shape body;
    a ragged edge, a grid and m < n (before ``gesvd`` transposes) take
    the SPMD one. Read from the span's ``program`` label where
    ``ge2tb`` takes the operand, from the rule where it refuses it."""
    m, n, nb = shape
    A = st.Matrix.from_dense(rand(m, n, np.float64, 5), nb=nb,
                             grid=request.getfixturevalue(grid_name))
    assert g2._program(A) == program
    if m < n:
        return
    ge2tb(A)
    assert program_label() == (program, "xla")
    assert metrics.counter_value("ge2tb.path", program=program) == 1
    assert metrics.counter_total("ge2tb.path") == 1


# sha256 of _ge2tb_jit's lowered StableHLO text where the shape picks
# the SPMD body, as the commit before the exact-shape body (89dc2bf)
# lowers it; jax 0.9.0, x64 on as tests/conftest.py sets it
_SPMD_TEXT = {
    ("grid11", 29, 21, 8, "float64"):
        "cccfa1eee88475fd744782d3ff7224103599109365f80f43fc0c62ee926e41fe",
    ("grid24", 40, 24, 8, "float64"):
        "bb839b808a0119eaa4983352c9c92362a6b1e5bed0c47d5c61224efdd3770fed",
    ("grid22", 384, 256, 32, "float32"):
        "1258ccb18a9a9cf3997863f743ae424e0d83074eaa894ed6516da259d14775da",
    ("grid11", 32, 48, 8, "float64"):
        "d5c653739bc4f72177fd70cda521fd84c4dd46700e1b32e1deee4e995d5d3b7c",
}


@pytest.mark.parametrize("case", list(_SPMD_TEXT),
                         ids=["ragged", "grid24", "grid22-f32", "wide"])
def test_spmd_body_lowers_to_the_text_it_had(request, case):
    import hashlib
    import jax
    if jax.__version__ != "0.9.0":
        pytest.skip("the digests were taken with jax 0.9.0")
    grid_name, m, n, nb, dt = case
    A = st.random_matrix(m, n, nb, request.getfixturevalue(grid_name),
                         np.dtype(dt), seed=1)
    # a trace that an earlier call left (the same shape from
    # ``from_dense``) would hand its own spelling of the sharding back
    g2._ge2tb_jit._jit.clear_cache()
    text = g2._ge2tb_jit.lower(A, "bf16_6x").as_text()
    assert "module @jit__ge2tb_jit" in text
    assert hashlib.sha256(text.encode()).hexdigest() == _SPMD_TEXT[case]


@pytest.mark.parametrize("m,n,nb", [(32, 32, 8), (40, 24, 8), (29, 21, 8)])
@pytest.mark.parametrize("dt", [np.float64, np.complex128])
def test_ge2tb_band_similarity(grid24, m, n, nb, dt):
    """Band matrix has the same singular values; band structure holds."""
    a = rand(m, n, dt, 1)
    A = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    Aout, Tq, Tl = ge2tb(A)
    ub = ge2tb_gather(Aout)                 # compact [nb+1, n] storage
    assert ub.shape == (nb + 1, n)
    dense = np.zeros((n, n), ub.dtype)
    for d in range(nb + 1):
        idx = np.arange(n - d)
        dense[idx, idx + d] = ub[d, : n - d]
    s_band = np.linalg.svd(dense, compute_uv=False)
    s_a = np.linalg.svd(a, compute_uv=False)
    np.testing.assert_allclose(s_band[: min(m, n)], s_a, rtol=1e-9,
                               atol=1e-9)


@pytest.mark.parametrize("solver", ["host", "device"])
@pytest.mark.parametrize("dt", [np.float64, np.complex128])
def test_tb2bd_bidiagonal_solve(grid24, dt, solver):
    """tb2bd bulge chase + the bidiagonal solve reproduce the band's
    singular values and B itself: the host ``bdsqr`` and the device
    ``bdsdc`` (the same Golub-Kahan form through stedc's merges, Z on
    the grid) are cases of one test."""
    from slate_tpu.linalg.ge2tb import tb2bd
    from slate_tpu.linalg.bulge import bdsdc, bdsqr
    rng = np.random.default_rng(11)
    nb, n = 6, 37
    ub = rng.standard_normal((nb + 1, n)).astype(dt)
    if np.issubdtype(dt, np.complexfloating):
        ub = ub + 1j * rng.standard_normal((nb + 1, n))
    d, e, Vu, tauu, Vv, tauv, phase0 = tb2bd(ub)
    dense = np.zeros((n, n), ub.dtype)
    for dd in range(nb + 1):
        idx = np.arange(n - dd)
        dense[idx, idx + dd] = ub[dd, : n - dd]
    ref = np.linalg.svd(dense, compute_uv=False)
    np.testing.assert_allclose(bdsqr(d, e), ref, rtol=1e-10, atol=1e-10)
    if solver == "host":
        s, U, VT = bdsqr(d, e, want_uv=True)
    else:
        s, U, V = bdsdc(d, e, grid24, np.float64)
        U, VT = np.asarray(U), np.asarray(V).T
    np.testing.assert_allclose(s, ref, rtol=1e-10, atol=1e-10)
    B = np.diag(d) + np.diag(e, 1)
    np.testing.assert_allclose(U @ (np.diag(s) @ VT), B,
                               rtol=1e-9, atol=1e-9)
    np.testing.assert_allclose(U.T @ U, np.eye(n), atol=1e-9)
    np.testing.assert_allclose(VT @ VT.T, np.eye(n), atol=1e-9)


@pytest.mark.parametrize("dt", [np.float64, np.complex128])
def test_gesvd_two_stage_vectors(grid24, dt):
    m, n, nb = 40, 32, 8
    a = rand(m, n, dt, 2)
    A = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    s, U, VT = gesvd_two_stage(A, want_u=True, want_vt=True)
    np.testing.assert_allclose(s, np.linalg.svd(a, compute_uv=False),
                               rtol=1e-9, atol=1e-9)
    u = np.asarray(U.to_dense())
    vt = np.asarray(VT.to_dense())
    recon = (u * s) @ vt
    err = np.linalg.norm(recon - a) / np.linalg.norm(a)
    assert err < 1e-10
    orth_u = np.linalg.norm(np.conj(u.T) @ u - np.eye(u.shape[1]))
    orth_v = np.linalg.norm(vt @ np.conj(vt.T) - np.eye(vt.shape[0]))
    assert orth_u < 1e-10 and orth_v < 1e-10


def test_gesvd_dispatch(grid24):
    m, n, nb = 40, 32, 8
    a = rand(m, n, np.float64, 3)
    A = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    s_auto, _, _ = st.gesvd(A)                      # Auto → two-stage
    s_dense, _, _ = st.gesvd(A, opts={Option.MethodSVD: MethodSVD.Dense})
    ref = np.linalg.svd(a, compute_uv=False)
    np.testing.assert_allclose(s_auto, ref, rtol=1e-9, atol=1e-9)
    np.testing.assert_allclose(s_dense, ref, rtol=1e-9, atol=1e-9)


@pytest.mark.parametrize("dt", [np.float64, np.complex128])
def test_gesvd_wide_two_stage(grid24, dt):
    """m < n runs the two-stage pipeline on Aᴴ with U/VT swapped back
    (no silent dense fall-back for wide inputs)."""
    m, n, nb = 32, 48, 8
    a = rand(m, n, dt, 7)
    A = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    s, U, VT = st.gesvd(A, opts={Option.MethodSVD: MethodSVD.TwoStage},
                        want_u=True, want_vt=True)
    np.testing.assert_allclose(s, np.linalg.svd(a, compute_uv=False),
                               rtol=1e-9, atol=1e-9)
    u = np.asarray(U.to_dense())[:, :m]
    vt = np.asarray(VT.to_dense())[:m, :]
    recon = (u * s) @ vt
    err = np.linalg.norm(recon - a) / np.linalg.norm(a)
    assert err < 1e-10
    orth_u = np.linalg.norm(np.conj(u.T) @ u - np.eye(m))
    orth_v = np.linalg.norm(vt @ np.conj(vt.T) - np.eye(m))
    assert orth_u < 1e-10 and orth_v < 1e-10
