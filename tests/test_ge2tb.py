"""Two-stage SVD stage 1 (reference src/ge2tb.cc, gesvd.cc:77-102)."""

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu.types import Op, Option, MethodSVD
from slate_tpu.linalg.ge2tb import (ge2tb, ge2tb_gather, gesvd_two_stage,
                                    unmbr_ge2tb_u)
from tests.conftest import rand


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
