"""The VMEM-resident Pallas bulge chaser of tb2bd
(internal/band_wave_vmem_bd.py, the SVD's stage 2) against the numpy
twin (internal/band_bulge.py), in interpret mode on the CPU test mesh.
No benchmark cell runs the compiled kernel on a chip yet (ROADMAP R7)."""

import numpy as np
import pytest

from slate_tpu.internal import band_bulge
from slate_tpu.internal.band_wave_vmem import vmem_applies
from slate_tpu.internal.band_wave_vmem_bd import (tb2bd_wave_vmem,
                                                  vmem_applies_bd)
from tests.test_band_wave import _rand_uband


@pytest.mark.parametrize("n,band", [(50, 8), (70, 8), (100, 16)])
def test_tb2bd_vmem_matches_numpy_twin(n, band):
    ub = _rand_uband(n, band, np.float32, seed=n + band)
    d0, e0, Vu0, tu0, Vv0, tv0, ph0 = band_bulge.tb2bd(ub.copy())
    d1, e1, Vu1, tu1, Vv1, tv1, ph1 = tb2bd_wave_vmem(ub.copy(),
                                                      interpret=True)
    tol = 5e-3
    assert np.allclose(d0, d1, atol=tol, rtol=tol)
    assert np.allclose(e0, e1, atol=tol, rtol=tol)
    assert abs(ph0 - ph1) < tol
    # near-trivial reflectors (|tail| ~ f32 eps) sit on a knife edge:
    # the twins' different summation order can legitimately disagree
    # on trivial (tau=0) vs near-parallel (tau=2) — exclude them from
    # the element-wise check (measured: one such task at (70, 8))
    for V0, t0, V1, t1 in ((Vu0, tu0, Vu1, tu1), (Vv0, tv0, Vv1, tv1)):
        knife = np.abs(V0[..., 1:]).max(axis=-1) < 1e-5
        okm = knife | np.isclose(t0, t1, atol=tol, rtol=tol)
        assert okm.all()
        vok = knife[..., None] | np.isclose(V0, V1, atol=tol, rtol=tol)
        assert vok.all()


def test_tb2bd_vmem_frames_path_matches_twin():
    """FRAMES path of the bidiagonal twin (incl. the c0Sr = 0 seed
    shortcut) vs the numpy reference at band 128, at the smallest
    order whose sweeps have a seed task, an interior chase and a last
    one (as tests/test_band_wave_vmem.py's ``frames_run``)."""
    n, band = 260, 128
    assert band_bulge.max_chase(n, band) == 3
    ub = _rand_uband(n, band, np.float32, seed=37)
    d0, e0, Vu0, tu0, Vv0, tv0, ph0 = band_bulge.tb2bd(ub.copy())
    d1, e1, Vu1, tu1, Vv1, tv1, ph1 = tb2bd_wave_vmem(ub.copy(),
                                                      interpret=True)
    tol = 5e-3
    assert np.allclose(d0, d1, atol=tol, rtol=tol)
    assert np.allclose(e0, e1, atol=tol, rtol=tol)
    # No element-wise V/tau assert at this depth: f32 drift over 259
    # b=128 sweeps legitimately diverges individual reflectors — the
    # shipped XLA wave shows the SAME divergences vs the numpy twin
    # (measured: tau 1.85 vs 1.70 at (s=41, t=2)) while all three
    # implementations agree spectrally to ~1.5e-6. A frame-indexing
    # bug would corrupt d/e wholesale (caught above) and the spectrum
    # (pinned below); V/tau self-consistency is covered by the e2e
    # heev/gesvd dispatch tests.
    assert Vu1.shape == Vu0.shape and Vv1.shape == Vv0.shape
    B = np.diag(d1.astype(np.float64)) + np.diag(e1.astype(np.float64),
                                                 1)
    sv = np.linalg.svd(B, compute_uv=False)
    dense = np.zeros((n, n))
    for dd in range(band + 1):
        idx = np.arange(n - dd)
        dense[idx, idx + dd] = ub[dd, : n - dd]
    ref = np.linalg.svd(dense, compute_uv=False)
    assert np.allclose(np.sort(sv), np.sort(ref),
                       atol=2e-3 * max(1, ref.max()))


def test_tb2bd_vmem_singular_values_match_dense():
    n, band = 80, 8
    ub = _rand_uband(n, band, np.float32, seed=11)
    d, e, *_ = tb2bd_wave_vmem(ub, interpret=True)
    B = np.diag(d.astype(np.float64)) + np.diag(e.astype(np.float64), 1)
    sv = np.linalg.svd(B, compute_uv=False)
    dense = np.zeros((n, n))
    for dd in range(band + 1):
        idx = np.arange(n - dd)
        dense[idx, idx + dd] = ub[dd, : n - dd]
    ref = np.linalg.svd(dense, compute_uv=False)
    assert np.allclose(np.sort(sv), np.sort(ref),
                       atol=2e-3 * max(1, ref.max()))


def test_tb2bd_vmem_fallback():
    # unsupported band (not pow2) falls back to the XLA wave
    ub = _rand_uband(40, 3, np.float64, seed=2)
    out0 = band_bulge.tb2bd(ub.copy())
    out1 = tb2bd_wave_vmem(ub.copy())
    for a, b in zip(out0[:2], out1[:2]):
        assert np.allclose(a, b, atol=1e-11)


def test_tb2bd_dispatch_vmem(monkeypatch):
    """SLATE_TB2BD=vmem routes tb2bd through the VMEM chaser
    (interpret off-TPU) and matches the numpy twin's bidiagonal."""
    from slate_tpu.linalg.ge2tb import tb2bd
    monkeypatch.setenv("SLATE_TB2BD", "vmem")
    n, band = 50, 8
    ub = _rand_uband(n, band, np.float32, seed=13)
    d0, e0, *_ = band_bulge.tb2bd(ub.copy())
    d1, e1, *_ = tb2bd(ub.copy())
    tol = 5e-3
    assert np.allclose(d0, d1, atol=tol, rtol=tol)
    assert np.allclose(e0, e1, atol=tol, rtol=tol)


def test_bd_footprint_accounts_output_windows():
    """The bd chaser's resident set carries four per-step output
    windows (two PP×b V packs + two 8×TAUP tau packs, double-
    buffered) on top of the eig twin's model; sharing the eig gate
    undercounted right at the 96 MB boundary (ADVICE r5, low). Pin
    the band-256 boundary: the eig gate holds to n = 8601 but the
    bd budget runs out at n = 8577."""
    assert vmem_applies(8601, 256, np.float32)
    assert not vmem_applies(8602, 256, np.float32)
    assert vmem_applies_bd(8577, 256, np.float32)
    assert not vmem_applies_bd(8578, 256, np.float32)
    # the differential window: eig fits, bd must not
    assert vmem_applies(8601, 256, np.float32)
    assert not vmem_applies_bd(8601, 256, np.float32)
    # bd never accepts what the eig gate rejects
    for n in (2042, 8602, 200_000):
        assert not vmem_applies_bd(n, 256, np.float32) or \
            vmem_applies(n, 256, np.float32)
