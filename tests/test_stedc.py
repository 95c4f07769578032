"""Divide & conquer tridiagonal eigensolver (reference src/stedc.cc +
stedc_{sort,deflate,secular,solve,merge,z_vector}.cc)."""

import numpy as np
import pytest
from scipy.linalg import eigh_tridiagonal

import slate_tpu as st
from slate_tpu.linalg.stedc import stedc, _merge_spec, _assemble_g


def _check(d, e, lam, Z, tol=1e-12):
    n = len(d)
    T = np.diag(d) + np.diag(e, 1) + np.diag(e, -1)
    ref = eigh_tridiagonal(d, e, eigvals_only=True)
    scale = max(1.0, np.abs(ref).max())
    assert np.abs(lam - ref).max() / scale < tol
    Z = np.asarray(Z)
    assert np.abs(T @ Z - Z * lam[None, :]).max() / scale < tol
    assert np.abs(Z.T @ Z - np.eye(n)).max() < tol


@pytest.mark.parametrize("n", [7, 50, 130, 257])
def test_stedc_host_random(n):
    rng = np.random.default_rng(n)
    d = rng.standard_normal(n)
    e = rng.standard_normal(n - 1)
    lam, Z = stedc(d.copy(), e.copy(), nmin=16)
    _check(d, e, lam, Z)


def test_stedc_deflation_heavy():
    """Clustered spectrum + glued Wilkinson → heavy deflation paths."""
    rng = np.random.default_rng(0)
    d = np.repeat(np.arange(8.0), 16)
    e = rng.standard_normal(127) * 1e-8
    lam, Z = stedc(d.copy(), e.copy(), nmin=16)
    _check(d, e, lam, Z)
    w = np.abs(np.arange(-10, 11)).astype(float)
    d = np.concatenate([w] * 4)
    e = np.ones(len(d) - 1)
    e[20::21] = 1e-10
    lam, Z = stedc(d.copy(), e.copy(), nmin=16)
    _check(d, e, lam, Z)


def test_stedc_rho_zero():
    d = np.arange(10.0)[::-1].copy()
    e = np.zeros(9)
    lam, Z = stedc(d.copy(), e.copy(), nmin=4)
    _check(d, e, lam, Z)


def test_merge_rank_one_direct():
    """Merge factor G diagonalizes diag(D) + rho·z·zᵀ exactly."""
    rng = np.random.default_rng(3)
    k = 80
    D = np.sort(rng.standard_normal(k))
    D[10] = D[9] + 1e-13          # near-tie → Givens deflation
    z = rng.standard_normal(k)
    z[5] = 1e-18                   # small-z deflation
    rho = 0.7
    A = np.diag(D) + rho * np.outer(z, z)
    spec = _merge_spec(D, z, rho)
    G = _assemble_g(spec, k)
    assert np.abs(G.T @ G - np.eye(k)).max() < 1e-13
    assert np.abs(G.T @ A @ G - np.diag(spec.vals)).max() < 1e-12
    assert np.abs(spec.vals - np.linalg.eigvalsh(A)).max() < 1e-12


def test_stedc_device_grid(grid24):
    """Device-accumulated Z (row-sharded) matches the host path."""
    rng = np.random.default_rng(9)
    n = 150
    d = rng.standard_normal(n)
    e = rng.standard_normal(n - 1)
    lam, Z = stedc(d.copy(), e.copy(), grid=grid24, nmin=16)
    _check(d, e, lam, np.asarray(Z))


def test_heev_two_stage_dc(grid24):
    """Full heev pipeline with the D&C tridiagonal stage."""
    from slate_tpu.types import Option, MethodEig
    rng = np.random.default_rng(4)
    n, nb = 140, 16
    a = rng.standard_normal((n, n))
    a = (a + a.T) / 2
    A = st.HermitianMatrix.from_dense(a, nb=nb, grid=grid24)
    lam, Z = st.heev(A, opts={Option.MethodEig: MethodEig.TwoStage})
    np.testing.assert_allclose(lam, np.linalg.eigvalsh(a), rtol=1e-9,
                               atol=1e-9)
    z = np.asarray(Z.to_dense())
    assert np.linalg.norm(a @ z - z * lam[None, :]) / np.linalg.norm(a) \
        < 1e-10
    assert np.abs(z.T @ z - np.eye(n)).max() < 1e-11
