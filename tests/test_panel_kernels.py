"""slatetune kernel-suite tests: the explicit capability table, the
rung registry, and the interpret-mode parity suite — panel-PLU pivot
vectors bitwise against the XLA panel, trsm/rank-k against reference
solves at tier tolerance, plus routine-level proofs through st.getrf
/ st.potrf on the 8-device CPU mesh (interpret=True exercises the
same kernel code path the TPU rung compiles)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import slate_tpu as st
from slate_tpu.internal import pallas_kernels as pk
from slate_tpu.internal.precision import TIERS
from tests.conftest import rand, spd

def well_conditioned_lower(n, dtype=np.float64, seed=0, unit=False):
    """Random lower-triangular with bounded condition number —
    raw ``tril(randn)`` grows solve error exponentially in n."""
    l = np.tril(rand(n, n, dtype, seed)) / n + np.eye(n, dtype=dtype)
    if unit:
        np.fill_diagonal(l, 1.0)
    return l.astype(dtype)


# ---------------------------------------------------------------------------
# capability table (satellite: explicit dtype × nb × platform)
# ---------------------------------------------------------------------------

def test_capability_interpret_rows():
    # interpret (cpu/gpu) rows include the f64 parity suite's shapes
    assert pk.pallas_supported(128, jnp.float32, "cpu", "panel_plu")
    assert pk.pallas_supported(128, jnp.float64, "cpu", "panel_plu")
    assert pk.pallas_supported(256, jnp.float64, "cpu", "panel_plu")
    assert pk.pallas_supported(512, jnp.float64, "cpu", "trsm")
    assert pk.pallas_supported(64, jnp.float64, "cpu", "rank_k")


def test_capability_tpu_rows_are_narrower():
    # the TPU table only lists what Mosaic lowers: no f64 anywhere
    assert not pk.pallas_supported(128, jnp.float64, "tpu", "panel_plu")
    assert not pk.pallas_supported(128, jnp.float64, "tpu", "trsm")
    assert pk.pallas_supported(128, jnp.float32, "tpu", "panel_plu")
    assert pk.pallas_supported(256, jnp.bfloat16, "tpu", "trsm")
    assert pk.pallas_supported(126, jnp.float32, "tpu", "rank_k")


def test_capability_nb_range_and_multiple():
    # below lo, above hi, off-multiple all refused
    assert not pk.pallas_supported(64, jnp.float32, "cpu", "panel_plu")
    assert not pk.pallas_supported(384, jnp.float32, "cpu", "panel_plu")
    assert not pk.pallas_supported(129, jnp.float32, "cpu", "trsm")
    # rank_k is deliberately capped BELOW one lane tile
    assert not pk.pallas_supported(128, jnp.float32, "cpu", "rank_k")
    assert pk.pallas_supported(127, jnp.float32, "cpu", "rank_k")


def test_capability_unknown_axes_refuse():
    assert not pk.pallas_supported(128, jnp.float32, "cpu", "nope")
    assert not pk.pallas_supported(128, jnp.float32, "quantum", "tile")
    assert not pk.pallas_supported(128, jnp.complex64, "cpu", "trsm")


def test_capability_default_platform_is_backend():
    want = pk.pallas_supported(128, jnp.float32,
                               jax.default_backend(), "trsm")
    assert pk.pallas_supported(128, jnp.float32, kernel="trsm") == want


# ---------------------------------------------------------------------------
# rung registry
# ---------------------------------------------------------------------------

def test_rung_registry_default_and_set():
    assert pk.active_rung("trsm") == "xla"
    pk.set_rung("trsm", "pallas")
    try:
        assert pk.rung_enabled("trsm")
    finally:
        pk.set_rung("trsm", None)
    assert pk.active_rung("trsm") == "xla"


def test_rung_env_force(monkeypatch):
    monkeypatch.setenv("SLATE_PALLAS_RANKK", "1")
    assert pk.active_rung("rank_k") == "pallas"
    monkeypatch.setenv("SLATE_PALLAS_RANKK", "0")
    assert pk.active_rung("rank_k") == "xla"


def test_forced_rung_restores_on_exit():
    assert pk.active_rung("panel_plu") == "xla"
    with pk.forced_rung("panel_plu"):
        assert pk.rung_enabled("panel_plu")
    assert pk.active_rung("panel_plu") == "xla"


def test_vmem_gates_refuse_oversize_panels():
    # a 45k-row panel cannot promise the 40 MiB ceiling
    assert pk.panel_plu_vmem_applies(256, 128)
    assert not pk.panel_plu_vmem_applies(45056, 128)
    assert pk.trsm_vmem_applies(128, 1024)
    assert not pk.trsm_vmem_applies(2048, 8192)


# ---------------------------------------------------------------------------
# panel-PLU parity: pivots bitwise vs the XLA panel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("h,w,dt", [(256, 128, np.float32),
                                    (384, 128, np.float64),
                                    (256, 256, np.float32)])
def test_panel_plu_pivots_bitwise_vs_xla(h, w, dt):
    a = jnp.asarray(rand(h, w, dt, seed=3))
    lu, piv, info = pk.panel_plu_pallas(a, interpret=True)
    lu_ref, piv_ref, _ = lax.linalg.lu(a)
    assert int(info) == 0
    # the acceptance criterion: ipiv identical, element for element
    assert np.array_equal(np.asarray(piv), np.asarray(piv_ref))
    tol = 1e-4 if dt == np.float32 else 1e-11
    scale = np.linalg.norm(np.asarray(lu_ref))
    assert np.linalg.norm(np.asarray(lu) - np.asarray(lu_ref)) \
        <= tol * scale


def test_panel_plu_reconstructs_pa_equals_lu():
    h, w = 256, 128
    a = rand(h, w, np.float64, seed=5)
    lu, piv, info = pk.panel_plu_pallas(jnp.asarray(a), interpret=True)
    lu = np.asarray(lu)
    perm = np.arange(h)
    for j, pv in enumerate(np.asarray(piv)):
        perm[[j, pv]] = perm[[pv, j]]
    l = np.tril(lu, -1)[:, :w] + np.eye(h, w)
    u = np.triu(lu[:w])
    err = np.linalg.norm(a[perm] - l @ u) / np.linalg.norm(a)
    assert err < 1e-13
    assert np.abs(np.tril(lu, -1)).max() <= 1.0 + 1e-12  # pivot bound


def test_panel_plu_zero_column_counts_info():
    a = rand(256, 128, np.float64, seed=7)
    a[:, 0] = 0.0
    _, _, info = pk.panel_plu_pallas(jnp.asarray(a), interpret=True)
    assert int(info) >= 1


# ---------------------------------------------------------------------------
# trsm parity (tier tolerance, well-conditioned operands)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dt,tol", [(np.float32, 1e-5),
                                    (np.float64, 1e-12)])
@pytest.mark.parametrize("unit", [False, True])
def test_trsm_left_lower_parity(dt, tol, unit):
    n, m = 256, 384
    l = well_conditioned_lower(n, dt, seed=1, unit=unit)
    b = rand(n, m, dt, seed=2)
    x = np.asarray(pk.trsm_left_lower_pallas(
        jnp.asarray(l), jnp.asarray(b), unit=unit, interpret=True))
    lr = np.tril(l, -1) + np.eye(n) if unit else l
    ref = np.linalg.solve(lr.astype(np.float64), b.astype(np.float64))
    rel = np.linalg.norm(x - ref) / np.linalg.norm(ref)
    assert rel < tol, rel


@pytest.mark.parametrize("dt,tol", [(np.float32, 1e-5),
                                    (np.float64, 1e-12)])
def test_trsm_right_lower_t_parity(dt, tol):
    n, m = 256, 192
    l = well_conditioned_lower(n, dt, seed=4)
    b = rand(m, n, dt, seed=5)
    x = np.asarray(pk.trsm_right_lower_t_pallas(
        jnp.asarray(l), jnp.asarray(b), interpret=True))
    # X·Lᵀ = B  ⇔  X = solve(L, Bᵀ)ᵀ
    ref = np.linalg.solve(l.astype(np.float64),
                          b.astype(np.float64).T).T
    rel = np.linalg.norm(x - ref) / np.linalg.norm(ref)
    assert rel < tol, rel


# ---------------------------------------------------------------------------
# rank-k tail parity across the three precision tiers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tier", TIERS)
@pytest.mark.parametrize("dt,tol", [(np.float32, 1e-5),
                                    (np.float64, 1e-13)])
def test_rank_k_tail_parity(tier, dt, tol):
    m, n, k = 64, 192, 48
    c = rand(m, n, dt, seed=1)
    a = rand(m, k, dt, seed=2)
    b = rand(k, n, dt, seed=3)
    out = np.asarray(pk.rank_k_tail_pallas(
        jnp.asarray(c), jnp.asarray(a), jnp.asarray(b),
        alpha=-1.0, beta=1.0, tier=tier, interpret=True))
    ref = c.astype(np.float64) - a.astype(np.float64) @ \
        b.astype(np.float64)
    rel = np.linalg.norm(out - ref) / np.linalg.norm(ref)
    assert rel < tol, (tier, rel)


def test_rank_k_tail_scalars():
    m, n, k = 32, 96, 16
    c = rand(m, n, np.float64, seed=6)
    a = rand(m, k, np.float64, seed=7)
    b = rand(k, n, np.float64, seed=8)
    out = np.asarray(pk.rank_k_tail_pallas(
        jnp.asarray(c), jnp.asarray(a), jnp.asarray(b),
        alpha=0.5, beta=-2.0, interpret=True))
    np.testing.assert_allclose(out, 0.5 * (a @ b) - 2.0 * c,
                               rtol=1e-12, atol=1e-12)


# ---------------------------------------------------------------------------
# routine-level: forced rungs through the drivers on the 8-device mesh
# ---------------------------------------------------------------------------

def test_getrf_panel_plu_rung_pivots_bitwise(grid24):
    n, nb = 256, 128
    a = rand(n, n, np.float64, seed=9)
    A = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    LU0, piv0, info0 = st.getrf(A)
    lu0 = np.asarray(LU0.to_dense())
    with pk.forced_rung("panel_plu"):
        A1 = st.Matrix.from_dense(a, nb=nb, grid=grid24)
        LU1, piv1, info1 = st.getrf(A1)
        lu1 = np.asarray(LU1.to_dense())
    assert int(info0) == int(info1) == 0
    assert np.array_equal(np.asarray(piv0), np.asarray(piv1))
    err = np.linalg.norm(lu1 - lu0) / np.linalg.norm(lu0)
    assert err < 1e-10, err


def test_potrf_trsm_rung_matches_default(grid24):
    n, nb = 256, 128
    a = spd(n, np.float64, seed=10)
    A = st.HermitianMatrix.from_dense(a, nb=nb, grid=grid24)
    L0, info0 = st.potrf(A)
    l0 = np.asarray(L0.to_dense())
    with pk.forced_rung("trsm"):
        A1 = st.HermitianMatrix.from_dense(a, nb=nb, grid=grid24)
        L1, info1 = st.potrf(A1)
        l1 = np.asarray(L1.to_dense())
    assert int(info0) == int(info1) == 0
    err = np.linalg.norm(l1 - l0) / np.linalg.norm(l0)
    assert err < 1e-10, err


def test_getrf_rank_k_rung_backward_error(grid24):
    # an off-multiple size leaves a sub-nb remainder → the rank_k tail
    n, nb = 200, 64
    a = rand(n, n, np.float64, seed=11)
    with pk.forced_rung("rank_k"):
        A = st.Matrix.from_dense(a, nb=nb, grid=grid24)
        LU, piv, info = st.getrf(A)
        lu = np.asarray(LU.to_dense())
    assert int(info) == 0
    perm = np.arange(n)
    for j, pv in enumerate(np.asarray(piv).reshape(-1)[:n]):
        if pv < n:
            perm[[j, pv]] = perm[[pv, j]]
    l = np.tril(lu, -1) + np.eye(n)
    u = np.triu(lu)
    err = np.linalg.norm(a[perm] - l @ u) / (n * np.linalg.norm(a))
    assert err < 1e-13, err
