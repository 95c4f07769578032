"""Divide & conquer tridiagonal eigensolver (reference src/stedc.cc +
stedc_{sort,deflate,secular,solve,merge,z_vector}.cc)."""

import numpy as np
import pytest
from scipy.linalg import eigh_tridiagonal

import slate_tpu as st
from slate_tpu import obs
from slate_tpu.linalg import stedc as dc
from slate_tpu.linalg.stedc import stedc, _merge_spec, _assemble_g
from slate_tpu.obs import metrics


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


# ------------------- the device path, a level of the tree at a time

@pytest.fixture(params=["1x1", "2x2"])
def grid(request, grid11, grid22):
    return grid11 if request.param == "1x1" else grid22


@pytest.fixture
def counted():
    """What ``stedc`` has counted since the test began (a call of the
    fixture's value), the counters on for the test."""
    was = obs.metrics_enabled()
    obs.metrics_on()
    before = {name: metrics.counter_total(name) for name in dc.COUNTERS}
    yield lambda: {name: metrics.counter_total(name) - before[name]
                   for name in dc.COUNTERS}
    if not was:
        obs.metrics_off()


def tree(d, e, nmin):
    """``_tear``'s leaves and levels of (d, e)."""
    leaves, levels = [], []
    dc._tear(d.copy(), e, 0, len(d), nmin, leaves, levels)
    return leaves, levels


@pytest.mark.parametrize("n, nmin", [(8192, 48), (256, 16), (391, 16),
                                     (1000, 48), (97, 48), (150, 16)])
def test_levels_of_the_tree(n, nmin):
    """What a batch stands on: a level's merges are disjoint, ascending
    and a row apart at most in width; the children of a level's merges
    are leaves or the merges of the next level."""
    leaves, levels = tree(np.ones(n), np.ones(n - 1), nmin)
    assert sum(map(len, levels)) == len(leaves) - 1
    if n == 8192:
        assert [len(lv) for lv in levels] == [2 ** i for i in range(8)]
        assert [lv[0][2] - lv[0][0] for lv in levels] == [
            8192 >> i for i in range(8)]
    done = set(leaves)
    for level in reversed(levels):
        widths = [hi - lo for lo, _, hi in level]
        assert max(widths) - min(widths) <= 1
        assert all(a[2] <= b[0] for a, b in zip(level, level[1:]))
        for lo, mid, hi in level:
            assert (lo, mid) in done and (mid, hi) in done
        done |= {(lo, hi) for lo, _, hi in level}
    assert (0, n) in done


@pytest.mark.parametrize("nmin", [1, 5, 16, 48])
def test_a_widened_merge_stays_inside_n(nmin):
    """``_zrows_jit`` and ``_merge_jit`` slice every merge at the
    level's widest k: no slice may be clamped at Z's edge, whatever n."""
    for n in range(nmin + 1, 700):
        for level in tree(np.ones(n), np.ones(n - 1), nmin)[1]:
            k = max(hi - lo for lo, _, hi in level)
            assert level[-1][2] - level[-1][0] == k, (n, level)
            assert all(lo + k <= n for lo, _, _ in level), (n, level)


def tridiagonal(kind, n, nmin, seed=11):
    rng = np.random.default_rng(seed)
    d, e = rng.standard_normal(n), rng.standard_normal(n - 1)
    if kind == "rho0":              # an exact zero at a split, mid-level
        e[tree(d, e, nmin)[1][2][1][1] - 1] = 0.0
    elif kind == "clustered":
        d = np.repeat(np.arange(8.0), n // 8)
        e = 1e-8 * rng.standard_normal(n - 1)
    elif kind == "graded":
        d = 10.0 ** (-10 * np.arange(n) / n)
        e = 0.3 * np.sqrt(d[:-1] * d[1:])
    return d, e


def deflations(monkeypatch, order, run):
    """The poles each merge deflated, by (lo, hi): ``_deflate``'s calls
    of ``run`` in turn, which come in ``order``."""
    seen = []
    plain = dc._deflate

    def counted(D, *args, **kwargs):
        spec = plain(D, *args, **kwargs)
        seen.append((len(D), int(spec.fidx.size)))
        return spec

    with monkeypatch.context() as patched:
        patched.setattr(dc, "_deflate", counted)
        out = run()
    assert [k for k, _ in seen] == [hi - lo for lo, _, hi in order]
    return out, {(lo, hi): gone for (lo, _, hi), (_, gone)
                 in zip(order, seen)}


@pytest.mark.parametrize("kind, n, nmin", [
    ("random", 256, 16), ("random", 391, 16), ("random", 1000, 48),
    ("random", 97, 48), ("rho0", 256, 16), ("rho0", 391, 16),
    ("clustered", 256, 16), ("graded", 320, 24)])
def test_device_levels_against_host_merge_by_merge(grid, monkeypatch,
                                                   counted, kind, n, nmin):
    """The level-batched device path and the host's depth-first one
    walk the same tree: the same deflations merge by merge, and (in
    float64, the tests' precision) the same decomposition."""
    d, e = tridiagonal(kind, n, nmin)
    leaves, levels = tree(d, e, nmin)
    solved = [m for lv in levels for m in lv if e[m[1] - 1] != 0.0]
    assert len(solved) == len(leaves) - 1 - (kind == "rho0")
    # the host: children before their parent, left before right
    depth_first = sorted(solved, key=lambda t: (t[2], -t[0]))
    by_level = [m for lv in reversed(levels) for m in lv if m in solved]
    (lam_h, Z_h), host = deflations(
        monkeypatch, depth_first,
        lambda: stedc(d.copy(), e.copy(), nmin=nmin))
    (lam, Z), device = deflations(
        monkeypatch, by_level,
        lambda: stedc(d.copy(), e.copy(), grid=grid, nmin=nmin))
    assert device == host
    if kind in ("clustered", "rho0"):
        assert sum(host.values()) > 0
    assert counted() == {
        "stedc.levels": len(levels), "stedc.merges": len(solved),
        "stedc.poles": sum(hi - lo for lo, _, hi in solved),
        "stedc.deflated": sum(host.values())}
    assert Z.shape == (n, n)
    scale = max(1.0, np.abs(lam_h).max())
    assert np.abs(lam - lam_h).max() <= 1e-13 * scale
    _check(d, e, lam, np.asarray(Z))


@pytest.mark.parametrize("n", [1, 16])
def test_device_at_or_under_nmin_is_one_leaf(grid, counted, n):
    rng = np.random.default_rng(n)
    d, e = rng.standard_normal(n), rng.standard_normal(n - 1)
    lam, Z = stedc(d.copy(), e.copy(), grid=grid, nmin=16)
    assert counted()["stedc.levels"] == 0
    _check(d, e, lam, np.asarray(Z))


def test_one_merge_is_one_level(grid11, counted):
    """m = 1 at the top is the program m = 128 is at the bottom."""
    rng = np.random.default_rng(2)
    n = 31                          # 15 + 16: one merge, ragged
    d, e = rng.standard_normal(n), rng.standard_normal(n - 1)
    lam, Z = stedc(d.copy(), e.copy(), grid=grid11, nmin=16)
    assert (counted()["stedc.levels"], counted()["stedc.merges"]) == (1, 1)
    _check(d, e, lam, np.asarray(Z))
