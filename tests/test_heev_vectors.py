"""``slate.heev`` with vectors through the two-stage pipeline (PR 41):
the public call at ``MethodEig.DC`` against a textbook reference kept
here (float64 ``numpy.linalg.eigh``: residual, orthogonality, values),
the device secular solve against the host one merge by merge, what a
call reports (the span tree, the blocking reads, the counters, a
demoted ``hb2st`` rung), and the principle of the benchmark's control:
a band reduction whose trailing products run at ``bf16_3x`` reads worse
by the residual than one at f32 (on the CPU every tier is true f32, so
the lower tier is ``benchmarks/harness/plain_eig.py``'s numpy one).
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import obs
from slate_tpu.linalg import bulge, eig, stedc
from slate_tpu.obs import metrics
from slate_tpu.robust import ladder
from slate_tpu.types import MethodEig, Option, Uplo
from benchmarks.harness import plain_eig

EPS32 = 2.0 ** -24
N, NB, BAND = 384, 64, 32       # the band forced under the tile


@pytest.fixture(params=["1x1", "2x2"])
def grid(request, grid11, grid22):
    return grid11 if request.param == "1x1" else grid22


def dc(tier="bf16_6x"):
    return {Option.MethodEig: MethodEig.DC, Option.EigBand: BAND,
            Option.TrailingPrecision: tier}


def symmetric(kind, seed, n=N):
    rng = np.random.default_rng(seed)
    g = rng.standard_normal((n, n))
    if kind == "spd":               # random_spd's class, made here
        a = g @ g.T / n + np.eye(n)
    else:                           # indefinite: both signs, a zero near
        a = (g + g.T) / np.sqrt(2 * n)
    return a.astype(np.float32)


def numbers_in_eps(a, lam, z):
    """The benchmark's four numbers by the textbook, in float64."""
    a, z = np.asarray(a, np.float64), np.asarray(z, np.float64)
    lam = np.asarray(lam, np.float64)
    ref = np.linalg.eigh(a)[0]
    r = a @ z - z * lam
    fro = np.linalg.norm(a)
    return {"residual_max": np.linalg.norm(r, axis=0).max() / fro / EPS32,
            "residual_fro": np.linalg.norm(r) / (fro * np.linalg.norm(z))
            / EPS32,
            "orth_fro": np.linalg.norm(z.T @ z - np.eye(len(lam)))
            / np.sqrt(len(lam)) / EPS32,
            "values_max": np.abs(lam - ref).max() / np.abs(ref).max()
            / EPS32}


# ----------------------------------------- against the plain reference

@pytest.mark.parametrize("kind", ["spd", "indefinite"])
@pytest.mark.parametrize("uplo", [Uplo.Lower, Uplo.Upper])
def test_heev_dc_against_numpy(grid, uplo, kind):
    a = symmetric(kind, 17)
    half = np.tril(a) if uplo == Uplo.Lower else np.triu(a)
    A = st.HermitianMatrix.from_dense(half, nb=NB, grid=grid, uplo=uplo)
    lam, Z = st.heev(A, dc())
    assert lam.dtype == np.float32 and lam.shape == (N,)
    assert (Z.m, Z.n, Z.nb) == (N, N, BAND)
    assert np.all(np.diff(lam) >= 0)
    got = numbers_in_eps(a, lam, np.asarray(Z.to_dense()))
    # f32 end to end: a few units each; the orthogonality carries the
    # n/band block reflectors of the two back-transforms
    assert got["residual_max"] < 40 and got["residual_fro"] < 10, got
    assert got["orth_fro"] < 120 and got["values_max"] < 40, got
    # the benchmark's own reference reads the same numbers
    plain = plain_eig.equations(jnp.asarray(a), lam,
                                jnp.asarray(Z.to_dense()), block=128)
    for name in ("residual_max", "residual_fro", "orth_fro"):
        assert plain[name] / EPS32 == pytest.approx(got[name], rel=0.05,
                                                    abs=0.5)


def test_heev_dc_values_only_and_dense_agree(grid11):
    a = symmetric("indefinite", 23, n=192)
    A = st.HermitianMatrix.from_dense(np.tril(a), nb=32, grid=grid11)
    lam, Z = st.heev(A, {Option.MethodEig: MethodEig.DC})
    vals, none = st.heev(A, {Option.MethodEig: MethodEig.DC},
                         want_vectors=False)
    dense, Zd = st.heev(A, {Option.MethodEig: MethodEig.Dense})
    assert none is None and Zd is not None
    scale = np.abs(dense).max()
    assert np.abs(lam - dense).max() <= 40 * EPS32 * scale
    assert np.abs(vals - dense).max() <= 40 * EPS32 * scale


# ------------------------------- the device secular solve, by the merge

def merge_case(kind, k=160, seed=5):
    rng = np.random.default_rng(seed)
    if kind == "random":
        D = np.sort(rng.standard_normal(k))
    elif kind == "clustered":       # tight clusters: Givens deflations
        D = np.sort(np.repeat(np.arange(8.0), k // 8)
                    + 1e-13 * rng.standard_normal(k))
    else:                           # graded over twelve decades
        D = np.sort(10.0 ** (-12 * rng.random(k)))
    z = rng.standard_normal(k)
    z[::11] *= 1e-18                # small weights: deflated outright
    z /= np.linalg.norm(z) / np.sqrt(2)
    return D, z, 0.7


def device_merge(D, z, rho, dtype, eps):
    """``_stedc_device``'s merge steps on one (D, z, rho), a level of
    one merge: the spec the host closes from the device's roots, and
    the device's ẑ."""
    spec = stedc._deflate(D, z, rho, eps)
    k, k1 = len(D), spec.uidx.size
    poles = np.zeros((1, 3, k), dtype)
    poles[0, 0, :k1], poles[0, 1, :k1] = stedc._split(spec.dd, dtype)
    poles[0, 2, :k1] = spec.zz
    base, off, zhat = (x[0] for x in stedc._secular_jit(
        poles, np.full(1, rho, dtype), np.full(1, k1, np.int32),
        iters=int(np.finfo(dtype).nmant) + 12))
    assert np.all(np.asarray(off[k1:]) == 0)
    assert np.all(np.asarray(zhat[k1:]) == 0)
    stedc._close(spec, np.asarray(base[:k1], int),
                 np.asarray(off[:k1], np.float64))
    return spec, np.asarray(zhat[:k1], np.float64)


@pytest.mark.parametrize("kind", ["random", "clustered", "graded"])
@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_device_secular_against_host(kind, dtype):
    D, z, rho = merge_case(kind)
    host = stedc._merge_spec(D, z, rho)
    # the same tolerance: the same deflations, the same poles
    dev, zhat = device_merge(D, z, rho, dtype, stedc._EPS)
    assert dev.fidx.size == host.fidx.size > 0
    assert np.array_equal(dev.uidx, host.uidx)
    assert len(dev.rots) == len(host.rots)
    if kind == "clustered":
        assert len(host.rots) > 0
    eps = float(np.finfo(dtype).eps)
    # a root is a pole plus an offset; the offset is what is solved
    # for, and it is right to a few units of its own size (2e3 eps
    # where the secular function is flat: a graded or clustered
    # spectrum's largest gaps)
    lam_h = host.dd[host.base] + host.off
    lam_d = dev.dd[dev.base] + dev.off
    gap = np.diff(np.append(host.dd, host.dd[-1] + rho * 2))
    assert np.abs(lam_d - lam_h).max() <= 2e3 * eps * gap.max()
    same = dev.base == host.base    # a root at mid-gap may pick either end
    assert same.mean() > 0.95
    assert np.allclose(dev.off[same], host.off[same], rtol=2e3 * eps,
                       atol=1e-300)
    assert np.allclose(zhat, host.zhat, rtol=2e3 * eps, atol=1e-300)
    assert np.all(np.diff(dev.vals) >= 0)
    assert np.abs(dev.vals - host.vals).max() <= 2e3 * eps * gap.max()


@pytest.mark.parametrize("kind", ["clustered", "graded"])
def test_device_stedc_f32_on_hard_spectra(grid, kind):
    n = 300
    rng = np.random.default_rng(8)
    if kind == "clustered":
        d = np.repeat(np.arange(6.0), n // 6) + 1e-6 * rng.standard_normal(n)
        e = 1e-4 * rng.standard_normal(n - 1)
    else:
        d = 10.0 ** (-6 * np.arange(n) / n)
        e = 0.3 * np.sqrt(d[:-1] * d[1:])
    d, e = (x.astype(np.float32).astype(np.float64) for x in (d, e))
    lam, Z = stedc.stedc(d, e, grid=grid, dtype=np.float32, nmin=24)
    assert Z.dtype == jnp.float32
    T = np.diag(d) + np.diag(e, 1) + np.diag(e, -1)
    got = numbers_in_eps(T, lam, np.asarray(Z))
    # the working precision is Z's: every number a few units of 2^-24
    assert got["orth_fro"] < 20 and got["values_max"] < 20, got
    assert got["residual_max"] < 100, got
    assert np.all(np.diff(lam) >= 0)


# ------------------------------ the blocked back-transform, by the sweep

def sweep_by_sweep(V, tau, Z, band, forward, conj_tau):
    """The packed family one reflector at a time: what
    ``bulge._apply_bulge_jit`` has to equal."""
    S, T = tau.shape
    n = Z.shape[0]
    Zp = np.zeros((S + T * band + 1, Z.shape[1]), Z.dtype)
    Zp[:n] = Z
    taus = np.conj(tau) if conj_tau else tau
    for i in range(S):
        s = i if forward else S - 1 - i
        for t in range(T):
            r = s + 1 + t * band
            w = np.conj(V[s, t]) @ Zp[r:r + band]
            Zp[r:r + band] -= taus[s, t] * np.outer(V[s, t], w)
    return Zp[:n]


@pytest.mark.parametrize("forward", [False, True])
@pytest.mark.parametrize("conj_tau", [False, True])
@pytest.mark.parametrize("dtype, n, band", [
    (np.float64, 70, 8), (np.complex128, 61, 12), (np.float32, 130, 16)])
def test_blocked_reflectors_equal_the_sweeps(dtype, n, band, conj_tau,
                                             forward):
    """Any u and any tau, not only a chase's: the order the blocks
    apply in is the sweeps' order wherever two reflectors meet."""
    rng = np.random.default_rng(n)
    S, T, m = n - 1, -(-(n - 1) // band), 9
    V = rng.standard_normal((S, T, band)).astype(dtype)
    tau = rng.standard_normal((S, T)).astype(dtype)
    if np.issubdtype(dtype, np.complexfloating):
        V = V + 1j * rng.standard_normal(V.shape)
        tau = tau + 1j * rng.standard_normal(tau.shape)
    for s in range(S):              # the format: nothing past row n - 1
        for t in range(T):
            live = max(0, min(band, n - (s + 1 + t * band)))
            V[s, t, live:] = 0
            if live == 0:
                tau[s, t] = 0
    # of size one, so that n·T factors neither grow nor shrink the rows
    V /= np.maximum(np.linalg.norm(V, axis=2, keepdims=True), 1e-30)
    tau[3, 1] = 0                   # a dead reflector among live ones
    Z = rng.standard_normal((n, m)).astype(dtype)
    want = sweep_by_sweep(V, tau, Z, band, forward, conj_tau)
    got = np.asarray(bulge._apply_bulge_jit(
        jnp.asarray(V), jnp.asarray(tau), jnp.asarray(Z), band, forward,
        conj_tau))
    tol = 1e-4 if dtype == np.float32 else 1e-11
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


# --------------------------------------------------- what a call reports

def run_observed(grid, opts=None, kind="spd"):
    a = symmetric(kind, 29)
    A = st.HermitianMatrix.from_dense(np.tril(a), nb=NB, grid=grid)
    lam, Z = st.heev(A, opts or dc())
    return a, lam, Z


def test_span_tree_of_a_two_stage_call(grid, observed):
    run_observed(grid)
    spans = obs.captured_spans()
    (root,) = [s for s in spans if s["parent"] == 0
               and s["name"] == "slate.heev"]
    assert root["name"] == eig.SPANS[0]
    labels = root["labels"]
    assert {k: labels[k] for k in ("routine", "n", "nb", "grid", "jobz",
                                   "method", "path")} == {
        "routine": "heev", "n": N, "nb": NB,
        "grid": f"{grid.p}x{grid.q}", "jobz": "V", "method": "DC",
        "path": "two_stage"}
    # at its end: what the pipeline chose
    assert labels["band"] == BAND
    assert labels["chase_backend"] == ladder.hb2st_ladder().last_rung
    children = [s["name"] for s in sorted(
        (s for s in spans if s["parent"] == root["id"]
         and s["name"].startswith("heev.")), key=lambda s: s["start_ns"])]
    assert children == list(eig.SPANS[1:7])
    # every blocking read of the path is a named sync=1 span
    sites = {}
    for s in spans:
        if s["labels"].get("sync") == 1:
            sites[s["name"]] = sites.get(s["name"], 0) + 1
    # N = 384 at nmin 48: 8 leaves of 48, 7 merges in 3 levels, and no
    # split of a random matrix has an off-diagonal of exactly zero
    levels = int(metrics.counter_total("stedc.levels"))
    assert levels == 3
    assert int(metrics.counter_total("stedc.merges")) == 8 - 1
    assert sites.pop("band.gather") == 1
    assert sites.pop("stedc.zrow") == sites.pop("stedc.roots") == levels
    for site in ("stedc.zrow", "stedc.roots"):
        assert [(s["labels"]["level"], s["labels"]["k"], s["labels"]["m"])
                for s in spans if s["name"] == site] == [
            (2, 96, 4), (1, 192, 2), (0, 384, 1)]
    assert set(sites) <= {"hb2st.tridiagonal"}, sites
    (tridiag,) = [s for s in spans if s["name"] == "heev.tridiag"]
    inside = [s for s in spans if s["name"].startswith("stedc.")]
    assert all(tridiag["start_ns"] <= s["start_ns"]
               and s["end_ns"] <= tridiag["end_ns"] for s in inside)
    # the counters
    assert set(eig.COUNTERS) >= set(stedc.COUNTERS)
    assert metrics.counter_value("heev.path", path="two_stage") == 1
    rung = labels["chase_backend"]
    assert metrics.counter_value("hb2st.backend", rung=rung) == 1
    assert metrics.counter_total("hb2st.demotion") == 0
    # a shear form is the VMEM chaser's alone
    (chase,) = [s for s in spans if s["name"] == "hb2st"]
    assert ("shear" in chase["labels"]) == (rung == "vmem")
    assert metrics.counter_total("hb2st.shear") == (rung == "vmem")
    poles = metrics.counter_total("stedc.poles")
    assert 0 <= metrics.counter_total("stedc.deflated") < poles
    assert poles >= N               # the top merge alone has n poles


def test_span_tree_of_a_dense_call(grid11, observed):
    a, lam, Z = run_observed(grid11, {Option.MethodEig: MethodEig.Dense})
    (root,) = [s for s in obs.captured_spans() if s["parent"] == 0]
    assert root["name"] == "slate.heev"
    assert (root["labels"]["path"], root["labels"]["method"]) == (
        "dense", "Dense")
    assert "band" not in root["labels"]
    names = [s["name"] for s in obs.captured_spans()
             if s["parent"] == root["id"]]
    assert "heev.dense" in names and "heev.values" in names
    assert metrics.counter_value("heev.path", path="dense") == 1
    assert numbers_in_eps(a, lam, np.asarray(Z.to_dense()))[
        "residual_max"] < 40


def test_a_demoted_rung_is_counted(grid11, observed, monkeypatch):
    lad = ladder.hb2st_ladder()
    first = lad.select(np.zeros((BAND + 1, N), np.float32))
    i = lad._names.index(first)
    below = lad._names[i + 1]

    def raises(band):
        raise RuntimeError("made to raise")

    rungs = list(lad.rungs)
    rungs[i] = dataclasses.replace(rungs[i], run=raises)
    monkeypatch.setattr(lad, "rungs", rungs)
    before = len(ladder.demotion_log())
    a, lam, Z = run_observed(grid11)
    # the answer is still right: that is why it has to be counted
    assert numbers_in_eps(a, lam, np.asarray(Z.to_dense()))[
        "residual_max"] < 40
    assert metrics.counter_value("hb2st.demotion", to=below,
                                 **{"from": first}) == 1
    assert metrics.counter_value("hb2st.backend", rung=below) == 1
    assert metrics.counter_value("hb2st.backend", rung=first) == 0
    (root,) = [s for s in obs.captured_spans()
               if s["name"] == "slate.heev"]
    assert root["labels"]["chase_backend"] == below
    logged = ladder.demotion_log()[before:]
    assert [(d.from_rung, d.to_rung) for d in logged] == [(first, below)]


@pytest.mark.parametrize("band, form", [(8, "ladder"),
                                        (128, "single_pass")])
def test_the_vmem_chasers_shear_form_is_counted(observed, monkeypatch,
                                                band, form):
    """``hb2st.shear{form}`` once a call of the VMEM chaser and the same
    word on the ``hb2st`` span: the form is read off the band (the
    kernel itself runs at band 8 only; at 128 its program is stood in
    for, tests/test_band_wave.py runs it)."""
    from slate_tpu.internal import band_bulge, band_wave_vmem
    from slate_tpu.linalg.he2hb import hb2st
    # off the chip the rung's probe says no: made to say yes (the
    # kernel then runs in interpret mode)
    lad = ladder.hb2st_ladder()
    rungs = list(lad.rungs)
    i = lad._names.index("vmem")
    rungs[i] = dataclasses.replace(rungs[i], probe=lambda band: True)
    monkeypatch.setattr(lad, "rungs", rungs)
    monkeypatch.setenv("SLATE_HB2ST", "vmem")
    n = 3 * band + 2
    rng = np.random.default_rng(band)
    ab = rng.standard_normal((band + 1, n)).astype(np.float32)
    want = band_bulge.hb2st(ab.copy())
    if band == 128:
        monkeypatch.setattr(
            band_wave_vmem, "_hb2st_vmem_jit",
            lambda ab, band, n, interpret=False: tuple(
                jnp.asarray(x) for x in want))
    d, e, _, _ = hb2st(ab.copy())
    assert np.allclose(d, want[0], atol=5e-3, rtol=5e-3)
    assert metrics.counter_value("hb2st.shear", form=form) == 1
    assert metrics.counter_total("hb2st.shear") == 1
    assert metrics.counter_value("hb2st.backend", rung="vmem") == 1
    (span,) = [s for s in obs.captured_spans() if s["name"] == "hb2st"]
    assert (span["labels"]["rung"], span["labels"]["shear"]) == (
        "vmem", form)


# ------------------------------------------- the control's principle

@pytest.mark.parametrize("seed", [3, 2_147_483_659])
def test_a_lower_tier_reads_worse_by_the_residual(seed):
    a = symmetric("spd", seed, n=256)
    sound = numbers_in_eps(a, *plain_eig.eig_via_band(a, 32, "f32"))
    lower = numbers_in_eps(a, *plain_eig.eig_via_band(a, 32, "bf16_3x"))
    coarse = numbers_in_eps(a, *plain_eig.eig_via_band(a, 32, "mxu_bf16"))
    assert sound["residual_fro"] < 4 and sound["values_max"] < 8, sound
    assert lower["residual_fro"] > 4 * sound["residual_fro"], (lower,
                                                               sound)
    assert coarse["residual_fro"] > 50 * lower["residual_fro"]
    # the vectors stay orthogonal whatever the tier: the residual and
    # the values are what tell the tiers apart
    assert lower["orth_fro"] < 4
