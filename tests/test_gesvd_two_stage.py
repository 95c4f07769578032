"""``slate.gesvd`` through the two-stage pipeline with both sets of
vectors, held to the plain reference the benchmark cell
``gesvd_12288x8192_vec_1x1`` holds it to (``benchmarks/harness/
plain_svd.py``: independent of ``slate_tpu``), on seeded operands at
small sizes on the CPU: tall, square and wide, the 1x1 grid and the CPU
2x2 mesh; the device bidiagonal solve against ``bdsqr``'s float64 answer;
the precision tier reaching ``ge2tb``; what a call reports (span tree,
sync sites, counters, a demoted chase); and that nothing of O(n^2)
crosses to the host inside a call.

Tolerances are in units of eps = 2^-24 (f32) with their reasons beside
them; the chip's readings at the cell's size are in PERF.md section 2.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import obs
from slate_tpu.linalg import bulge, ge2tb as g2, stedc, svd
from slate_tpu.obs import metrics
from slate_tpu.robust import ladder
from slate_tpu.types import MethodSVD, Option

from benchmarks.harness import plain_svd

EPS32 = 2.0 ** -24
M, N, NB, BAND = 384, 256, 64, 32       # the band forced under the tile


@pytest.fixture(params=["1x1", "2x2"])
def grid(request, grid11, grid22):
    return grid11 if request.param == "1x1" else grid22


def two_stage(tier="bf16_6x"):
    return {Option.MethodSVD: MethodSVD.TwoStage, Option.EigBand: BAND,
            Option.TrailingPrecision: tier}


def normal(m, n, seed):
    """The cell's matrix class: iid standard normal, f32."""
    return np.random.default_rng(seed).standard_normal(
        (m, n)).astype(np.float32)


def numbers_in_eps(a, s, U, VT):
    """The five numbers of the cell's check, in units of 2^-24."""
    out = plain_svd.equations(jnp.asarray(a), s, jnp.asarray(U),
                              jnp.asarray(VT))
    out["values_max"] = plain_svd.values_error(
        s, plain_svd.reference_values(a))
    return {k: v / EPS32 for k, v in out.items()}


# ------------------------------------------ the bidiagonal solve alone

def bidiagonal(kind, n, seed=5):
    rng = np.random.default_rng(seed)
    d = 1.0 + rng.random(n)
    e = 0.5 * rng.standard_normal(n - 1)
    if kind == "graded":            # sigma over three decades
        d = d * np.logspace(0, -3, n)
        e = e * np.logspace(0, -3, n)[1:]
    if kind == "rank_deficient":    # two exact zeros on the diagonal
        d[[n // 3, n // 2]] = 0.0
    return d, e


def held_to_float64(d, e, s, U, V, tol):
    """(s, U, V) against B itself and ``bdsqr``'s float64 values."""
    n = d.shape[0]
    B = np.diag(d) + np.diag(e, 1)
    s0 = bulge.bdsqr(d, e)
    U, V = np.asarray(U, np.float64), np.asarray(V, np.float64)
    assert np.all(np.diff(s) <= 0) and np.all(s >= 0)
    assert np.abs(s - s0).max() <= tol * s0[0]
    assert np.linalg.norm((U * s) @ V.T - B) <= tol * np.linalg.norm(B)
    assert np.linalg.norm(U.T @ U - np.eye(n)) <= tol * np.sqrt(n)
    assert np.linalg.norm(V.T @ V - np.eye(n)) <= tol * np.sqrt(n)


@pytest.mark.parametrize("kind, dtype, tol", [
    # f32 merges at kappa ~ 10: stedc's own orthogonality (tens of eps)
    ("random", np.float32, 200 * EPS32),
    # kappa = 1e3 in float64: the u and v halves drift by eps64 * kappa
    ("graded", np.float64, 1e-11),
])
def test_the_device_bidiagonal_solve_against_float64(grid, kind, dtype,
                                                     tol):
    d, e = bidiagonal(kind, 200)
    s, U, V = bulge.bdsdc(d, e, grid, dtype)
    assert U.dtype == V.dtype == np.dtype(dtype)
    held_to_float64(d, e, s, U, V, tol)


def test_a_rank_deficient_bidiagonal_goes_to_the_host_branch(grid11):
    """sigma = 0: the +- spaces of the Golub-Kahan form collide, the
    halves of those vectors lose their norm, ``bdsdc`` says so (None)
    and ``bdsqr`` completes the null spaces in float64."""
    d, e = bidiagonal("rank_deficient", 120)
    assert bulge.bdsdc(d, e, grid11, np.float32) is None
    s, U, VT = bulge.bdsqr(d, e, want_uv=True)
    assert s[-1] <= 1e-12 * s[0]
    held_to_float64(d, e, s, U, VT.T, 1e-10)


def test_a_rank_deficient_matrix_is_answered_by_the_host_route(
        grid11, observed):
    """The same through the public call: an exactly rank-deficient A
    (two columns repeated) is answered by ``gesvd.bidiag{route=host}``
    and the answer still holds the equations."""
    a = normal(96, 64, 3)
    a[:, 10], a[:, 50] = a[:, 11], a[:, 51]
    A = st.Matrix.from_dense(a, nb=16, grid=grid11)
    s, U, VT = st.gesvd(A, {Option.MethodSVD: MethodSVD.TwoStage},
                        want_u=True, want_vt=True)
    assert metrics.counter_value("gesvd.bidiag", route="host") == 1
    got = numbers_in_eps(a, s, U.to_dense(), VT.to_dense())
    # f32 reflectors around a float64 bidiagonal solve
    assert max(got.values()) < 60, got
    assert s[-2] < 1e-5 * s[0]


# ------------------------------- the public call, against the reference

@pytest.mark.parametrize("shape", ["tall", "square", "wide"])
def test_two_stage_gesvd_against_the_plain_reference(grid, shape):
    m, n = {"tall": (M, N), "square": (N, N), "wide": (N, M)}[shape]
    a = normal(m, n, 17)
    A = st.Matrix.from_dense(a, nb=NB, grid=grid)
    s, U, VT = st.gesvd(A, two_stage(), want_u=True, want_vt=True)
    k = min(m, n)
    assert s.shape == (k,) and s.dtype == np.float32
    assert (U.m, U.n) == (m, k) and (VT.m, VT.n) == (k, n)
    assert plain_svd.descending(s)
    got = numbers_in_eps(a, s, U.to_dense(), VT.to_dense())
    # residual_fro sums k triplets (sqrt(k) = 16 times residual_max);
    # the two orthogonalities are stedc's at order 2k in f32 (tens of
    # eps; the square matrix has small sigmas, where the halves of a
    # Golub-Kahan vector drift by eps * ||A|| / sigma)
    assert got["residual_max"] < 12, got
    assert got["residual_fro"] < 120, got
    assert got["orth_u"] < 150 and got["orth_v"] < 150, got
    assert got["values_max"] < 12, got
    ref = np.linalg.svd(a.astype(np.float64), compute_uv=False)
    assert np.abs(s - ref).max() < 12 * EPS32 * ref[0]


def test_two_stage_and_dense_agree_up_to_column_signs(grid11):
    """The share test of this family: with singular values apart (a
    prescribed spectrum, gaps of 1/96 of the norm), every u_j and v_j of
    the two-stage path is the dense path's up to its sign."""
    m, n = 144, 96
    rng = np.random.default_rng(23)
    Q, _ = np.linalg.qr(rng.standard_normal((m, n)))
    P, _ = np.linalg.qr(rng.standard_normal((n, n)))
    a = ((Q * np.linspace(2.0, 1.0, n)) @ P.T).astype(np.float32)
    A = st.Matrix.from_dense(a, nb=32, grid=grid11)
    s2, U2, VT2 = st.gesvd(A, {Option.MethodSVD: MethodSVD.TwoStage},
                           want_u=True, want_vt=True)
    s1, U1, VT1 = st.gesvd(A, {Option.MethodSVD: MethodSVD.Dense},
                           want_u=True, want_vt=True)
    assert np.abs(s1 - s2).max() < 8 * EPS32 * s1[0]
    cu = np.sum(np.asarray(U1.to_dense()) * np.asarray(U2.to_dense()), 0)
    cv = np.sum(np.asarray(VT1.to_dense()) * np.asarray(VT2.to_dense()), 1)
    # a vector turns by eps * ||A|| / gap = 96 eps: |cos| = 1 - O(1e-10)
    assert np.abs(np.abs(cu) - 1).max() < 1e-4
    assert np.abs(np.abs(cv) - 1).max() < 1e-4
    assert np.array_equal(np.sign(cu), np.sign(cv))     # A = U S VT


# -------------------------------------------- the tier reaches stage 1

def test_the_tier_reaches_ge2tbs_trailing_products(grid):
    """``Option.TrailingPrecision`` is on the products of the two
    trailing updates and on nothing else of ``_ge2tb_jit``, in either
    body: the four matmuls of a stage's step in the exact-shape one
    (VᴴC, V·W, C·V, W·Vᴴ; not the panels, the Gram matrices or the
    T's), the six einsums of the SPMD one. The default call traces the
    program it traced before (``bf16_6x`` is the package default, so
    ``tests/test_ge2tb.py`` reads the same numbers)."""
    a = normal(96, 64, 2)
    A = st.Matrix.from_dense(a, nb=16, grid=grid)
    program = g2._program(A)
    assert program == {1: "exact", 4: "spmd"}[grid.size]

    def dots(tier):
        text = g2._ge2tb_jit.lower(A, tier).as_text()
        return [line for line in text.splitlines()
                if "dot_general" in line]

    high = [d for d in dots("bf16_3x") if "HIGH>" in d or "HIGH," in d
            or "HIGH]" in d]
    # nt = 4: one stage (a loop body) in the exact-shape program
    assert len(high) == {"exact": 4, "spmd": 6}[program], high
    assert not any("HIGHEST" in d for d in high)
    # at the sound tier (and by default) no product is below HIGHEST
    assert all("HIGHEST" in d or "precision" not in d
               for d in dots("bf16_6x"))
    out6 = g2.ge2tb(A, {Option.TrailingPrecision: "bf16_6x"})
    out0 = g2.ge2tb(A)
    for x, y in zip((out6[0].data, *out6[1:]), (out0[0].data, *out0[1:])):
        assert np.array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("seed", [3, 2_147_483_659])
def test_a_lower_tier_reads_worse_by_the_residual(seed):
    """The control's principle, where no chip is: the plain band
    reduction with its trailing products as the MXU computes them."""
    a = normal(384, 256, seed)
    sound = numbers_in_eps(a, *plain_svd.svd_via_band(a, 32, "f32"))
    lower = numbers_in_eps(a, *plain_svd.svd_via_band(a, 32, "bf16_3x"))
    coarse = numbers_in_eps(a, *plain_svd.svd_via_band(a, 32, "mxu_bf16"))
    # the sound reading is the f32 rounding of 24 panels' products and
    # of the final U and VT (residual_fro sums 256 triplets)
    assert sound["residual_fro"] < 20 and sound["values_max"] < 8, sound
    assert lower["residual_fro"] > 4 * sound["residual_fro"], (lower,
                                                               sound)
    assert coarse["residual_fro"] > 50 * lower["residual_fro"]
    # the vectors stay orthogonal whatever the tier: the residuals and
    # the values are what tell the tiers apart
    assert lower["orth_u"] < 8 and lower["orth_v"] < 8, lower


# --------------------------------------------------- what a call reports

def run_observed(grid, opts=None, m=M, n=N):
    a = normal(m, n, 29)
    A = st.Matrix.from_dense(a, nb=NB, grid=grid)
    s, U, VT = st.gesvd(A, opts or two_stage(), want_u=True, want_vt=True)
    return a, s, U, VT


def test_span_tree_of_a_two_stage_call(grid, observed):
    run_observed(grid)
    spans = obs.captured_spans()
    (root,) = [s for s in spans if s["parent"] == 0
               and s["name"] == "slate.gesvd"]
    assert root["name"] == svd.SPANS[0]
    labels = root["labels"]
    assert {k: labels[k] for k in ("routine", "m", "n", "nb", "grid",
                                   "jobu", "jobvt", "method", "path")} == {
        "routine": "gesvd", "m": M, "n": N, "nb": NB,
        "grid": f"{grid.p}x{grid.q}", "jobu": "S", "jobvt": "S",
        "method": "TwoStage", "path": "two_stage"}
    # at its end: what the pipeline chose
    rung = ladder.tb2bd_ladder().last_rung
    assert (labels["band"], labels["chase_backend"], labels["bidiag"]) \
        == (BAND, rung, "gk_stedc")
    children = [s["name"] for s in sorted(
        (s for s in spans if s["parent"] == root["id"]
         and s["name"].startswith("gesvd.")), key=lambda s: s["start_ns"])]
    assert children == list(svd.SPANS[1:9])
    # the routines under the stages
    for stage, routine in (("gesvd.stage1", "ge2tb"),
                           ("gesvd.stage2", "tb2bd"),
                           ("gesvd.back.tb2bd.u", "unmtr_bulge"),
                           ("gesvd.back.tb2bd.v", "unmtr_bulge"),
                           ("gesvd.back.ge2tb.u", "unmqr"),
                           ("gesvd.back.ge2tb.v", "unmbr_ge2tb_v")):
        (outer,) = [s for s in spans if s["name"] == stage]
        assert any(s["name"] == routine and s["parent"] == outer["id"]
                   for s in spans), (stage, routine)
    (chase,) = [s for s in spans if s["name"] == "tb2bd"]
    assert chase["labels"]["rung"] == rung
    assert ("shear" in chase["labels"]) == (rung == "vmem")
    # stage 1 says which program and which panel form answered: the
    # band's tiles are whole on the one chip, so it takes the
    # exact-shape body there and the SPMD one on the grid
    program = "exact" if grid.size == 1 else "spmd"
    (stage1,) = [s for s in spans if s["name"] == "ge2tb"]
    assert (stage1["labels"]["program"], stage1["labels"]["panel"]) == (
        program, "xla")
    assert metrics.counter_value("ge2tb.path", program=program) == 1
    assert metrics.counter_total("ge2tb.path") == 1
    assert "ge2tb.path" in svd.COUNTERS
    # every blocking read of the path is a named sync=1 span
    sites = {}
    for s in spans:
        if s["labels"].get("sync") == 1:
            sites[s["name"]] = sites.get(s["name"], 0) + 1
    levels = int(metrics.counter_total("stedc.levels"))
    assert levels >= 3
    assert sites.pop("band.gather") == 1
    assert sites.pop("gesvd.values") == 1
    assert sites.pop("stedc.zrow") == sites.pop("stedc.roots") == levels
    assert set(sites) <= {"tb2bd.bidiagonal"}, sites
    # the merges run at order 2n, inside the bidiagonal stage
    tops = [(s["labels"]["level"], s["labels"]["k"], s["labels"]["m"])
            for s in spans if s["name"] == "stedc.roots"]
    assert tops[-1] == (0, 2 * N, 1)
    (bidiag,) = [s for s in spans if s["name"] == "gesvd.bidiag"]
    inside = [s for s in spans if s["name"].startswith("stedc.")
              or s["name"] == "gesvd.values"]
    assert all(bidiag["start_ns"] <= s["start_ns"]
               and s["end_ns"] <= bidiag["end_ns"] for s in inside)
    # the counters, once a call each
    assert set(svd.COUNTERS) >= set(stedc.COUNTERS)
    assert metrics.counter_value("gesvd.path", path="two_stage") == 1
    assert metrics.counter_total("gesvd.path") == 1
    assert metrics.counter_value("tb2bd.backend", rung=rung) == 1
    assert metrics.counter_total("tb2bd.backend") == 1
    assert metrics.counter_total("tb2bd.demotion") == 0
    assert metrics.counter_value("gesvd.bidiag", route="gk_stedc") == 1
    assert metrics.counter_total("gesvd.bidiag") == 1
    poles = metrics.counter_total("stedc.poles")
    assert 0 <= metrics.counter_total("stedc.deflated") < poles
    assert poles >= 2 * N           # the top merge alone has 2n poles


def test_span_tree_of_a_values_only_call(grid11, observed):
    a = normal(M, N, 31)
    A = st.Matrix.from_dense(a, nb=NB, grid=grid11)
    s, U, VT = st.gesvd(A, two_stage())
    assert U is None and VT is None
    spans = obs.captured_spans()
    (root,) = [s for s in spans if s["name"] == "slate.gesvd"]
    assert (root["labels"]["jobu"], root["labels"]["jobvt"],
            root["labels"]["bidiag"]) == ("N", "N", "host")
    children = [s["name"] for s in sorted(
        (s for s in spans if s["parent"] == root["id"]
         and s["name"].startswith("gesvd.")), key=lambda s: s["start_ns"])]
    assert children == list(svd.SPANS[1:5])
    assert metrics.counter_value("gesvd.bidiag", route="host") == 1
    ref = np.linalg.svd(a.astype(np.float64), compute_uv=False)
    assert np.abs(s - ref).max() < 12 * EPS32 * ref[0]


def test_span_tree_of_a_dense_call(grid11, observed):
    a, s, U, VT = run_observed(grid11,
                               {Option.MethodSVD: MethodSVD.Dense})
    (root,) = [s_ for s_ in obs.captured_spans() if s_["parent"] == 0]
    assert root["name"] == "slate.gesvd"
    assert (root["labels"]["path"], root["labels"]["method"]) == (
        "dense", "Dense")
    assert "band" not in root["labels"]
    names = [s_["name"] for s_ in obs.captured_spans()
             if s_["parent"] == root["id"]]
    assert "gesvd.dense" in names and "gesvd.values" in names
    assert metrics.counter_value("gesvd.path", path="dense") == 1
    assert metrics.counter_total("gesvd.bidiag") == 0
    assert numbers_in_eps(a, s, U.to_dense(), VT.to_dense())[
        "residual_max"] < 12


def test_a_demoted_chase_is_counted_and_logged(grid11, observed,
                                               monkeypatch):
    lad = ladder.tb2bd_ladder()
    first = lad.select(np.zeros((BAND + 1, N), np.float32))
    i = lad._names.index(first)
    below = lad._names[i + 1]

    def raises(band):
        raise RuntimeError("made to raise")

    rungs = list(lad.rungs)
    rungs[i] = dataclasses.replace(rungs[i], run=raises)
    monkeypatch.setattr(lad, "rungs", rungs)
    before = len(ladder.demotion_log())
    a, s, U, VT = run_observed(grid11)
    # the answer is still right: that is why it has to be counted
    assert numbers_in_eps(a, s, U.to_dense(), VT.to_dense())[
        "residual_max"] < 12
    assert metrics.counter_value("tb2bd.demotion", to=below,
                                 **{"from": first}) == 1
    assert metrics.counter_value("tb2bd.backend", rung=below) == 1
    assert metrics.counter_value("tb2bd.backend", rung=first) == 0
    (root,) = [s_ for s_ in obs.captured_spans()
               if s_["name"] == "slate.gesvd"]
    assert root["labels"]["chase_backend"] == below
    logged = ladder.demotion_log()[before:]
    assert [(d.ladder, d.from_rung, d.to_rung) for d in logged] == [
        ("tb2bd", first, below)]


def test_the_env_override_pins_the_starting_rung(monkeypatch, observed):
    monkeypatch.setenv("SLATE_TB2BD", "numpy")
    ub = np.random.default_rng(4).standard_normal((9, 40))
    want = np.linalg.svd(
        sum(np.diag(ub[k, :40 - k], k) for k in range(9)),
        compute_uv=False)
    d, e = g2.tb2bd(ub)[:2]
    assert ladder.tb2bd_ladder().last_rung == "numpy"
    assert metrics.counter_value("tb2bd.backend", rung="numpy") == 1
    assert np.allclose(bulge.bdsqr(d, e), want, rtol=1e-10, atol=1e-10)


def test_the_vmem_rung_reads_d_and_e_through_one_sync_site(
        observed, monkeypatch):
    """Off the chip the rung's probe says no: made to say yes, the
    kernel runs in interpret mode at band 8 and its one blocking read is
    ``tb2bd.bidiagonal``."""
    from slate_tpu.internal import band_bulge
    lad = ladder.tb2bd_ladder()
    rungs = list(lad.rungs)
    i = lad._names.index("vmem")
    rungs[i] = dataclasses.replace(rungs[i], probe=lambda band: True)
    monkeypatch.setattr(lad, "rungs", rungs)
    monkeypatch.setenv("SLATE_TB2BD", "vmem")
    band, n = 8, 26
    ub = np.random.default_rng(8).standard_normal(
        (band + 1, n)).astype(np.float32)
    want = band_bulge.tb2bd(ub.copy())
    d, e = g2.tb2bd(ub.copy())[:2]
    assert np.allclose(np.abs(d), np.abs(want[0]), atol=5e-3, rtol=5e-3)
    assert metrics.counter_value("tb2bd.backend", rung="vmem") == 1
    (span,) = [s for s in obs.captured_spans() if s["name"] == "tb2bd"]
    assert (span["labels"]["rung"], span["labels"]["shear"]) == (
        "vmem", "ladder")
    sites = [s["name"] for s in obs.captured_spans()
             if s["labels"].get("sync") == 1]
    assert sites == ["tb2bd.bidiagonal"]


# ------------------------------ nothing of O(n^2) on the host in a call

def test_no_array_of_order_n_squared_crosses_to_the_host(grid11,
                                                         monkeypatch):
    """Inside a call with both sets of vectors the host is handed the
    band (2 nt tiles), d and e, and the O(k) reads of the merges, all of
    them through ``obs.sync_read``; no dense operand is tiled from a host
    array."""
    m, n = 576, 384
    a = normal(m, n, 41)
    A = st.Matrix.from_dense(a, nb=NB, grid=grid11)
    read, tiled_on_host = {}, []
    real_read, real_from_dense = obs.sync_read, st.Matrix.from_dense.__func__

    def counting_read(name, fn, x, **labels):
        out = real_read(name, fn, x, **labels)
        nbytes = sum(np.asarray(leaf).nbytes for leaf in
                     (out if isinstance(out, (tuple, list)) else (out,)))
        read[name] = read.get(name, 0) + nbytes
        return out

    def watching_from_dense(cls, x, *args, **kw):
        if isinstance(x, np.ndarray):
            tiled_on_host.append(x.shape)
        return real_from_dense(cls, x, *args, **kw)

    monkeypatch.setattr(obs, "sync_read", counting_read)
    monkeypatch.setattr(st.Matrix, "from_dense",
                        classmethod(watching_from_dense))
    s, U, VT = st.gesvd(A, two_stage(), want_u=True, want_vt=True)
    assert tiled_on_host == []
    assert set(read) <= {"band.gather", "tb2bd.bidiagonal", "stedc.zrow",
                         "stedc.roots", "gesvd.values"}, read
    # the band is 2 nt tiles of band^2 words; everything else is O(n) a
    # level of the tree. One n x n f32 array would be 589,824 bytes
    assert read["band.gather"] == 2 * (n // BAND) * BAND * BAND * 4 \
        - BAND * BAND * 4
    assert sum(read.values()) < n * n * 4 / 3, read
    assert numbers_in_eps(a, s, U.to_dense(), VT.to_dense())[
        "residual_max"] < 12
