"""``slate.gesv`` on ``Grid(2,2)``: the LU that ``gesv_16k_2x2`` times on
four chips (``getrf()`` → eight ``_getrf_chunk_core`` programs of two
block columns → ``getrs``: LAPACK pivots replayed swap by swap, two
X-moving ``trsm``), against the benchmark's plain numpy solver
(``benchmarks/harness/plain_solver.gesv``) and the residual of
``benchmarks/harness/check.py``.

Both forms of the panel run. ``partial``: the row cap as the CPU has it
(none), the [M, nb] panel gathered to every device, one
``lax.linalg.lu`` of it, LAPACK's pivots. ``tournament``: the cap
lowered to 128 rows, so that the 256-row panel is over it, as the
chip's 16,384-row panel is over its cap of 10,240 (the rule itself,
``getrf._panel_max_rows``, reads the platform and is not changed); the
panel is then factored where its rows are stored
(``getrf._panel_stored_rows``): one ``lu`` of each device's 128 rows,
the 2 x 32 winners gathered over p, a last ``lu`` of them.
``tournament-cap64``: the cap under the local height, so a device's
rows go through two chunks and a second round before anything crosses.

Geometries: kt = 8 block columns of 32, so four chunk programs of two
block columns (a first, two inner, a last; the chip's kt = 16 makes
eight, and each (form, n) compiles every one of its own, so the count
is kept to what has an inner chunk); n = 256 on the tile
grid and n = 244 with 20 real rows in the last tile. One right-hand
side (HPL's, the cell's) and eight.

CPU only (every tier is f32 here): what the chip adds is in PERF.md.
"""

import jax
import numpy as np
import pytest
import scipy.linalg as sla

import slate_tpu as st
from slate_tpu import obs
from slate_tpu.cache import jitcache
from slate_tpu.linalg import getrf as getrf_mod
from slate_tpu.obs import metrics, tracing
from benchmarks.harness import check, plain_solver
from tests.test_gesv_ragged import _paths, errors_in_eps

NB, KT = 32, 8
# the row cap of each form: None as the CPU has it; 128 = a device's
# rows of the 256-row panel; 64 = two local chunks, a second round
CAPS = {"partial": None, "tournament": 128, "tournament-cap64": 64}
GEOMETRIES = [256, 244]
FORMS = list(CAPS)
CASES = [(n, nrhs, form) for form in FORMS for n in GEOMETRIES
         for nrhs in (1, 8)]
IDS = [f"n{n}-nrhs{nrhs}-{form}" for n, nrhs, form in CASES]
TIER = {st.Option.TrailingPrecision: "bf16_6x"}

# Backward errors in units of eps = 2^-24, evaluated in float64. Over
# these cases the program reads 1.2-2.6 (inf) and 1.1-1.9 (Frobenius)
# with any of the three panels (2.4 and 1.8 at most through Op.Trans),
# the plain f32 solver 1.3-1.9 and 1.1-1.9; the plain solver with its
# trailing products at bf16_3x reads 55-71 and 38-44 (eight trailing
# updates of depth 32; 69-85 and 52-56 with sixteen, at n = 512). The
# limit 12 is 4.6x over the largest sound reading and 3.2x under the
# smallest lowered one.
TOL_EPS = 12.0
# As tests/test_gesv_ragged.py: two answers that each solve a nearby
# system lie within (the sum of their backward errors) x cond of each
# other; measured 0.025-0.08 cond*eps here, tournament pivots included
# (another choice of pivots, the same system); cond is 5.2e3-1.1e4.
TOL_COND_EPS = 1.0
# ||P A - L U||_F / ||A||_F in eps: 12.5-12.6 with LAPACK's pivots,
# 12.6-14.2 with the stored tournament's, max |L| 1.6-1.9 (n rounding
# errors of random sign an entry: sqrt(n) = 16). 100 leaves 7x; a
# wrong permutation or a row of L out of place reads 10^6 and more.
TOL_FACTOR_EPS = 100.0


@pytest.fixture(scope="module")
def solved(grid22):
    """``{(n, nrhs, form): operands, outputs, records}``: every case
    solved once, the spans of the solve captured and its counters read.
    The chunk programs are keyed by shapes, not by the cap, so the
    in-process executables are dropped where the form changes and again
    at the end (a later test of this worker must not be handed a
    tournament panel)."""
    out = {}
    patch = pytest.MonkeyPatch()
    was_metrics = obs.metrics_enabled()
    patch.setattr(tracing, "_profiling", lambda: True)
    try:
        for form in FORMS:
            jitcache.clear_in_process("getrf.chunk")
            if CAPS[form] is not None:
                patch.setattr(getrf_mod, "_panel_max_rows",
                              lambda platform, cap=CAPS[form]: cap)
            for i, n in enumerate(GEOMETRIES):
                A = st.random_matrix(n, n, NB, grid22, np.float32,
                                     seed=6001 + i)
                a = np.asarray(A.to_dense())
                for nrhs in (1, 8):
                    B = st.random_matrix(n, nrhs, NB, grid22, np.float32,
                                         seed=7001 + i)
                    obs.reset()
                    obs.metrics_on()
                    X, LU, piv, info = jax.block_until_ready(
                        st.gesv(A, B, TIER))
                    b = np.asarray(B.to_dense())
                    out[n, nrhs, form] = {
                        "A": A, "B": B, "X": X, "LU": LU, "piv": piv,
                        "a": a, "b": b, "info": int(info),
                        "x": np.asarray(X.to_dense()),
                        "x_ref": plain_solver.gesv(a, b, NB, "f32"),
                        "spans": _paths(obs.captured_spans()),
                        "chunked": metrics.counter_value(
                            "getrf.path", phase="spmd_chunk"),
                        "paths": metrics.counter_total("getrf.path"),
                        "gathered": metrics.counter_total(
                            "getrf.panel_gather_bytes"),
                        "moved_x": metrics.counter_total("trsm.move_x"),
                        "replays": metrics.counter_value(
                            "getrs.apply_pivots", kind="swap_sim")}
        yield out
    finally:
        patch.undo()
        jitcache.clear_in_process("getrf.chunk")
        if not was_metrics:
            obs.metrics_off()
        obs.reset()


def _panel_bytes(form):
    """What a device receives of panel rows over p in one factorization:
    gathered, every one of the kt panels whole; stored, the p x nb
    winner rows of each."""
    rows = KT * NB if form == "partial" else 2 * NB
    return KT * rows * NB * 4


def _perm_of(piv, rows):
    """LAPACK's swap list replayed: row i of P·A is row ``perm[i]`` of A."""
    perm = np.arange(rows)
    for j, t in enumerate(np.asarray(piv).reshape(-1)):
        perm[j], perm[t] = perm[t], perm[j]
    return perm


@pytest.mark.parametrize("n,nrhs,form", CASES, ids=IDS)
def test_info_zero_on_the_chunked_path(solved, n, nrhs, form):
    s = solved[n, nrhs, form]
    assert s["info"] == 0
    assert s["x"].shape == (n, nrhs) and np.isfinite(s["x"]).all()
    assert s["chunked"] == 1 and s["paths"] == 1
    assert s["gathered"] == _panel_bytes(form)


@pytest.mark.parametrize("n,nrhs,form", CASES, ids=IDS)
def test_x_agrees_with_the_plain_solver(solved, n, nrhs, form):
    s = solved[n, nrhs, form]
    cond = np.linalg.cond(s["a"].astype(np.float64), np.inf)
    apart = (np.linalg.norm(s["x"] - s["x_ref"], np.inf)
             / np.linalg.norm(s["x_ref"], np.inf))
    assert apart <= TOL_COND_EPS * cond * check.EPS, (apart, cond)


@pytest.mark.parametrize("n,nrhs,form", CASES, ids=IDS)
def test_backward_errors_within_the_limit(solved, n, nrhs, form):
    s = solved[n, nrhs, form]
    got = errors_in_eps(s["a"], s["x"], s["b"])
    ref = errors_in_eps(s["a"], s["x_ref"], s["b"])
    assert all(v <= TOL_EPS for v in got.values()), got
    assert all(v <= TOL_EPS for v in ref.values()), ref


@pytest.mark.parametrize("n,nrhs,form", CASES, ids=IDS)
def test_a_tier_down_fails_the_limit_the_sound_tier_passes(solved, n,
                                                           nrhs, form):
    s = solved[n, nrhs, form]
    lowered = plain_solver.gesv(s["a"], s["b"], NB, "bf16_3x")
    low = errors_in_eps(s["a"], lowered, s["b"])
    assert all(v > TOL_EPS for v in low.values()), low
    assert low["fro"] > 4 * errors_in_eps(s["a"], s["x"], s["b"])["fro"]


@pytest.mark.parametrize("n,nrhs,form", CASES, ids=IDS)
def test_the_pivots_and_the_factor(solved, n, nrhs, form):
    """``partial``: LAPACK's rows, row for row. ``tournament``: another
    choice of rows (CALU), still a swap list, and P·A = L·U with unit
    lower L either way."""
    s = solved[n, nrhs, form]
    piv = np.asarray(s["piv"])
    assert piv.shape == (KT, NB) and piv.dtype == np.int32
    rows = np.arange(KT * NB)
    flat = piv.reshape(-1)
    assert (flat[n:] == rows[n:]).all()          # padded slots self-swap
    assert (flat[:n] >= rows[:n]).all() and (flat[:n] < n).all()
    lapack = sla.lu_factor(s["a"])[1]
    if form == "partial":
        assert (flat[:n] == lapack).all()
    else:
        assert (flat[:n] != lapack).any()
    perm = _perm_of(piv, KT * NB)
    assert sorted(perm[:n]) == list(range(n))
    lu = np.asarray(s["LU"].to_dense(), np.float64)
    L = np.tril(lu, -1) + np.eye(n)
    apart = np.linalg.norm(s["a"].astype(np.float64)[perm[:n]]
                           - L @ np.triu(lu))
    assert apart <= TOL_FACTOR_EPS * check.EPS * np.linalg.norm(s["a"])
    if form == "partial":
        assert np.abs(L).max() <= 1.0            # what row pivoting bounds


@pytest.mark.parametrize("n,nrhs,form", CASES, ids=IDS)
def test_four_equal_shards(solved, n, nrhs, form):
    s = solved[n, nrhs, form]
    for M in (s["A"], s["LU"], s["X"]):
        where = check.equal_shards(M.data, 4)
        assert where["ok"], where


@pytest.mark.parametrize("n,nrhs,form", CASES, ids=IDS)
def test_getrs_trans_on_the_same_factor(solved, n, nrhs, form):
    s = solved[n, nrhs, form]
    Xt = st.getrs(s["LU"], s["piv"], s["B"], st.Op.Trans, TIER)
    got = errors_in_eps(s["a"].T, np.asarray(Xt.to_dense()), s["b"])
    assert all(v <= TOL_EPS for v in got.values()), got


@pytest.mark.parametrize("n,nrhs,form", CASES, ids=IDS)
def test_the_span_tree_of_one_solve(solved, n, nrhs, form):
    s = solved[n, nrhs, form]
    tree = s["spans"]
    names = [p for p, _ in tree]
    assert names[0] == "slate.gesv"
    assert tree[0][1]["labels"]["grid"] == "2x2"
    assert tree[0][1]["labels"]["nrhs"] == nrhs
    top = dict(tree)["slate.gesv/getrf"]["labels"]
    assert top["pivoting"] == form.split("-")[0]
    assert top["panel_form"] == ("gathered" if form == "partial"
                                 else "stored")
    assert top["panel_rows"] == KT * NB
    assert top["panel_gather_bytes"] == _panel_bytes(form)
    assert top["precision"] == "bf16_6x"
    assert top["mt"] == KT and top["pad_rows"] == KT * NB - n
    prepare = names.index("slate.gesv/getrf/getrf.prepare")
    chunks = [(i, span) for i, (p, span) in enumerate(tree)
              if p == "slate.gesv/getrf/getrf.chunk"]
    assert prepare < chunks[0][0]
    assert [(c["labels"]["phase"], c["labels"]["k0"], c["labels"]["klen"])
            for _, c in chunks] == [("spmd_chunk", k0, 2)
                                    for k0 in range(0, KT, 2)]
    pivots = dict(tree)["slate.gesv/getrs/getrs.apply_pivots"]["labels"]
    assert pivots["kind"] == "swap_sim" and pivots["steps"] == KT * NB
    assert pivots["serial_steps"] == NB + KT and s["replays"] == 1
    solves = [span for p, span in tree if p == "slate.gesv/getrs/trsm"]
    assert [t["labels"]["form"] for t in solves] == ["move_x", "move_x"]
    assert [t["labels"]["op"] for t in solves] == ["N", "N"]
    assert s["moved_x"] == 2
    assert names.index("slate.gesv/getrf") < names.index(
        "slate.gesv/getrs")
