"""``slate.gesv`` on ``Grid(1,1)`` at orders off the tile grid: the LU
that ``gesv_10000_nb384_1x1`` times on the chip (``getrf()`` →
``_getrf_dense_1dev``: XLA ``lu`` panels on true-height slices, LAPACK
pivots, ragged last tile, no Pallas), against the benchmark's plain
numpy solver (``benchmarks/harness/plain_solver.gesv``) and the
residual of ``benchmarks/harness/check.py``.

The geometries keep the cell's features small: (250, 96) three tile
rows, the last 58 real rows of 96; (625, 24) the cell's 27 tile rows,
ONE real row in the last; (1000, 384) the cell's tile of 384 = 3·128
lanes, the last tile 232 of 384. Operands come from the program's own
seeded generators, as the cell makes them, at the cell's tier.

CPU only (every tier is f32 here): what the chip adds is in PERF.md.
"""

import jax
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import obs
from slate_tpu.linalg import getrf as getrf_mod
from slate_tpu.obs import metrics, tracing
from benchmarks.harness import check, plain_solver

CASES = [(250, 96), (625, 24), (1000, 384)]
NRHS = 8
TIER = {st.Option.TrailingPrecision: "bf16_6x"}

# Backward errors in units of eps = 2^-24, evaluated in float64 (XLA:CPU's
# f32 accumulation would add the check's own rounding). Over the three
# cases and three seeds each the program reads 1.1-7.2 (inf) and 1.5-5.5
# (Frobenius), the plain f32 solver the same; the plain solver with its
# trailing products at bf16_3x reads 40-118 and 33-65. The limit 16 is
# 2.2x over the largest sound reading and 2x under the smallest lowered one.
TOL_EPS = 16.0
# Two answers that each solve a nearby system (backward error of a few
# eps) lie within (their backward errors' sum) x cond of each other;
# measured 0.01-0.08 cond*eps. 1.0 leaves 13x room; an answer to another
# system is off by O(1), 40-8000 in these units at these conditions.
TOL_COND_EPS = 1.0


@pytest.fixture(scope="module")
def solved(grid11):
    """``{(n, nb): operands, outputs and the plain solver's X}``, each
    geometry solved once for all the tests below."""
    out = {}
    for i, (n, nb) in enumerate(CASES):
        A = st.random_matrix(n, n, nb, grid11, np.float32, seed=4001 + i)
        B = st.random_matrix(n, NRHS, nb, grid11, np.float32,
                             seed=5001 + i)
        X, LU, piv, info = st.gesv(A, B, TIER)
        a, b = np.asarray(A.to_dense()), np.asarray(B.to_dense())
        out[n, nb] = {"A": A, "B": B, "a": a, "b": b, "LU": LU,
                      "piv": piv, "info": int(info),
                      "x": np.asarray(X.to_dense()),
                      "x_ref": plain_solver.gesv(a, b, nb, "f32")}
    return out


def errors_in_eps(a, x, b):
    """``check.backward_errors``' formula, in float64."""
    a, x, b = (np.asarray(M, np.float64) for M in (a, x, b))
    r = a @ x - b
    out = {}
    for label, ord_ in (("inf", np.inf), ("fro", "fro")):
        def norm(M):
            return np.linalg.norm(M, ord=ord_)
        out[label] = norm(r) / (norm(a) * norm(x) + norm(b)) / check.EPS
    return out


@pytest.mark.parametrize("n,nb", CASES)
def test_off_the_fast_path_with_info_zero(solved, n, nb):
    s = solved[n, nb]
    assert getrf_mod._fast_path_mode(s["A"], "partial") is None
    assert s["info"] == 0
    assert s["x"].shape == (n, NRHS) and np.isfinite(s["x"]).all()


@pytest.mark.parametrize("n,nb", CASES)
def test_backward_errors_within_the_limit(solved, n, nb):
    s = solved[n, nb]
    got = errors_in_eps(s["a"], s["x"], s["b"])
    ref = errors_in_eps(s["a"], s["x_ref"], s["b"])
    assert all(v <= TOL_EPS for v in got.values()), got
    assert all(v <= TOL_EPS for v in ref.values()), ref
    # and the check the cell runs on the chip agrees with this float64
    # evaluation where its own f32 rounding is small beside the residual
    coarse = plain_solver.gesv(s["a"], s["b"], nb, "mxu_bf16")
    ours = errors_in_eps(s["a"], coarse, s["b"])
    theirs = check.backward_errors(s["a"], coarse, s["b"])
    for norm in ours:
        assert theirs[norm] / check.EPS == pytest.approx(ours[norm],
                                                         rel=1e-2)


@pytest.mark.parametrize("n,nb", CASES)
def test_x_agrees_with_the_plain_solver(solved, n, nb):
    s = solved[n, nb]
    cond = np.linalg.cond(s["a"].astype(np.float64), np.inf)
    apart = (np.linalg.norm(s["x"] - s["x_ref"], np.inf)
             / np.linalg.norm(s["x_ref"], np.inf))
    assert apart <= TOL_COND_EPS * cond * check.EPS, (apart, cond)


@pytest.mark.parametrize("n,nb", CASES)
def test_pivots_are_lapack_rows_and_padding_swaps_with_itself(solved,
                                                              n, nb):
    s = solved[n, nb]
    piv = np.asarray(s["piv"])
    mt = -(-n // nb)
    assert piv.shape == (mt, nb) and piv.dtype == np.int32
    rows = np.arange(mt * nb).reshape(mt, nb)
    real = rows < n
    assert (piv[~real] == rows[~real]).all()     # padded slots self-swap
    # a real step swaps with a real row at or below it
    assert (piv[real] >= rows[real]).all() and (piv[real] < n).all()
    # the stored factor's padded rows and columns hold zeros (trsm puts
    # the identity on the padded diagonal as it reads the tile,
    # masks.tile_diag_pad_identity, so the backward solve that starts
    # in the ragged tile divides by 1 there)
    tiles = np.asarray(s["LU"].data)[0, 0]       # [mt, nt, nb, nb]
    last = tiles[mt - 1, mt - 1]
    r = n - (mt - 1) * nb
    assert last[:r, :r].any()
    assert not last[r:, :].any() and not last[:, r:].any()
    assert not tiles[mt - 1, :, r:, :].any()
    assert not tiles[:, mt - 1, :, r:].any()


@pytest.mark.parametrize("n,nb", CASES)
def test_getrs_trans_on_the_same_factor(solved, n, nb):
    s = solved[n, nb]
    Xt = st.getrs(s["LU"], s["piv"], s["B"], st.Op.Trans, TIER)
    xt = np.asarray(Xt.to_dense())
    got = errors_in_eps(s["a"].T, xt, s["b"])
    assert all(v <= TOL_EPS for v in got.values()), got


@pytest.mark.parametrize("n,nb", [(1000, 384)])
def test_a_tier_down_fails_the_limit_the_sound_tier_passes(solved, n, nb):
    """The largest case, where the lowered trailing products show most
    steadily (two trailing updates of depth 384)."""
    s = solved[n, nb]
    lowered = plain_solver.gesv(s["a"], s["b"], nb, "bf16_3x")
    low = errors_in_eps(s["a"], lowered, s["b"])
    sound = errors_in_eps(s["a"], s["x"], s["b"])
    assert all(v <= TOL_EPS for v in sound.values()), sound
    assert all(v > TOL_EPS for v in low.values()), low
    assert low["fro"] > 5 * sound["fro"]


# ------------------------------------------------------------- dispatch

def _paths(spans):
    by_id = {s["id"]: s for s in spans}

    def path(s):
        names = [s["name"]]
        while s["parent"]:
            s = by_id[s["parent"]]
            names.append(s["name"])
        return "/".join(reversed(names))

    return [(path(s), s) for s in sorted(spans,
                                         key=lambda s: s["start_ns"])]


def test_the_span_tree_names_the_path_taken(grid11, monkeypatch):
    """A ragged solve goes ``getrf.prepare`` → one program → pivots
    replayed swap by swap; an exact multiple under ``SLATE_LU_FAST=1``
    (interpret mode) goes straight to the fast program and one gather.
    ``getrf.path{phase}`` counts both."""
    monkeypatch.setattr(tracing, "_profiling", lambda: True)
    was_metrics = obs.metrics_enabled()
    try:
        n, nb = 250, 96
        A = st.random_matrix(n, n, nb, grid11, np.float32, seed=4001)
        B = st.random_matrix(n, NRHS, nb, grid11, np.float32, seed=5001)
        obs.reset()             # the generators' roots are not the solve's
        obs.metrics_on()
        jax.block_until_ready(st.gesv(A, B, TIER))
        tree = _paths(obs.captured_spans())
        names = [p for p, _ in tree]
        assert names[0] == "slate.gesv"
        top = dict(tree)["slate.gesv/getrf"]
        assert top["labels"]["mt"] == 3
        assert top["labels"]["pad_rows"] == 3 * 96 - 250
        assert top["labels"]["precision"] == "bf16_6x"
        prepare = names.index("slate.gesv/getrf/getrf.prepare")
        chunk = names.index("slate.gesv/getrf/getrf.chunk")
        assert prepare < chunk
        assert tree[chunk][1]["labels"]["phase"] == "one_program"
        assert tree[prepare][1]["end_ns"] <= tree[chunk][1]["start_ns"]
        pivots = dict(tree)["slate.gesv/getrs/getrs.apply_pivots"]
        assert pivots["labels"]["kind"] == "swap_sim"
        assert pivots["labels"]["steps"] == 3 * 96
        assert pivots["labels"]["serial_steps"] == 96 + 3
        assert metrics.counter_value("getrs.apply_pivots",
                                     kind="swap_sim") == 1
        assert metrics.counter_value("getrf.path",
                                     phase="one_program") == 1

        monkeypatch.setenv("SLATE_LU_FAST", "1")
        n, nb = 384, 128
        A = st.random_matrix(n, n, nb, grid11, np.float32, seed=4002)
        B = st.random_matrix(n, NRHS, nb, grid11, np.float32, seed=5002)
        obs.reset()
        obs.metrics_on()
        X, LU, piv, info = st.gesv(A, B, TIER)
        assert int(info) == 0
        tree = _paths(obs.captured_spans())
        names = [p for p, _ in tree]
        assert not [p for p in names if p.endswith("getrf.prepare")]
        chunk = dict(tree)["slate.gesv/getrf.chunk"]
        assert chunk["labels"]["phase"] == "fast_path"
        pivots = dict(tree)["slate.gesv/getrs/getrs.apply_pivots"]
        assert pivots["labels"] == {"kind": "order_gather"}
        assert metrics.counter_value("getrs.apply_pivots",
                                     kind="order_gather") == 1
        assert metrics.counter_total("getrs.apply_pivots") == 1
        assert metrics.counter_value("getrf.path",
                                     phase="fast_path") == 1
        assert metrics.counter_total("getrf.path") == 1
    finally:
        if not was_metrics:
            obs.metrics_off()
        obs.reset()
