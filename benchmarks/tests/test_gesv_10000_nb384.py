"""The cell ``gesv_10000_nb384_1x1`` (PR 27) where no chip is there: at
a ragged rehearsal size the plain solver a tier down fails the cell's
own limits and a broken timed path comes out ``correct: false``; and
the four readers the cell brought, on the start of a trace recorded on
the chip (``recorded_gesv_10000_nb384_1x1.json``: the first 400 device
ops of one traced solve of the parent at n=10000, nb=384 on a TPU v5
lite, cut with ``cut_trace.py``) and on hand-made traces that carry the
module names the chip printed."""

import argparse
import json
import os

import jax
import numpy as np
import pytest

import slate_tpu as slate
from benchmarks import run as bench_run
from benchmarks.harness import (cells, check, flops, module_seconds,
                                plain_solver, trace_reduce as tr)
from benchmarks.layer_metrics import (getrf_prepare_host_s,
                                      lu_factor_peak_share, lu_factor_s,
                                      pivot_apply_s)

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "gesv_10000_nb384_1x1"
N, NB, NRHS = 625, 24, 8        # 27 tile rows, one real row in the last
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


# ------------------------------------------------ the control, by hand

def errors_in_eps(A, X, B):
    """``check.backward_errors``' formula, in float64 (XLA:CPU's f32
    accumulation is as large as the sound error at this size)."""
    A, X, B = (np.asarray(M, np.float64) for M in (A, X, B))
    R = A @ X - B
    out = {}
    for label, ord_ in (("inf", np.inf), ("fro", "fro")):
        def norm(M):
            return np.linalg.norm(M, ord=ord_)
        out[label] = norm(R) / (norm(A) * norm(X) + norm(B)) / check.EPS
    return out


@pytest.mark.parametrize("seed", (3, 2_147_483_659, 4_000_000_007))
def test_plain_f32_passes_and_a_tier_down_fails_the_cells_limits(seed):
    spec = cells.load_cell(CELL)
    assert (spec["config"]["n"], spec["config"]["nb"]) == (10000, 384)
    limits = {"inf": spec["cell"]["tol_eps"],
              "fro": spec["cell"]["tol_fro_eps"]}
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((N, N)).astype(np.float32)
    B = rng.standard_normal((N, NRHS)).astype(np.float32)
    sound = errors_in_eps(A, plain_solver.gesv(A, B, NB, "f32"), B)
    lower = errors_in_eps(
        A, plain_solver.gesv(A, B, NB, spec["cell"]["control_tier"]), B)
    assert all(sound[norm] <= limits[norm] for norm in limits), sound
    assert any(lower[norm] > limits[norm] for norm in limits), \
        (lower, limits)
    assert lower["fro"] > 5 * sound["fro"]


# ------------------------------------------------- a broken timed path

def drive(monkeypatch, tmp_path, broken=None):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    spec = cells.load_cell(CELL, n=N, nb=NB)
    if broken is not None:
        monkeypatch.setattr(slate, "gesv", broken(slate.gesv))
    args = argparse.Namespace(seed=2_400_000_011, seconds=0.5, trace=0,
                              keep_trace=None)
    return bench_run.run_cell(spec, jax.devices(), args, rehearsal=True)


def ragged_tile_row_unsolved(solve):
    """The fault this geometry invites: the last tile row of X (one
    real row of 24 here, 16 of 384 in the cell) left as B had it."""
    def wrapped(A, B, opts=None):
        out = solve(A, B, opts)
        X = out[0]
        data = X.data.at[0, 0, -1].set(B.data[0, 0, -1])
        return (X._replace(data=data),) + tuple(out[1:])
    return wrapped


def stale_after_warm_up(solve):
    """After the first call every answer is B itself."""
    calls = []

    def wrapped(A, B, opts=None):
        out = solve(A, B, opts)
        calls.append(1)
        return out if len(calls) == 1 else (B,) + tuple(out[1:])
    return wrapped


def test_a_sound_ragged_run_is_correct(monkeypatch, tmp_path):
    result = drive(monkeypatch, tmp_path)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    # (no allocator to read on the CPU: peak_hbm_gib is left out)
    assert {"solve_s", "solve_p90_s", "setup_s"} <= set(result["metrics"])


@pytest.mark.parametrize("broken", [ragged_tile_row_unsolved,
                                    stale_after_warm_up])
def test_a_broken_ragged_answer_is_not_correct(broken, monkeypatch,
                                               tmp_path):
    result = drive(monkeypatch, tmp_path, broken)
    assert result["correct"] is False
    assert result["failed"] == 0        # the calls ran; the check caught it


# --------------------------------------------- the readers, on a trace

def recorded(name):
    with open(os.path.join(HERE, name), encoding="utf-8") as f:
        return tr.reduce(json.load(f))


def run_of(trace, n, device=V5E, spans=None):
    run = {"trace": trace, "device": device,
           "spec": {"config": {"n": n, "nrhs": NRHS},
                    "traffic": {"routine": "gesv"}}}
    if spans is not None:
        run["program_spans"] = spans
    return run


def test_the_lu_readers_on_the_trace_recorded_at_n_10000():
    red = recorded("recorded_gesv_10000_nb384_1x1.json")
    dev0 = red.first
    assert len(dev0.ops) == 400 and len(red.solves) == 1
    assert [m[0] for m in dev0.modules] == ["jit__getrf_core"]
    # off the fast path the LU holds no Pallas kernel: its custom calls
    # are XLA's own (LuDecompositionBlock, InvertDiagBlocks...)
    assert tr.total(dev0.where(tr.is_kernel)) == 0
    assert any(st.get("target") == "LuDecompositionBlock"
               for _, _, _, st in dev0.ops)
    run = run_of(red, 10000)
    # every op so far ran inside the one module: its seconds are the
    # device's busy seconds, by the union the other readers use
    lu = lu_factor_s.compute(run)
    assert lu == pytest.approx(tr.total(dev0.busy()), rel=1e-9)
    assert lu == pytest.approx(0.0375876, rel=1e-3)
    assert lu <= dev0.modules[0][2] - dev0.modules[0][1]
    assert pivot_apply_s.compute(run) is None       # not reached yet
    share = lu_factor_peak_share.compute(run)
    assert share == pytest.approx(
        100 * (2 * 10000 ** 3 / 3) / 197e12 / lu)
    # a rehearsal's backend has no published peak
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert lu_factor_peak_share.compute(run_of(red, 10000, cpu)) is None
    assert lu_factor_s.compute({"trace": None}) is None
    assert pivot_apply_s.compute({"trace": None}) is None


def test_the_lu_reader_finds_the_fast_path_program_too():
    red = recorded("recorded_gesv_16k_1x1.json")
    assert lu_factor_s.compute(run_of(red, 16384)) == pytest.approx(
        0.033946399, rel=1e-6)
    posv = recorded("recorded_posv_16k_2x2.json")
    assert lu_factor_s.compute(run_of(posv, 16384)) is None
    assert lu_factor_peak_share.compute(run_of(posv, 16384)) is None
    assert pivot_apply_s.compute(run_of(posv, 16384)) is None


def hand_trace(pivot_module):
    """Two solves of 200 ms with the six programs the chip printed for
    this cell (PR 27): the LU busy 130 of its 135 ms, the pivot module
    33 ms, two ``trsm`` of 5.5 ms between trivial converts."""
    ops, mods, solves = [], [], []
    for base in (0.0, 0.25):
        solves.append((base, base + 0.2))
        mods += [("jit__getrf_core", base, base + 0.135),
                 (pivot_module, base + 0.135, base + 0.168),
                 ("jit_convert_element_type", base + 0.168, base + 0.1681),
                 ("jit__trsm_left_jit", base + 0.1681, base + 0.1736),
                 ("jit_convert_element_type", base + 0.1736, base + 0.1737),
                 ("jit__trsm_left_jit", base + 0.1737, base + 0.1792)]
        ops += [("fusion.1", base, base + 0.06, {"opcode": "fusion"}),
                ("custom-call.2", base + 0.065, base + 0.135,
                 {"opcode": "custom-call",
                  "target": "LuDecompositionBlock"}),
                ("while.11", base + 0.135, base + 0.168,
                 {"opcode": "while"}),
                ("fusion.5", base + 0.136, base + 0.16,
                 {"opcode": "fusion"}),
                ("fusion.7", base + 0.1681, base + 0.1736,
                 {"opcode": "fusion"}),
                ("fusion.7", base + 0.1737, base + 0.1792,
                 {"opcode": "fusion"})]
    return tr.Reduced(devices={0: tr.DeviceTrace(ops=ops, modules=mods)},
                      solves=solves)


@pytest.mark.parametrize("pivot_module", ["jit__apply_piv_jit",
                                          "jit__apply_order_jit"])
def test_the_readers_split_a_solve_by_its_programs(pivot_module):
    red = hand_trace(pivot_module)
    run = run_of(red, 10000)
    lu = lu_factor_s.compute(run)
    pivots = pivot_apply_s.compute(run)
    assert lu == pytest.approx(0.130)           # idle inside is not busy
    assert pivots == pytest.approx(0.033)       # the while spans its body
    trsm = module_seconds.per_solve(red, ("jit__trsm",))
    assert trsm == pytest.approx(0.011)
    busy = tr.total(red.first.busy()) / len(red.solves)
    assert lu + pivots + trsm == pytest.approx(busy)
    assert lu_factor_peak_share.compute(run) == pytest.approx(
        100 * flops.getrf(10000) / 197e12 / 0.130)
    assert lu_factor_peak_share.compute(run) < 100 / 6
    assert module_seconds.per_solve(red, ("jit__potrf",)) is None


# ----------------------------------------- the reader of getrf.prepare

OFFSET = -1000.0            # trace axis = perf_counter seconds - 1000


def span(name, sid, parent, solve, start, end, **labels):
    return {"name": name, "id": sid, "parent": parent, "solve": solve,
            "start_ns": int((start - OFFSET) * 1e9),
            "end_ns": int((end - OFFSET) * 1e9), "labels": labels}


def spans_of(prepare_s):
    """Three solves whose ``getrf.prepare`` lasts ``prepare_s[i]``
    (None: the program has no such span)."""
    out = []
    for i, (base, took) in enumerate(zip((0.0, 0.25, 0.5), prepare_s)):
        s, k = i + 1, 10 * i
        out += [span("slate.gesv", k + 1, 0, s, base, base + 0.19),
                span("getrf", k + 2, k + 1, s, base + 0.001, base + 0.14),
                span("getrf.chunk", k + 4, k + 2, s, base + 0.01,
                     base + 0.012, phase="one_program"),
                span("getrs", k + 5, k + 1, s, base + 0.14, base + 0.15)]
        if took is not None:
            out.append(span("getrf.prepare", k + 3, k + 2, s,
                            base + 0.001, base + 0.001 + took))
    return out


def three_solves():
    ops = [("fusion.1", b + 0.01, b + 0.18, {"opcode": "fusion"})
           for b in (0.0, 0.25, 0.5)]
    return tr.Reduced(devices={0: tr.DeviceTrace(ops=ops)},
                      solves=[(b, b + 0.2) for b in (0.0, 0.25, 0.5)])


def test_getrf_prepare_host_s_is_the_median_of_the_spans():
    run = run_of(three_solves(), 10000,
                 spans=spans_of([0.0004, 0.0002, 0.0090]))
    assert getrf_prepare_host_s.compute(run) == pytest.approx(0.0004,
                                                              rel=1e-6)


def test_a_program_without_the_span_gives_nothing_to_read():
    """The parent commit, or the LU fast path, which launches its
    program without ``getrf()``: no value and no error."""
    run = run_of(three_solves(), 10000, spans=spans_of([None] * 3))
    assert getrf_prepare_host_s.compute(run) is None
    assert getrf_prepare_host_s.compute(
        run_of(three_solves(), 10000, spans=[])) is None
    assert getrf_prepare_host_s.compute({"trace": None}) is None
