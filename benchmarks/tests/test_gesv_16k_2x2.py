"""The cell ``gesv_16k_2x2`` (PR 32) where no chip is there: at a
rehearsal size with the cell's sixteen block columns the plain solver a
tier down fails the cell's own limits and a broken timed path on the
2x2 comes out ``correct: false``; and the five readers the cell
brought, on the start of a trace recorded on the chip
(``recorded_gesv_16k_2x2.json``: the first device ops of one traced
solve at n=16384, nb=1024 on four TPU v5 lite, cut with
``cut_trace.py``: it ends inside the first chunk program) and on
hand-made traces that carry the module names the chip printed, which is
how ``getrs``'s programs, 0.4 s later in a solve, are reached."""

import argparse
import json
import os

import jax
import numpy as np
import pytest

import slate_tpu as slate
from benchmarks import run as bench_run
from benchmarks.harness import cells, flops, plain_solver
from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import (getrs_grid_s, lu_chunk_collective_s,
                                      lu_chunk_peak_share, lu_chunk_s,
                                      lu_factor_s,
                                      panel_gather_bytes_per_solve)
from benchmarks.tests.test_gesv_10000_nb384 import (errors_in_eps, span,
                                                    stale_after_warm_up,
                                                    three_solves)

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "gesv_16k_2x2"
N, NB, NRHS = 512, 32, 1        # sixteen block columns: eight chunks
# the plain control a size up, still sixteen block columns: at (512, 32)
# sixteen trailing updates of depth 32 at bf16_3x read 69-85 eps and
# 52-56, astride the cell's 83 and 53; at (1024, 64) 101-109 and 67-75
N_CONTROL, NB_CONTROL = 1024, 64
V5E_2X2 = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}

needs_four = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="the 2x2 needs four devices: XLA_FLAGS="
           "--xla_force_host_platform_device_count=4")


def test_the_cell_is_hpls_deployment():
    spec = cells.load_cell(CELL)
    config = spec["config"]
    assert (config["n"], config["nb"], config["nrhs"]) == (16384, 1024, 1)
    assert config["grid"] == [2, 2] and spec["chips"] == 4
    assert config["reduced"] == ["n"] and config["architecture"] is None
    assert spec["traffic"]["routine"] == "gesv"
    entry = next(c for c in cells.contract()["configs"]
                 if c["name"] == "hpl_f32_2x2")
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["n"]
    mine = {m["name"] for m in spec["per_layer"]}
    assert {"lu_chunk_s", "lu_chunk_peak_share", "lu_chunk_collective_s",
            "getrs_grid_s", "panel_gather_bytes_per_solve"} <= mine
    # nothing the benchmark had was made to list the new cell
    assert not {"collective_s", "collective_exposed_s", "lu_factor_s",
                "pivot_apply_s", "tri_solve_s", "host_syncs_per_solve",
                "idle_attributed_share"} & mine


# ------------------------------------------------ the control, by hand

@pytest.mark.parametrize("seed", (3, 2_147_483_659, 4_000_000_007))
def test_plain_f32_passes_and_a_tier_down_fails_the_cells_limits(seed):
    spec = cells.load_cell(CELL)
    limits = {"inf": spec["cell"]["tol_eps"],
              "fro": spec["cell"]["tol_fro_eps"]}
    rng = np.random.default_rng(seed)
    n, nb = N_CONTROL, NB_CONTROL
    A = rng.standard_normal((n, n)).astype(np.float32)
    B = rng.standard_normal((n, NRHS)).astype(np.float32)
    sound = errors_in_eps(A, plain_solver.gesv(A, B, nb, "f32"), B)
    lower = errors_in_eps(
        A, plain_solver.gesv(A, B, nb, spec["cell"]["control_tier"]), B)
    assert all(sound[norm] <= limits[norm] for norm in limits), sound
    assert all(lower[norm] > limits[norm] for norm in limits), \
        (lower, limits)             # correct: false by both norms
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


def pivots_not_applied(solve):
    """The fault a grid invites: the two triangular solves run on B as
    it stands, its rows never permuted (``getrs`` without
    ``getrs.apply_pivots``)."""
    def wrapped(A, B, opts=None):
        X, LU, piv, info = solve(A, B, opts)
        return slate.getrs_nopiv(LU, B, opts), LU, piv, info
    return wrapped


@needs_four
def test_a_sound_run_on_the_2x2_is_correct(monkeypatch, tmp_path):
    result = drive(monkeypatch, tmp_path)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"solve_s", "solve_p90_s", "setup_s"} <= set(result["metrics"])


@needs_four
@pytest.mark.parametrize("broken", [pivots_not_applied, stale_after_warm_up])
def test_a_broken_answer_on_the_2x2_is_not_correct(broken, monkeypatch,
                                                   tmp_path):
    result = drive(monkeypatch, tmp_path, broken)
    assert result["correct"] is False
    assert result["failed"] == 0        # the calls ran; the check caught it


# --------------------------------------------- the readers, on a trace

def recorded(name):
    with open(os.path.join(HERE, name), encoding="utf-8") as f:
        return tr.reduce(json.load(f))


def run_of(trace, device=V5E_2X2, spans=None):
    run = {"trace": trace, "device": device,
           "spec": {"config": {"n": 16384, "nrhs": NRHS},
                    "traffic": {"routine": "gesv"}}}
    if spans is not None:
        run["program_spans"] = spans
    return run


def test_the_chunk_readers_on_the_trace_recorded_on_the_2x2():
    """The first 23 ms of the first chunk program of one traced solve:
    step 0's panel crossing q (``psum.57``) and gathered over p
    (``all-gather.4``), then XLA's blocked ``lu`` of the tournament's
    row chunks on every device."""
    red = recorded("recorded_gesv_16k_2x2.json")
    assert sorted(red.devices) == [0, 1, 2, 3] and len(red.solves) == 1
    dev0 = red.first
    assert len(dev0.ops) == 257
    # the seven trivial programs that build piv0 and info0, then chunk 0
    assert [m[0] for m in dev0.modules][-1] == "jit__getrf_chunk_core"
    assert len(dev0.modules) == 8
    assert tr.total(dev0.where(tr.is_kernel)) == 0      # no Pallas here
    targets = {st.get("target") for _, _, _, st in dev0.ops}
    assert "LuDecompositionBlock" in targets
    collectives = {name for name, _, _, st in dev0.ops
                   if tr.is_collective(st)}
    assert collectives == {"psum.57", "all-gather.4"}
    run = run_of(red)
    chunk = lu_chunk_s.compute(run)
    assert chunk == pytest.approx(0.023180236, rel=1e-6)
    assert chunk <= dev0.modules[-1][2] - dev0.modules[-1][1]
    crossing = lu_chunk_collective_s.compute(run)
    assert crossing == pytest.approx(0.001480758, rel=1e-6)
    assert 0 < crossing <= chunk
    # the existing LU reader's prefix matches the chunk programs too
    assert lu_factor_s.compute(run) == pytest.approx(chunk)
    # the share divides by the chips of the trace (a cut trace's share
    # means nothing: the whole LU's flops over 23 ms of it)
    assert lu_chunk_peak_share.compute(run) == pytest.approx(
        100 * flops.getrf(16384) / (4 * 197e12) / chunk)
    cpu = {"platform": "cpu", "kind": "cpu", "count": 4}
    assert lu_chunk_peak_share.compute(run_of(red, cpu)) is None
    assert getrs_grid_s.compute(run) is None        # not reached yet
    for reader in (lu_chunk_s, lu_chunk_collective_s, getrs_grid_s,
                   panel_gather_bytes_per_solve):
        assert reader.compute({"trace": None}) is None


def test_another_cells_trace_gives_the_chunk_readers_nothing():
    posv = run_of(recorded("recorded_posv_16k_2x2.json"))
    assert lu_chunk_s.compute(posv) is None
    assert lu_chunk_peak_share.compute(posv) is None
    assert lu_chunk_collective_s.compute(posv) is None
    assert getrs_grid_s.compute(posv) is None       # ends in chunk 0
    one_chip = run_of(recorded("recorded_gesv_16k_1x1.json"))
    assert lu_chunk_s.compute(one_chip) is None     # jit__getrf_fast_core


def hand_trace(chunk="jit__getrf_chunk_core"):
    """Two solves of 760 ms on device 0 with the programs the chip
    printed for this cell (PR 32): eight chunk programs of 85 ms, busy
    83 of them, 7 of that in collectives; the pivot program 53 ms; two
    ``trsm`` of 7.5 ms, 3 of it in collectives, between converts."""
    ops, mods, solves = [], [], []
    for base in (0.0, 1.0):
        solves.append((base, base + 0.76))
        t = base + 0.001
        for _ in range(8):
            mods.append((chunk, t, t + 0.085))
            ops += [("psum.66", t, t + 0.003, {"opcode": "all-reduce"}),
                    ("all-gather.4", t + 0.003, t + 0.007,
                     {"opcode": "all-gather"}),
                    ("lu.3030", t + 0.007, t + 0.083,
                     {"opcode": "custom-call",
                      "target": "LuDecompositionBlock"})]
            t += 0.085
        mods += [("jit__apply_piv_jit", t, t + 0.053),
                 ("jit_convert_element_type", t + 0.053, t + 0.0531),
                 ("jit__trsm_left_jit", t + 0.0531, t + 0.0606),
                 ("jit_convert_element_type", t + 0.0606, t + 0.0607),
                 ("jit__trsm_left_jit", t + 0.0607, t + 0.0682)]
        ops += [("while.16", t, t + 0.053, {"opcode": "while"}),
                ("fusion.4", t + 0.001, t + 0.04, {"opcode": "fusion"}),
                ("all-reduce.3", t + 0.0531, t + 0.0561,
                 {"opcode": "all-reduce"}),
                ("fusion.7", t + 0.0561, t + 0.0606, {"opcode": "fusion"}),
                ("psum.70", t + 0.0607, t + 0.0637,
                 {"opcode": "all-reduce"}),
                ("fusion.7", t + 0.0637, t + 0.0682, {"opcode": "fusion"})]
    devices = {i: tr.DeviceTrace(ops=list(ops), modules=list(mods))
               for i in range(4)}
    return tr.Reduced(devices=devices, solves=solves)


@pytest.mark.parametrize("chunk", ["jit__getrf_chunk_core",
                                   "jit__getrf_pipe_chunk_core"])
def test_the_readers_split_a_solve_by_its_programs(chunk):
    red = hand_trace(chunk)
    run = run_of(red)
    lu = lu_chunk_s.compute(run)
    crossing = lu_chunk_collective_s.compute(run)
    solve = getrs_grid_s.compute(run)
    assert lu == pytest.approx(8 * 0.083)       # idle inside is not busy
    assert crossing == pytest.approx(8 * 0.007)     # getrs' are not in it
    assert solve == pytest.approx(0.053 + 2 * 0.0075)
    busy = tr.total(red.first.busy()) / len(red.solves)
    assert lu + solve == pytest.approx(busy)
    share = lu_chunk_peak_share.compute(run)
    assert share == pytest.approx(
        100 * flops.getrf(16384) / (4 * 197e12) / lu)
    # what the chip read: 0.5449 % at lu_chunk_s 0.68286 (PR 32)
    assert 100 * flops.getrf(16384) / (4 * 197e12) / 0.68286 == \
        pytest.approx(0.5449, rel=1e-3)
    assert share < 100 / 6


# ------------------------------- the reader of the getrf span's label

def spans_of(gathered):
    """Three solves whose ``getrf`` span carries ``panel_gather_bytes``
    = ``gathered[i]`` (None: the program has no such label)."""
    out = []
    for i, (base, nbytes) in enumerate(zip((0.0, 0.25, 0.5), gathered)):
        s, k = i + 1, 10 * i
        labels = {} if nbytes is None else {
            "pivoting": "tournament", "panel_rows": 16384,
            "panel_gather_bytes": nbytes}
        out += [span("slate.gesv", k + 1, 0, s, base, base + 0.19),
                span("getrf", k + 2, k + 1, s, base + 0.001, base + 0.14,
                     precision="bf16_6x", **labels),
                span("getrf.chunk", k + 4, k + 2, s, base + 0.01,
                     base + 0.012, phase="spmd_chunk", k0=0, klen=2),
                span("getrs", k + 5, k + 1, s, base + 0.14, base + 0.15)]
    return out


def test_panel_gather_bytes_is_the_label_of_the_getrf_span():
    closed_form = 16 * 16384 * 1024 * 4
    run = run_of(three_solves(), spans=spans_of([closed_form] * 3))
    assert panel_gather_bytes_per_solve.compute(run) == 1_073_741_824


def test_a_program_without_the_label_gives_nothing_to_read():
    """The parent commit (the driver runs the new cell on it too): no
    value and no error."""
    run = run_of(three_solves(), spans=spans_of([None] * 3))
    assert panel_gather_bytes_per_solve.compute(run) is None
    assert panel_gather_bytes_per_solve.compute(
        run_of(three_solves(), spans=[])) is None
