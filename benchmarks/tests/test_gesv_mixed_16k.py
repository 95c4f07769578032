"""The cell ``gesv_mixed_16k_1x1`` (PR 35) where no chip is there: the
plain reference (``harness/plain_refine.py``) passes the cell's own
limits refined and in plain f32 and fails them with the refinement left
out; a rehearsal whose call falls back, does not converge or answers
with a stale X comes out ``correct: false``; a program that cannot
report a refinement's outcome is refused at session open; and the six
readers the cell brought, on the start of a trace recorded on the chip
(``recorded_gesv_mixed_16k_1x1.json``: the first device ops of a traced
call at n=16384, nb=1024 on one TPU v5 lite, cut with ``cut_trace.py``)
and on a hand-made trace that carries the module names the chip
printed."""

import argparse
import json
import os

import jax
import numpy as np
import pytest

import slate_tpu as slate
from slate_tpu.linalg import mixed
from benchmarks import run as bench_run
from benchmarks.harness import (cells, flops, plain_refine, plain_solver,
                                refine_spans)
from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import (lu_factor_s, mixed_factor_peak_share,
                                      mixed_factor_s,
                                      refine_host_syncs_per_solve,
                                      refine_matvec_s, refine_solve_s,
                                      refine_steps_per_solve, tri_solve_s)
from benchmarks.tests.test_gesv_10000_nb384 import (errors_in_eps, span,
                                                    stale_after_warm_up,
                                                    three_solves)
from benchmarks.traffic import closed_loop_refine

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "gesv_mixed_16k_1x1"
CALL = "gesv_mixed_gmres"
NEW_METRICS = ("mixed_factor_s", "mixed_factor_peak_share",
               "refine_solve_s", "refine_matvec_s",
               "refine_steps_per_solve", "refine_host_syncs_per_solve")
N, NB = 512, 32                 # the rehearsal: sixteen block columns
N_CONTROL, NB_CONTROL = 2048, 128
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_the_cell_is_hpl_mxps_deployment():
    spec = cells.load_cell(CELL)
    config, traffic = spec["config"], spec["traffic"]
    assert (config["n"], config["nb"], config["nrhs"]) == (16384, 1024, 1)
    assert config["grid"] == [1, 1] and spec["chips"] == 1
    # the low leg is the library's own default for an f32 system
    assert (config["tier"], config["working_tier"]) == ("bf16_3x",
                                                        "bf16_6x")
    assert config["tier"] == mixed._lo_plan(
        np.float32, None)[1][slate.Option.TrailingPrecision]
    # the matrix and the stop criterion are the library's own too
    assert not {"diagonal_shift", "tolerance"} & set(config)
    assert set(config["assumed"]) == {"nb", "dtype", "tier"}
    assert config["reduced"] == ["n"] and config["architecture"] is None
    # the work counted is HPL's; the function called is named beside it
    assert (traffic["kind"], traffic["routine"], traffic["call"]) == (
        "closed_loop_refine", "gesv", CALL)
    # what the configuration states are the library's own defaults
    assert config["max_iterations"] == slate.types.get_option(
        None, slate.Option.MaxIterations)
    assert config["restart"] == mixed.GMRES_RESTART
    contract = cells.contract()
    entry = contract["configs"][-1]
    assert entry["name"] == "hpl_mxp_f32_1x1"
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["n"]
    assert contract["workloads"][-1]["name"] == CELL
    # the six new metrics close the list, each for this cell alone
    assert tuple(m["name"] for m in contract["per_layer"][-6:]) == NEW_METRICS
    for m in contract["per_layer"][-6:]:
        assert m["workloads"] == [CELL] and m["moves"] == "solve_s"
    mine = {m["name"] for m in spec["per_layer"]}
    assert set(NEW_METRICS) | {"mxu_peak_share", "device_idle_share",
                               "host_gap_s", "launches_per_solve"} <= mine
    # nothing the benchmark had was made to list the new cell
    assert not {"lu_factor_s", "pivot_apply_s", "tri_solve_s",
                "host_syncs_per_solve", "idle_attributed_share"} & mine


# ------------------------------------------------ the control, by hand

@pytest.mark.parametrize("seed", (3, 2_147_483_659, 4_000_000_007))
def test_refined_passes_and_unrefined_fails_the_cells_limits(seed):
    spec = cells.load_cell(CELL)
    limits = {"inf": spec["cell"]["tol_eps"],
              "fro": spec["cell"]["tol_fro_eps"]}
    rng = np.random.default_rng(seed)
    n, nb = N_CONTROL, NB_CONTROL
    A = rng.standard_normal((n, n)).astype(np.float32)
    B = rng.standard_normal((n, 1)).astype(np.float32)
    LU, order = plain_refine.lu_factor(A, nb, spec["config"]["tier"])
    x, report = plain_refine.gmres_ir(A, B, LU, order)
    x0, report0 = plain_refine.gmres_ir(A, B, LU, order, refine=False)
    refined = errors_in_eps(A, x[:, None], B)
    unrefined = errors_in_eps(A, x0[:, None], B)
    plain = errors_in_eps(A, plain_solver.gesv(A, B, nb, "f32"), B)
    assert report["converged"] and 1 <= sum(report["steps"]) < 30
    assert not report0["converged"]
    assert all(refined[norm] <= limits[norm] for norm in limits), refined
    assert all(plain[norm] <= limits[norm] for norm in limits), plain
    assert all(unrefined[norm] > limits[norm] for norm in limits), \
        (unrefined, limits)         # correct: false by both norms
    assert unrefined["fro"] > 5 * refined["fro"]


# ------------------------------------------------- a broken timed path

def drive(monkeypatch, tmp_path, broken=None):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    spec = cells.load_cell(CELL, n=N, nb=NB)
    if broken is not None:
        monkeypatch.setattr(slate, CALL, broken(getattr(slate, CALL)))
    args = argparse.Namespace(seed=2_400_000_011, seconds=0.5, trace=0,
                              keep_trace=None)
    rows = []
    monkeypatch.setattr(bench_run, "say", lambda **line: rows.append(line))
    return bench_run.run_cell(spec, jax.devices(), args,
                              rehearsal=True), rows


def answered_by_the_fallback(solve):
    """A right answer that is not this deployment's: ``iters`` as the
    program returns it when the full-precision solver answered."""
    def wrapped(A, B, opts=None):
        X, iters, info = solve(A, B, opts)
        return X, -31, info
    return wrapped


def fallback_once(solve):
    """One call of the window falls back; the rest, and the X that is
    checked, are sound."""
    calls = []

    def wrapped(A, B, opts=None):
        X, iters, info = solve(A, B, opts)
        calls.append(1)
        return X, (-31 if len(calls) == 3 else iters), info
    return wrapped


def row(rows, name):
    (found,) = [r for r in rows if r.get("check") == name]
    return found


def test_a_sound_rehearsal_is_correct(monkeypatch, tmp_path):
    result, rows = drive(monkeypatch, tmp_path)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"solve_s", "setup_s"} <= set(result["metrics"])
    assert row(rows, "refine.fallbacks")["value"] == 0
    converged = row(rows, "refine.converged")
    # every call counted, the warm-up's two among them
    assert converged["value"] == converged["limit"] == \
        result["attempted"] + 2


@pytest.mark.parametrize("broken", [answered_by_the_fallback,
                                    fallback_once, stale_after_warm_up])
def test_a_fallback_or_a_stale_answer_is_not_correct(broken, monkeypatch,
                                                     tmp_path):
    result, rows = drive(monkeypatch, tmp_path, broken)
    assert result["correct"] is False
    assert result["failed"] == 0        # the calls ran; the check caught it
    if broken is not stale_after_warm_up:
        assert row(rows, "refine.fallbacks")["ok"] is False
        assert row(rows, "refine.converged")["ok"] is False
        assert all(r["ok"] for r in rows
                   if str(r.get("check", "")).startswith("backward_error"))


def test_the_programs_own_counter_is_read_too(monkeypatch, tmp_path):
    from slate_tpu import obs
    was = obs.metrics_enabled()
    obs.metrics_on()
    try:
        obs.count("mixed.fallback", 1, routine=CALL)
        result, rows = drive(monkeypatch, tmp_path)
    finally:
        obs.reset()
        if not was:
            obs.metrics_off()
    assert result["correct"] is False
    assert row(rows, "refine.fallbacks")["counter"] == 1
    assert row(rows, "refine.converged")["ok"] is True


def test_a_program_that_cannot_report_the_outcome_is_refused(monkeypatch):
    """The parent commit (the driver tries the new cell on it first):
    non-zero at session open, before any operand is made."""
    spec = cells.load_cell(CELL, n=N, nb=NB)
    monkeypatch.delattr(mixed, "COUNTERS")
    made = []
    monkeypatch.setattr(slate, "random_matrix",
                        lambda *a, **k: made.append(1))
    with pytest.raises(SystemExit) as refusal:
        closed_loop_refine.open_session(spec, jax.devices(), 7)
    assert refusal.value.code not in (0, None)
    assert "mixed.fallback" in str(refusal.value.code) and not made


def test_the_control_is_the_configuration_without_refinement(monkeypatch):
    spec = cells.load_cell(CELL, n=N, nb=NB)
    session = closed_loop_refine.open_session(spec, jax.devices(), 11)
    seen = []
    real = slate.getrf
    monkeypatch.setattr(slate, "getrf", lambda A, opts=None: (
        seen.append(opts), real(A, opts))[1])
    X = session.lower_precision("unrefined")
    assert seen == [{slate.Option.TrailingPrecision: "bf16_3x"}]
    errors = session.errors_of({"control": X})["control"]
    assert errors["inf"] < 1e-5         # an LU's answer (true f32 here)
    session.lower_precision("mxu_bf16")
    assert seen[-1] == {slate.Option.TrailingPrecision: "mxu_bf16"}
    # what the timed call is given: the low leg's tier and nothing else
    assert session.opts == {slate.Option.TrailingPrecision: "bf16_3x"}


# --------------------------------------------- the readers, on a trace

def recorded(name):
    with open(os.path.join(HERE, name), encoding="utf-8") as f:
        return tr.reduce(json.load(f))


def run_of(trace, device=V5E, spans=None, call=CALL):
    traffic = {"routine": "gesv"}
    if call:
        traffic["call"] = call
    run = {"trace": trace, "device": device,
           "spec": {"config": {"n": 16384, "nrhs": 1}, "traffic": traffic}}
    if spans is not None:
        run["program_spans"] = spans
    return run


def hand_trace():
    """Two calls of 300 ms on device 0 with the programs the chip
    printed for this cell (PR 35): the LU fast path 170 ms; three
    applications of the factors, each an order gather of 0.5 ms and two
    ``trsm`` of 7.5; three ``gemm`` of 17 ms; vector passes between."""
    ops, mods, solves = [], [], []
    for base in (0.0, 1.0):
        solves.append((base, base + 0.3))
        t = base + 0.002
        mods.append(("jit__getrf_fast_core", t, t + 0.17))
        ops.append(("fusion.51", t, t + 0.17, {"opcode": "fusion"}))
        t += 0.171
        for _ in range(3):
            mods += [("jit__apply_order_jit", t, t + 0.0005),
                     ("jit__trsm_left_jit", t + 0.001, t + 0.0085),
                     ("jit__trsm_left_jit", t + 0.009, t + 0.0165),
                     ("jit__gemm_jit", t + 0.017, t + 0.034),
                     ("jit__norm_jit", t + 0.0345, t + 0.0347)]
            ops += [("gather.1", t, t + 0.0005, {"opcode": "gather"}),
                    ("while.3", t + 0.001, t + 0.0085, {"opcode": "while"}),
                    ("while.4", t + 0.009, t + 0.0165, {"opcode": "while"}),
                    ("fusion.9", t + 0.017, t + 0.034, {"opcode": "fusion"}),
                    ("fusion.2", t + 0.0345, t + 0.0347,
                     {"opcode": "fusion"})]
            t += 0.036
    return tr.Reduced(devices={0: tr.DeviceTrace(ops=ops, modules=mods)},
                      solves=solves)


def test_the_readers_split_a_call_by_its_programs():
    red = hand_trace()
    run = run_of(red)
    factor = mixed_factor_s.compute(run)
    solves = refine_solve_s.compute(run)
    matvec = refine_matvec_s.compute(run)
    assert factor == pytest.approx(0.17)
    assert solves == pytest.approx(3 * (0.0005 + 2 * 0.0075))
    assert matvec == pytest.approx(3 * 0.017)
    busy = tr.total(red.first.busy()) / len(red.solves)
    assert factor + solves + matvec == pytest.approx(busy - 3 * 0.0002)
    # the accepted readers agree where they read the same programs
    assert lu_factor_s.compute(run) == pytest.approx(factor)
    assert tri_solve_s.compute(run) == pytest.approx(3 * 2 * 0.0075)
    share = mixed_factor_peak_share.compute(run)
    assert share == pytest.approx(100 * flops.getrf(16384) / 197e12 / 0.17)
    assert share < 100 / 3          # three passes a product
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert mixed_factor_peak_share.compute(run_of(red, cpu)) is None
    for reader in (mixed_factor_s, mixed_factor_peak_share, refine_solve_s,
                   refine_matvec_s, refine_steps_per_solve,
                   refine_host_syncs_per_solve):
        assert reader.compute({"trace": None, "device": V5E, "spec": {
            "config": {"n": 16384}, "traffic": {"call": CALL}}}) is None


def test_the_readers_on_the_trace_recorded_on_the_chip():
    """The first 33 ms of one traced call: ‖A‖∞ (``jit__norm_jit``),
    its blocking read, then the start of the LU fast path at three bf16
    passes; the factors are not applied yet and no product with A has
    run."""
    red = recorded("recorded_gesv_mixed_16k_1x1.json")
    assert sorted(red.devices) == [0] and len(red.solves) == 1
    dev0 = red.first
    assert len(dev0.ops) == 400
    assert [m[0] for m in dev0.modules] == ["jit__norm_jit",
                                            "jit__getrf_fast_core"]
    assert tr.total(dev0.where(tr.is_kernel)) > 0       # the Pallas panel
    run = run_of(red)
    factor = mixed_factor_s.compute(run)
    lu = dev0.modules[-1]
    assert 0.02 < factor <= lu[2] - lu[1]
    assert factor == pytest.approx(lu_factor_s.compute(run))
    assert mixed_factor_peak_share.compute(run) == pytest.approx(
        100 * flops.getrf(16384) / 197e12 / factor)
    assert refine_solve_s.compute(run) is None          # not reached yet
    assert refine_matvec_s.compute(run) is None
    # the device sits idle between the norm and the LU: the host reads
    # the norm before it launches anything else
    norm = dev0.modules[0]
    assert lu[1] - norm[2] > 1e-4


def test_another_cells_trace_gives_the_gemm_reader_nothing():
    gesv = run_of(recorded("recorded_gesv_16k_1x1.json"), call=None)
    assert refine_matvec_s.compute(gesv) is None
    assert refine_steps_per_solve.compute(gesv) is None
    assert refine_host_syncs_per_solve.compute(gesv) is None


# ------------------------------ the readers of the root span's labels

def spans_of(outcomes, root="slate." + CALL):
    """Three calls whose root carries ``outer`` / ``inner`` as
    ``outcomes[i]``, with one blocking read for ‖A‖∞, two a residual
    check, one for beta and j + 2 an Arnoldi step."""
    out = []
    for i, (base, (outer, inner)) in enumerate(zip((0.0, 0.25, 0.5),
                                                   outcomes)):
        s, k = i + 1, 100 * i
        labels = {"routine": CALL, "n": 16384, "nb": 1024, "nrhs": 1,
                  "grid": "1x1", "tier_lo": "bf16_3x", "outer": outer,
                  "inner": inner, "converged": 1, "fallback": 0}
        reads = 1 + 2 * (outer + 1) + outer + sum(
            j + 2 for j in range(inner))
        out += [span(root, k + 1, 0, s, base, base + 0.19, **labels),
                span("mixed.factor_lo", k + 2, k + 1, s, base + 0.001,
                     base + 0.002),
                span("mixed.solve_lo", k + 3, k + 1, s, base + 0.002,
                     base + 0.003, phase="initial")]
        out += [span("mixed.h", k + 10 + r, k + 1, s,
                     base + 0.01 + 0.001 * r, base + 0.0105 + 0.001 * r,
                     sync=1) for r in range(reads)]
    return out


def test_steps_and_syncs_are_read_from_the_root_of_the_call():
    run = run_of(three_solves(), spans=spans_of([(1, 2), (1, 1), (2, 5)]))
    # inner + outer + 1 applications of the factors: 4, 3, 8
    assert refine_steps_per_solve.compute(run) == 4
    # 1 + 2·2 + 1 + (2 + 3) = 11; 1 + 4 + 1 + 2 = 8; 1 + 6 + 2 + 20 = 29
    assert refine_host_syncs_per_solve.compute(run) == 11
    assert refine_spans.as_call(run)["spec"]["traffic"]["routine"] == CALL


def test_a_program_without_the_root_gives_nothing():
    """The parent commit (its mixed solvers open no ``slate.*`` root):
    no value and no error."""
    legacy = run_of(three_solves(),
                    spans=spans_of([(1, 1)] * 3, root="gesv_mixed_gmres"))
    assert refine_steps_per_solve.compute(legacy) is None
    assert refine_host_syncs_per_solve.compute(legacy) is None
    assert refine_steps_per_solve.compute(
        run_of(three_solves(), spans=[])) is None
