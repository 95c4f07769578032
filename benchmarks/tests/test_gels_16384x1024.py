"""The cell ``gels_16384x1024_1x1`` (PR 44) where no chip is there: the
contract (the cell is the issue's, every entry found by name),
``flops_ls``'s closed forms against hand counts, the plain reference
(``harness/plain_ls.py``) against the textbook and as the control (a
blocked Householder QR at ``bf16_3x`` fails what one at f32 passes), a
rehearsal whose answer is broken (a zeroed row of X, a stale X, an X
that solves another problem, another method than the configuration's)
comes out ``correct: false``, a program without the root span is
refused at session open, ``control.py`` sweeps the kind as it stands,
and the eight readers the cell brought, on a hand-made trace that
carries the module names the chip printed and on a trace recorded on
the chip (``recorded_gels_16384x1024_1x1.json``: one traced call at
m=16384, n=1024 on one TPU v5 lite, cut with ``cut_trace.py``)."""

import argparse
import json
import os

import jax
import numpy as np
import pytest

import slate_tpu as slate
from slate_tpu.linalg import geqrf
from benchmarks import control
from benchmarks import run as bench_run
from benchmarks.harness import cells, flops_ls, plain_ls
from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import (ls_apply_q_hbm_share, ls_apply_q_s,
                                      ls_factor_peak_share, ls_factor_s,
                                      ls_mxu_peak_share,
                                      ls_panel_peak_share, ls_panel_s,
                                      ls_tri_solve_s)
from benchmarks.tests.test_gesv_10000_nb384 import span
from benchmarks.traffic import closed_loop_ls

HERE = os.path.dirname(os.path.abspath(__file__))
CELL, CONFIG = "gels_16384x1024_1x1", "gels_qr_f32_1x1"
READERS = {"ls_factor_s": ls_factor_s,
           "ls_factor_peak_share": ls_factor_peak_share,
           "ls_panel_s": ls_panel_s,
           "ls_panel_peak_share": ls_panel_peak_share,
           "ls_apply_q_s": ls_apply_q_s,
           "ls_apply_q_hbm_share": ls_apply_q_hbm_share,
           "ls_tri_solve_s": ls_tri_solve_s,
           "ls_mxu_peak_share": ls_mxu_peak_share}
LISTLESS = {"first_call_s", "backend_compile_s", "compiles_in_window",
            "launches_per_solve", "host_gap_s", "device_idle_share",
            "trace_lower_s"}
N, NB = 64, 16                  # the rehearsal: m = 16 n = 1024
M, MR, NR, NRHS = 16384, 16 * N, 1024, 8
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
EPS = 2.0 ** -24


def by_name(entries, name):
    (found,) = [e for e in entries if e["name"] == name]
    return found


def test_the_cell_is_the_issues():
    spec = cells.load_cell(CELL)
    config, traffic, cell = spec["config"], spec["traffic"], spec["cell"]
    assert (config["m"], config["n"], config["nrhs"], config["nb"]) == (
        16384, 1024, 8, 256)
    assert config["m_over_n"] * config["n"] == config["m"]
    assert (config["dtype"], config["tier"], config["method_gels"]) == (
        "float32", "bf16_6x", "Geqrf")
    assert config["grid"] == [1, 1] and config["architecture"] is None
    assert spec["chips"] == 1 and config["reduced"] == ["m"]
    assert set(config["reduced_why"]) == {"m"}
    assert set(config["assumed"]) == {"nrhs", "nb", "dtype", "matrix"}
    assert set(config["departures"]) == {"precision", "panel",
                                         "flop_count"}
    for key in ("source", "stands_for", "method_why", "layout", "memory",
                "guarantee", "chosen_by_the_library", "operands"):
        assert len(config[key]) > 40, key
    # which program and which panel answer is the library's choice:
    # nothing names them, and no environment variable is set
    assert not {"program", "panel", "fast", "env", "qr_fast"} \
        & (set(config) | set(traffic))
    assert traffic == {**traffic, "kind": "closed_loop_ls",
                       "routine": "gels", "callers": 1,
                       "warm_up_calls": 2, "seed_offset": 0}
    assert cell["control_tier"] == "bf16_3x"
    assert cell["tol_eps"] == cell["tol_opt_eps"] > 0
    assert cell["tol_fro_eps"] == cell["tol_forward_eps"] > 0
    assert cell["tol_excess_eps"] > 0
    assert len(cell["tol_why"]) > 200 and cell["why"] and cell["who"]
    contract = cells.contract()
    entry = by_name(contract["configs"], CONFIG)
    assert entry == {"name": CONFIG, "source": config["source"],
                     "file": f"benchmarks/configs/{CONFIG}.json",
                     "reduced": ["m"], "why": entry["why"]}
    assert len(entry["source"]) <= 200 and "\n" not in entry["source"]
    assert by_name(contract["workloads"], CELL) == {
        "name": CELL, "config": CONFIG, "traffic": "closed_loop_gels_qr",
        "chips": 1, "why": by_name(contract["workloads"], CELL)["why"]}
    assert len(by_name(contract["workloads"], CELL)["why"]) <= 200
    assert sum(w["config"] == CONFIG for w in contract["workloads"]) == 1
    # the eight new metrics, each for this cell alone, found by name
    for name, reader in READERS.items():
        m = by_name(contract["per_layer"], name)
        assert m["workloads"] == [CELL] and m["moves"] == "solve_s"
        assert m["layer"] == "least squares"
        assert m["source"] == "device_trace"
        assert reader.HEADER == {k: v for k, v in m.items()
                                 if k != "workloads"}
    # no accepted metric took the cell into its list
    for m in contract["per_layer"]:
        if m["name"] not in READERS:
            assert CELL not in m.get("workloads", [])
    assert {m["name"] for m in spec["per_layer"]} == set(READERS) | LISTLESS
    assert {m["name"] for m in spec["end_to_end"]} == {
        "solve_s", "solve_p90_s", "peak_hbm_gib", "setup_s"}


def test_a_rehearsal_keeps_the_aspect():
    spec = cells.load_cell(CELL, n=N, nb=NB)
    session = closed_loop_ls.open_session(spec, jax.devices(), 7)
    assert (session.m, session.n, session.nb, session.nrhs) == (
        16 * N, N, NB, 8)
    assert (session.A.m, session.A.n) == (16 * N, N)
    assert (session.B.m, session.B.n) == (16 * N, 8)
    assert session.opts == {
        slate.Option.MethodGels: slate.MethodGels.Geqrf,
        slate.Option.TrailingPrecision: "bf16_6x"}
    # another seed, other operands; the same seed, the same
    again = closed_loop_ls.open_session(spec, jax.devices(), 7)
    other = closed_loop_ls.open_session(spec, jax.devices(), 3_000_000_019)
    assert np.array_equal(np.asarray(session.A.data),
                          np.asarray(again.A.data))
    assert not np.array_equal(np.asarray(session.B.data),
                              np.asarray(other.B.data))


def test_the_closed_forms():
    m, n, nrhs, nb = M, NR, NRHS, 256
    assert flops_ls.geqrf(m, n) == pytest.approx(2 * m * n * n
                                                 - 2 * n ** 3 / 3)
    assert flops_ls.geqr2_panels(m, n, nb) == pytest.approx(sum(
        2 * h * 256 ** 2 - 2 * 256 ** 3 / 3
        for h in (16384, 16128, 15872, 15616)))
    assert flops_ls.unmqr(m, n, nrhs) == pytest.approx(
        4 * m * nrhs * n - 2 * nrhs * n * n)
    assert flops_ls.trsm(n, nrhs) == n * n * nrhs
    assert flops_ls.gels(m, n, nrhs) == pytest.approx(
        33.64e9 + 0.520e9 + 0.0084e9, rel=1e-3)
    assert flops_ls.unmqr_bytes(m, n, nrhs, nb) == pytest.approx(
        4 * (m * n - n * n / 2 + 2 * 4 * m * nrhs))


# ------------------------------------------------ the plain reference

def problem(seed, m=512, n=128, nrhs=3):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((m, n)).astype(np.float32),
            rng.standard_normal((m, nrhs)).astype(np.float32))


def in_eps(a, b, x):
    return {k: v / EPS for k, v in
            plain_ls.numbers(a, b, x, plain_ls.reference(a, b)).items()}


def test_the_numbers_against_the_textbook():
    a, b = problem(5)
    ref = plain_ls.reference(a, b)
    exact = np.linalg.lstsq(a.astype(np.float64), b.astype(np.float64),
                            rcond=None)[0]
    assert np.allclose(ref, exact, rtol=1e-12, atol=1e-14)
    at_ref = in_eps(a, b, ref)
    assert at_ref["optimality"] < 1e-6 and at_ref["forward"] == 0
    assert at_ref["residual_excess"] == 0
    rounded = in_eps(a, b, ref.astype(np.float32))
    assert rounded["optimality"] < 1 and rounded["forward"] < 1
    # each number reads the fault it is there for
    zeroed = ref.copy()
    zeroed[7] = 0.0
    assert in_eps(a, b, zeroed)["residual_excess"] > 1e3
    assert in_eps(a, b, zeroed)["optimality"] > 1e4
    other = plain_ls.reference(a, b[::-1].copy())   # solves another B
    assert in_eps(a, b, other)["forward"] > 1e6
    assert all(np.isnan(v) for v in in_eps(a, b, ref[:-1]).values())


@pytest.mark.parametrize("seed", (3, 2_147_483_659, 4_000_000_007))
def test_f32_passes_and_a_tier_down_fails(seed):
    """The control where no chip is there. The cell's limits are set at
    m=16384 on the chip; at this size the principle is what is held:
    the optimality and the forward error tell the tiers apart, the
    residual's excess (second order) guards the minimum."""
    a, b = problem(seed)
    sound = in_eps(a, b, plain_ls.gels_qr(a, b, 32, "f32"))
    lower = in_eps(a, b, plain_ls.gels_qr(a, b, 32, "bf16_3x"))
    assert sound["optimality"] < 2 and sound["forward"] < 30
    assert lower["optimality"] > 2.5 * sound["optimality"]
    assert lower["forward"] > 2.5 * sound["forward"]
    limits = {k: 1.6 * sound[k] for k in sound}     # between the two
    assert all(sound[k] <= limits[k] for k in limits)
    assert not all(lower[k] <= limits[k] for k in limits)


# ------------------------------------------------- a broken timed path

REHEARSAL_LIMITS = {"tol_opt_eps": 4.0, "tol_forward_eps": 80.0,
                    "tol_excess_eps": 1e-4}


def drive(monkeypatch, tmp_path, broken=None, config=None):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    spec = cells.load_cell(CELL, n=N, nb=NB)
    # a rehearsal's limits: the chip's are for m=16384 on the MXU
    spec["cell"].update(REHEARSAL_LIMITS)
    spec["config"].update(config or {})
    if broken is not None:
        monkeypatch.setattr(slate, "gels", broken(slate.gels))
    args = argparse.Namespace(seed=2_400_000_011, seconds=0.5, trace=0,
                              keep_trace=None)
    rows = []
    monkeypatch.setattr(bench_run, "say", lambda **line: rows.append(line))
    return bench_run.run_cell(spec, jax.devices(), args,
                              rehearsal=True), rows


def one_row_zeroed(solve):
    def wrapped(A, B, opts=None):
        X = solve(A, B, opts)
        return X._replace(data=X.data.at[..., 0, :].set(0.0))
    return wrapped


def stale_after_warm_up(solve):
    """After the first call every X is the answer to another B."""
    calls = []

    def wrapped(A, B, opts=None):
        calls.append(1)
        if len(calls) == 1:
            return solve(A, B, opts)
        return solve(A, B._replace(data=B.data[..., ::-1, :]), opts)
    return wrapped


def the_librarys_auto(solve):
    """A right answer that is not this deployment's: CholQR."""
    def wrapped(A, B, opts=None):
        return solve(A, B, {**opts, slate.Option.MethodGels:
                            slate.MethodGels.Auto})
    return wrapped


def row(rows, name):
    (found,) = [r for r in rows if r.get("check") == name]
    return found


def test_a_sound_rehearsal_is_correct(monkeypatch, tmp_path):
    result, rows = drive(monkeypatch, tmp_path)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"solve_s", "solve_p90_s", "setup_s"} <= set(result["metrics"])
    assert len([r for r in rows if str(r.get("check", "")).startswith(
        "ls_")]) == 6               # three numbers, two answers
    said = row(rows, "ls.program")
    assert said["method"] == {"Geqrf": 2}           # the warm-ups
    assert sum(said["program"].values()) == 2 == sum(
        said["panel"].values())
    assert row(rows, "ls.shape")["value"] == 2
    # the counters were on for the warm-up alone
    from slate_tpu import obs
    assert not obs.metrics_enabled()


@pytest.mark.parametrize("broken, failing", [
    (one_row_zeroed, "ls_residual_excess.last"),
    (stale_after_warm_up, "ls_forward.last"),
    (the_librarys_auto, "ls.program")])
def test_a_broken_answer_is_not_correct(broken, failing, monkeypatch,
                                        tmp_path):
    result, rows = drive(monkeypatch, tmp_path, broken)
    assert result["correct"] is False
    assert result["failed"] == 0        # the calls ran; the check caught it
    assert row(rows, failing)["ok"] is False
    if broken is the_librarys_auto:     # and nothing else did
        assert all(r["ok"] for r in rows if r.get("check") != failing
                   and "check" in r)
        assert row(rows, failing)["method"] == {"Cholqr": 2}
    if broken is stale_after_warm_up:
        assert row(rows, "ls_forward.warm_up")["ok"] is True


def test_a_program_without_the_root_span_is_refused(monkeypatch):
    """The parent commit (the driver tries the new cell on it first):
    non-zero at session open, before any operand is made."""
    spec = cells.load_cell(CELL, n=N, nb=NB)
    made = []
    monkeypatch.setattr(slate, "random_matrix",
                        lambda *a, **k: made.append(1))
    for missing in ("SPANS", "COUNTERS"):
        with monkeypatch.context() as m:
            m.delattr(geqrf, missing)
            with pytest.raises(SystemExit) as refusal:
                closed_loop_ls.open_session(spec, jax.devices(), 7)
        assert refusal.value.code not in (0, None)
        assert "slate.gels" in str(refusal.value.code) and not made


def test_control_py_sweeps_the_cell_as_it_stands(monkeypatch, capsys,
                                                 tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    seen = []
    real = slate.gels
    monkeypatch.setattr(slate, "gels", lambda A, B, opts=None: (
        seen.append(dict(opts)), real(A, B, opts))[1])
    assert control.main(["--workload", CELL, "--seeds", "1", "--tiers",
                         "bf16_3x", "--rehearse-on-cpu", "--n", str(N),
                         "--nb", str(NB)]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    # the timed call is given the method and the tier, and nothing else
    qr = slate.MethodGels.Geqrf
    assert seen[0] == {slate.Option.MethodGels: qr,
                       slate.Option.TrailingPrecision: "bf16_6x"}
    assert seen[-1] == {slate.Option.MethodGels: qr,
                        slate.Option.TrailingPrecision: "bf16_3x"}
    assert len(seen) == 4           # two warm-ups, one call, the control
    readings = [ln for ln in lines if "tier" in ln]
    assert [r["tier"] for r in readings] == ["bf16_6x", "bf16_3x"]
    errors = [ln for ln in lines if ln.get("step") == "ls_errors"]
    assert [e["answer"] for e in errors] == ["warm_up", "last", "control"]
    assert all(set(e["in_eps"]) == set(closed_loop_ls.LIMITS)
               for e in errors)
    # inf and fro are two of the three numbers of the same line
    assert readings[1]["in_eps"]["inf"] == pytest.approx(
        errors[2]["in_eps"]["optimality"])
    assert readings[1]["in_eps"]["fro"] == pytest.approx(
        errors[2]["in_eps"]["forward"])
    assert lines[-1]["bf16_6x"]["role"] == "sound"
    assert lines[-1]["limits_eps"] == {
        "inf": cells.load_cell(CELL)["cell"]["tol_opt_eps"],
        "fro": cells.load_cell(CELL)["cell"]["tol_forward_eps"]}


# --------------------------------------------- the readers, on a trace

STAGES = (("jit__geqrf_fast_core", 0.0080), ("jit__unmqr_jit", 0.0020),
          ("jit_dynamic_slice", 0.0001), ("jit__trsm_left_jit", 0.0004))
KERNEL = {"opcode": "custom-call", "target": "tpu_custom_call"}


def hand_trace(factor="jit__geqrf_fast_core", kernels=True):
    """Two calls on device 0 with the programs the chip printed for
    this cell, one after the other with 0.1 ms between; inside the
    factorization eight panel kernels of 0.6 ms (none when the panels
    are XLA's), and a kernel of another program outside it."""
    ops, mods, solves, spans = [], [], [], []
    for i, base in enumerate((0.0, 1.0)):
        t = base + 0.0002
        sid = 100 * (i + 1)
        for name, dur in STAGES:
            name = factor if name == "jit__geqrf_fast_core" else name
            mods.append((name, t, t + dur))
            ops.append((f"fusion.{len(ops)}", t, t + dur,
                        {"opcode": "fusion"}))
            if name == factor and kernels:
                for k in range(8):
                    ops.append((f"custom-call.{k}", t + 0.001 * k,
                                t + 0.001 * k + 0.0006, KERNEL))
            if name == "jit_dynamic_slice":
                ops.append(("custom-call.99", t, t + 0.00005, KERNEL))
            t += dur + 0.0001
        solves.append((base, t + 0.0001))
        spans.append(span("slate.gels", sid, 0, i + 1, base + 0.0001, t,
                          routine="gels", m=M, n=NR, nrhs=NRHS, nb=256,
                          grid="1x1", method="Geqrf", tier="bf16_6x",
                          program="fast", panel="pallas"))
    red = tr.Reduced(devices={0: tr.DeviceTrace(ops=ops, modules=mods)},
                     solves=solves)
    return red, spans


def run_of(trace, device=V5E, spans=None, n=NR, nb=256):
    run = {"trace": trace, "device": device, "spec": {
        "config": {"m_over_n": 16, "n": n, "nrhs": NRHS, "nb": nb,
                   "dtype": "float32"},
        "traffic": {"routine": "gels"}}}
    if spans is not None:
        run["program_spans"] = spans
    return run


def test_the_readers_split_a_call_by_its_stages():
    red, spans = hand_trace()
    run = run_of(red, spans=spans)
    assert ls_factor_s.compute(run) == pytest.approx(0.0080)
    assert ls_panel_s.compute(run) == pytest.approx(8 * 0.0006)
    assert ls_apply_q_s.compute(run) == pytest.approx(0.0020)
    assert ls_tri_solve_s.compute(run) == pytest.approx(0.0004)
    busy = sum(dur for _, dur in STAGES)
    staged = (ls_factor_s.compute(run) + ls_apply_q_s.compute(run)
              + ls_tri_solve_s.compute(run))
    assert staged == pytest.approx(busy - 0.0001)       # the eager slice
    peak, hbm = 197e12, 819e9
    assert ls_factor_peak_share.compute(run) == pytest.approx(
        100 * flops_ls.geqrf(M, NR) / peak / 0.0080)
    assert ls_panel_peak_share.compute(run) == pytest.approx(
        100 * flops_ls.geqr2_panels(M, NR, 256) / peak / 0.0048)
    assert ls_apply_q_hbm_share.compute(run) == pytest.approx(
        100 * flops_ls.unmqr_bytes(M, NR, NRHS, 256) / hbm / 0.0020)
    assert ls_mxu_peak_share.compute(run) == pytest.approx(
        100 * flops_ls.gels(M, NR, NRHS) / peak / busy)
    for name, reader in READERS.items():
        if name.endswith("_share"):
            assert 0 < reader.compute(run) < 100
    assert ls_factor_peak_share.compute(run) < 100 / 6


def test_the_readers_leave_out_what_they_cannot_read():
    red, spans = hand_trace()
    untraced = run_of(None)
    for reader in READERS.values():
        assert reader.compute(untraced) is None
    # the SPMD program is read under its own name, and holds no kernel:
    # the panel readers then read nothing, the rest read on
    spmd, _ = hand_trace("jit__geqrf_jit", kernels=False)
    run = run_of(spmd, spans=spans)
    assert ls_factor_s.compute(run) == pytest.approx(0.0080)
    assert ls_panel_s.compute(run) is None
    assert ls_panel_peak_share.compute(run) is None
    assert ls_mxu_peak_share.compute(run) > 0
    # a rehearsal's backend has no published peak
    cpu = run_of(red, {"platform": "cpu", "kind": "cpu", "count": 1}, spans)
    for name, reader in READERS.items():
        assert (reader.compute(cpu) is None) == name.endswith("_share")
    # a rehearsal's shape keeps m / n
    assert ls_factor_peak_share.shape_of(run_of(red, n=N, nb=NB)) == (
        16 * N, N, NRHS, NB)


def recorded(name):
    with open(os.path.join(HERE, name), encoding="utf-8") as f:
        return tr.reduce(json.load(f))


def test_the_readers_on_the_trace_recorded_on_the_chip():
    """One whole traced call (my chip run, PR 44, call 1, seed 4400011):
    16 programs, the exact-shape QR with its eight panel kernels, then
    ``unmqr``, the thirteen eager programs of the two ``sub()`` copies,
    then the one ``trsm``."""
    red = recorded("recorded_gels_16384x1024_1x1.json")
    assert sorted(red.devices) == [0] and len(red.solves) == 1
    dev0 = red.first
    assert len(dev0.ops) == 1683
    names = [m[0] for m in sorted(dev0.modules, key=lambda m: m[1])]
    assert len(names) == 16
    assert names[:2] == ["jit__geqrf_fast_core", "jit__unmqr_jit"]
    assert names[-1] == "jit__trsm_left_jit"
    assert set(names[2:-1]) == {"jit_transpose", "jit_dynamic_slice",
                                "jit__pad", "jit_convert_element_type"}
    kernels = [o for o in dev0.ops if tr.is_kernel(o[3])]
    assert len(kernels) == 8                        # 4 panels x 2
    assert all(o[0].startswith("qr_panel") for o in kernels)
    run = run_of(red, spans=[])
    factor = ls_factor_s.compute(run)
    panel = ls_panel_s.compute(run)
    apply_q = ls_apply_q_s.compute(run)
    solve_r = ls_tri_solve_s.compute(run)
    assert factor == pytest.approx(0.0092797, rel=1e-4)
    assert panel == pytest.approx(0.0066349, rel=1e-4)     # 6.5 us a column
    assert apply_q == pytest.approx(0.00082359, rel=1e-4)
    assert solve_r == pytest.approx(0.00011192, rel=1e-4)
    busy = tr.total(dev0.busy())
    assert busy == pytest.approx(0.0108908, rel=1e-4)
    # the three stages account for 93.8 % of the busy time; the rest is
    # the eager copies of R and of the top rows of Q^T B
    assert 0.93 < (factor + apply_q + solve_r) / busy < 0.95
    assert ls_factor_peak_share.compute(run) == pytest.approx(1.8404, rel=1e-3)
    assert ls_factor_peak_share.compute(run) < 100 / 6
    assert ls_panel_peak_share.compute(run) == pytest.approx(0.63836, rel=1e-3)
    assert ls_apply_q_hbm_share.compute(run) == pytest.approx(10.26, rel=1e-3)
    assert ls_mxu_peak_share.compute(run) == pytest.approx(1.5928, rel=1e-3)


def test_another_cells_trace_gives_the_readers_nothing():
    gesv = run_of(recorded("recorded_gesv_16k_1x1.json"), spans=[])
    for name, reader in READERS.items():
        if name != "ls_mxu_peak_share":     # busy seconds are any cell's
            assert reader.compute(gesv) is None, name
