"""The cell ``heev_8192_vec_1x1`` (PR 41) where no chip is there: the
contract (the cell is the issue's), ``flops_eig``'s closed forms, the
plain reference (``harness/plain_eig.py``) against the textbook and as
the control (a band reduction at ``bf16_3x`` fails by the residual what
one at f32 passes), a rehearsal whose answer is broken (a zeroed column
of Z, shuffled eigenvalues, a stale Z, a demoted rung) comes out
``correct: false``, a program without the root span is refused at
session open, ``control.py`` sweeps the kind as it stands, and the ten
readers the cell brought, on a hand-made trace that carries the module
names the chip printed and on the start of a trace recorded on the chip
(``recorded_heev_8192_vec_1x1.json``: the first device ops of a traced
call at n=8192 on one TPU v5 lite, cut with ``cut_trace.py``)."""

import argparse
import json
import os

import jax
import numpy as np
import pytest

import slate_tpu as slate
from slate_tpu.linalg import eig
from slate_tpu.robust import ladder
from benchmarks import control
from benchmarks import run as bench_run
from benchmarks.harness import cells, flops_eig, plain_eig
from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import (eig_back_hbm_share,
                                      eig_back_transform_s,
                                      eig_band_reduce_peak_share,
                                      eig_band_reduce_s,
                                      eig_chase_peak_share, eig_chase_s,
                                      eig_host_syncs_per_solve,
                                      eig_merge_device_s,
                                      eig_mxu_peak_share, eig_tridiag_s)
from benchmarks.tests.test_gesv_10000_nb384 import span
from benchmarks.traffic import closed_loop_eig

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "heev_8192_vec_1x1"
NEW_METRICS = ("eig_band_reduce_s", "eig_band_reduce_peak_share",
               "eig_chase_s", "eig_chase_peak_share", "eig_tridiag_s",
               "eig_merge_device_s", "eig_back_transform_s",
               "eig_back_hbm_share", "eig_host_syncs_per_solve",
               "eig_mxu_peak_share")
READERS = (eig_band_reduce_s, eig_band_reduce_peak_share, eig_chase_s,
           eig_chase_peak_share, eig_tridiag_s, eig_merge_device_s,
           eig_back_transform_s, eig_back_hbm_share,
           eig_host_syncs_per_solve, eig_mxu_peak_share)
SIX = ["gesv_16k_1x1", "posv_16k_1x1", "posv_16k_2x2",
       "gesv_10000_nb384_1x1", "gesv_16k_2x2", "gesv_mixed_16k_1x1"]
N, NB = 384, 64                 # the rehearsal
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
EPS = 2.0 ** -24


def test_the_cell_is_the_issues():
    spec = cells.load_cell(CELL)
    config, traffic = spec["config"], spec["traffic"]
    assert (config["n"], config["nb"], config["dtype"]) == (
        8192, 256, "float32")
    assert (config["jobz"], config["uplo"], config["method_eig"]) == (
        "V", "Lower", "DC")
    assert config["tier"] == "bf16_6x" and config["grid"] == [1, 1]
    assert spec["chips"] == 1 and config["architecture"] is None
    assert config["reduced"] == ["n"]
    assert set(config["assumed"]) == {"nb", "dtype", "matrix"}
    # the band and the rung are the library's choice: nothing names them
    assert not {"band", "eig_band", "hb2st", "chase_backend", "env"} \
        & (set(config) | set(traffic))
    assert (traffic["kind"], traffic["routine"], traffic["jobz"],
            traffic["callers"], traffic["warm_up_calls"]) == (
        "closed_loop_eig", "heev", "V", 1, 2)
    assert spec["cell"]["control_tier"] == "bf16_3x"
    contract = cells.contract()
    entry = contract["configs"][-1]
    assert entry["name"] == "heev_twostage_f32_1x1"
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200 and entry["reduced"] == ["n"]
    assert contract["workloads"][-1] == {
        "name": CELL, "config": "heev_twostage_f32_1x1",
        "traffic": "closed_loop_heev_vec", "chips": 1,
        "why": contract["workloads"][-1]["why"]}
    assert len(contract["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in contract["workloads"]) == 2
    # the ten new metrics close the list, each for this cell alone
    assert tuple(m["name"] for m in contract["per_layer"][-10:]) \
        == NEW_METRICS
    for m, reader in zip(contract["per_layer"][-10:], READERS):
        assert m["workloads"] == [CELL] and m["moves"] == "solve_s"
        assert reader.HEADER == {k: v for k, v in m.items()
                                 if k != "workloads"}
    # mxu_peak_share's reader knows no heev: it keeps the six it read
    (mxu,) = [m for m in contract["per_layer"]
              if m["name"] == "mxu_peak_share"]
    assert mxu["workloads"] == SIX
    mine = {m["name"] for m in spec["per_layer"]}
    assert mine == set(NEW_METRICS) | {
        "first_call_s", "backend_compile_s", "compiles_in_window",
        "launches_per_solve", "host_gap_s", "device_idle_share",
        "trace_lower_s"}
    assert {m["name"] for m in spec["end_to_end"]} == {
        "solve_s", "solve_p90_s", "peak_hbm_gib", "setup_s"}


def test_the_closed_forms():
    n, b = 8192, 128
    assert flops_eig.he2hb(n) == pytest.approx(4 * n ** 3 / 3)
    assert flops_eig.hb2st(n, b) == pytest.approx(6 * n * n * b)
    assert flops_eig.stedc(n) == pytest.approx(4 * n ** 3 / 3)
    assert flops_eig.unmtr_hb2st(n) == flops_eig.unmtr_he2hb(n) \
        == pytest.approx(2 * n ** 3)
    assert flops_eig.heev_vectors(n, b) == pytest.approx(
        (20 / 3) * n ** 3 + 6 * n * n * b)
    assert flops_eig.unmtr_hb2st_bytes(n, b) == pytest.approx(
        8 * n ** 3 / b)
    # 42 ms at the published bandwidth; 5.4 s one sweep at a time
    assert flops_eig.unmtr_hb2st_bytes(n, b) / 819e9 == pytest.approx(
        0.04195, abs=1e-4)


# ------------------------------------------------ the plain reference

def symmetric(seed, n=256):
    rng = np.random.default_rng(seed)
    g = rng.standard_normal((n, n))
    return (g @ g.T / n + np.eye(n)).astype(np.float32)


def in_eps(a, lam, z):
    import jax.numpy as jnp
    numbers = plain_eig.equations(jnp.asarray(a), lam, jnp.asarray(z),
                                  block=96)
    numbers["values_max"] = plain_eig.values_error(
        lam, plain_eig.reference_values(a))
    return {k: v / EPS for k, v in numbers.items()}


def test_the_equations_against_the_textbook():
    a = symmetric(5)
    lam, z = np.linalg.eigh(a.astype(np.float64))
    exact = in_eps(a, lam, z.astype(np.float32))
    assert all(v < 5 for v in exact.values()), exact
    # each number reads the fault it is there for
    z1 = z.astype(np.float32).copy()
    z1[:, 7] = 0.0
    assert in_eps(a, lam, z1)["orth_fro"] > 1e4
    assert in_eps(a, lam, z1)["residual_max"] < 2
    lam2 = lam.copy()
    lam2[[3, 200]] = lam2[[200, 3]]
    shuffled = in_eps(a, lam2, z.astype(np.float32))
    assert shuffled["residual_max"] > 1e4 and shuffled["values_max"] > 1e4
    assert shuffled["orth_fro"] == pytest.approx(exact["orth_fro"])
    assert not plain_eig.ascending(lam2) and plain_eig.ascending(lam)
    assert not plain_eig.ascending(np.array([1.0, np.nan]))
    assert np.isnan(plain_eig.values_error(lam[:-1], lam))
    full = np.asarray(plain_eig.symmetric_of(np.tril(a)))
    assert np.array_equal(full, a)


@pytest.mark.parametrize("seed", (3, 2_147_483_659, 4_000_000_007))
def test_f32_passes_and_a_tier_down_fails_by_the_residual(seed):
    """The control where no chip is there. The cell's limits are set at
    n=8192 on the chip; at this size the principle is what is held:
    the residual and the values tell the tiers apart, the
    orthogonality does not."""
    a = symmetric(seed)
    sound = in_eps(a, *plain_eig.eig_via_band(a, 32, "f32"))
    lower = in_eps(a, *plain_eig.eig_via_band(a, 32, "bf16_3x"))
    assert sound["residual_fro"] < 4 and sound["values_max"] < 8
    assert lower["residual_fro"] > 4 * sound["residual_fro"]
    assert lower["residual_max"] > 4 * sound["residual_max"]
    assert lower["orth_fro"] < 4 and sound["orth_fro"] < 4


# ------------------------------------------------- a broken timed path

def drive(monkeypatch, tmp_path, broken=None):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    spec = cells.load_cell(CELL, n=N, nb=NB)
    # a rehearsal's limits: the chip's are for n=8192 on the MXU
    spec["cell"].update(tol_eps=64.0, tol_fro_eps=16.0, tol_orth_eps=256.0,
                        tol_values_eps=64.0)
    if broken is not None:
        monkeypatch.setattr(slate, "heev", broken(slate.heev))
    args = argparse.Namespace(seed=2_400_000_011, seconds=0.5, trace=0,
                              keep_trace=None)
    rows = []
    monkeypatch.setattr(bench_run, "say", lambda **line: rows.append(line))
    return bench_run.run_cell(spec, jax.devices(), args,
                              rehearsal=True), rows


def one_column_zeroed(solve):
    def wrapped(A, opts=None):
        lam, Z = solve(A, opts)
        return lam, Z._replace(data=Z.data.at[..., 0].set(0.0))
    return wrapped


def values_shuffled(solve):
    def wrapped(A, opts=None):
        lam, Z = solve(A, opts)
        lam = np.array(lam)
        lam[[1, -2]] = lam[[-2, 1]]
        return lam, Z
    return wrapped


def stale_after_warm_up(solve):
    """After the first call every Z is the identity's tiles: A's own."""
    calls = []

    def wrapped(A, opts=None):
        lam, Z = solve(A, opts)
        calls.append(1)
        return (lam, Z) if len(calls) == 1 else (
            lam, Z._replace(data=A.retile(Z.nb).data))
    return wrapped


def demoted_rung(solve):
    """A right answer that is not this deployment's: the rung the
    ladder preferred was stepped past."""
    def wrapped(A, opts=None):
        ladder.record_demotion(ladder.Demotion(
            "hb2st", "vmem", "wave", "made to raise"))
        return solve(A, opts)
    return wrapped


def row(rows, name):
    (found,) = [r for r in rows if r.get("check") == name]
    return found


def test_a_sound_rehearsal_is_correct(monkeypatch, tmp_path):
    result, rows = drive(monkeypatch, tmp_path)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"solve_s", "setup_s"} <= set(result["metrics"])
    assert len([r for r in rows if str(r.get("check", "")).startswith(
        "eig_")]) == 8              # four numbers, two answers
    last = row(rows, "eig.demotions")
    assert last["value"] == 0 and last["merges"] >= 7
    assert 0 <= last["deflated_share"] < 1
    assert sum(last["chase_backend"].values()) == 2     # the warm-ups
    assert row(rows, "eig.ascending")["value"] == 2
    # the counters were on for the warm-up alone
    from slate_tpu import obs
    assert not obs.metrics_enabled()


@pytest.mark.parametrize("broken, failing", [
    (one_column_zeroed, "eig_orth_fro.last"),
    (values_shuffled, "eig.ascending"),
    (stale_after_warm_up, "eig_residual_fro.last"),
    (demoted_rung, "eig.demotions")])
def test_a_broken_answer_is_not_correct(broken, failing, monkeypatch,
                                        tmp_path):
    before = len(ladder.demotion_log())
    try:
        result, rows = drive(monkeypatch, tmp_path, broken)
    finally:
        kept = ladder.demotion_log()[:before]
        ladder.clear_demotion_log()
        ladder.restore_demotions(
            [{"ladder": d.ladder, "from_rung": d.from_rung,
              "to_rung": d.to_rung, "reason": d.reason} for d in kept])
    assert result["correct"] is False
    assert result["failed"] == 0        # the calls ran; the check caught it
    assert row(rows, failing)["ok"] is False
    if broken is demoted_rung:          # and nothing else did
        assert all(r["ok"] for r in rows if r.get("check") != failing
                   and "check" in r)
    if broken is stale_after_warm_up:
        assert row(rows, "eig_residual_fro.warm_up")["ok"] is True


def test_a_program_without_the_root_span_is_refused(monkeypatch):
    """The parent commit (the driver tries the new cell on it first):
    non-zero at session open, before any operand is made."""
    spec = cells.load_cell(CELL, n=N, nb=NB)
    made = []
    monkeypatch.setattr(slate, "random_spd", lambda *a, **k: made.append(1))
    for missing in ("SPANS", "COUNTERS"):
        with monkeypatch.context() as m:
            m.delattr(eig, missing)
            with pytest.raises(SystemExit) as refusal:
                closed_loop_eig.open_session(spec, jax.devices(), 7)
        assert refusal.value.code not in (0, None)
        assert "slate.heev" in str(refusal.value.code) and not made


def test_control_py_sweeps_the_cell_as_it_stands(monkeypatch, capsys,
                                                 tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    seen = []
    real = slate.heev
    monkeypatch.setattr(slate, "heev", lambda A, opts=None: (
        seen.append(dict(opts)), real(A, opts))[1])
    assert control.main(["--workload", CELL, "--seeds", "1", "--tiers",
                         "bf16_3x", "--rehearse-on-cpu", "--n", str(N),
                         "--nb", str(NB)]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    # the timed call is given the method and the tier, and nothing else
    dc = slate.MethodEig.DC
    assert seen[0] == {slate.Option.MethodEig: dc,
                       slate.Option.TrailingPrecision: "bf16_6x"}
    assert seen[-1] == {slate.Option.MethodEig: dc,
                        slate.Option.TrailingPrecision: "bf16_3x"}
    assert len(seen) == 4           # two warm-ups, one call, the control
    readings = [ln for ln in lines if "tier" in ln]
    assert [r["tier"] for r in readings] == ["bf16_6x", "bf16_3x"]
    assert all(0 < r["in_eps"]["fro"] < 16 for r in readings)
    errors = [ln for ln in lines if ln.get("step") == "eig_errors"]
    assert [e["answer"] for e in errors] == ["warm_up", "last", "control"]
    assert all(set(e["in_eps"]) == set(closed_loop_eig.LIMITS)
               for e in errors)
    # inf and fro are the two residual numbers of the same line
    assert readings[1]["in_eps"]["inf"] == pytest.approx(
        errors[2]["in_eps"]["residual_max"])
    assert readings[1]["in_eps"]["fro"] == pytest.approx(
        errors[2]["in_eps"]["residual_fro"])
    assert lines[-1]["bf16_6x"]["role"] == "sound"


# --------------------------------------------- the readers, on a trace

STAGES = (("jit__he2hb_jit", 0.40), ("jit__gather_tiles_jit", 0.001),
          ("jit__hb2st_vmem_jit", 2.40), ("jit__leaves_jit", 0.01),
          ("jit__zrows_jit", 0.001), ("jit__secular_jit", 0.08),
          ("jit__merge_jit", 0.12), ("jit__apply_bulge_jit", 0.30),
          ("jit__to_tiles", 0.002), ("jit__unmtr_he2hb_jit", 0.15))


def hand_trace():
    """Two calls on device 0 with the programs the chip printed for
    this cell, one after the other with 1 ms between, and the program's
    spans of them (the root with its labels, the six phases, three
    blocking reads under ``heev.tridiag``)."""
    ops, mods, solves, spans = [], [], [], []
    for i, base in enumerate((0.0, 20.0)):
        t = base + 0.002
        sid = 100 * (i + 1)
        tri = [None, None]
        for name, dur in STAGES:
            if name == "jit__leaves_jit":
                tri[0] = t - 0.0005
            mods.append((name, t, t + dur))
            ops.append((f"fusion.{len(ops)}", t, t + dur,
                        {"opcode": "fusion"}))
            t += dur + 0.001
            if name == "jit__merge_jit":
                tri[1] = t + 0.05       # the host walks on a little
                t = tri[1]
        solves.append((base, t + 0.001))
        spans.append(span("slate.heev", sid, 0, i + 1, base + 0.0005, t,
                          routine="heev", n=8192, nb=256, grid="1x1",
                          jobz="V", method="DC", path="two_stage",
                          band=128, chase_backend="vmem"))
        spans.append(span("heev.tridiag", sid + 1, sid, i + 1, *tri,
                          phase="eig_solve", n=8192))
        for j, site in enumerate(("stedc.zrow", "stedc.roots",
                                  "stedc.zrow")):
            spans.append(span(site, sid + 2 + j, sid + 1, i + 1,
                              tri[0] + 0.01 * j, tri[0] + 0.01 * j + 0.001,
                              sync=1))
    red = tr.Reduced(devices={0: tr.DeviceTrace(ops=ops, modules=mods)},
                     solves=solves)
    return red, spans


def run_of(trace, device=V5E, spans=None):
    run = {"trace": trace, "device": device, "spec": {
        "config": {"n": 8192, "dtype": "float32"},
        "traffic": {"routine": "heev"}}}
    if spans is not None:
        run["program_spans"] = spans
    return run


def test_the_readers_split_a_call_by_its_stages():
    red, spans = hand_trace()
    run = run_of(red, spans=spans)
    seconds = dict(STAGES)
    n = 8192
    assert eig_band_reduce_s.compute(run) == pytest.approx(0.40)
    assert eig_chase_s.compute(run) == pytest.approx(2.40)
    assert eig_merge_device_s.compute(run) == pytest.approx(
        0.01 + 0.001 + 0.08 + 0.12)
    assert eig_back_transform_s.compute(run) == pytest.approx(0.45)
    busy = sum(seconds.values())
    staged = (eig_band_reduce_s.compute(run) + eig_chase_s.compute(run)
              + eig_merge_device_s.compute(run)
              + eig_back_transform_s.compute(run))
    assert staged == pytest.approx(busy - 0.003)    # gather, to_tiles
    assert eig_band_reduce_peak_share.compute(run) == pytest.approx(
        100 * (4 * n ** 3 / 3) / 197e12 / 0.40)
    assert eig_chase_peak_share.compute(run) == pytest.approx(
        100 * 6 * n * n * 128 / 197e12 / 2.40)
    assert eig_back_hbm_share.compute(run) == pytest.approx(
        100 * (8 * n ** 3 / 128 / 819e9) / 0.30)
    assert eig_mxu_peak_share.compute(run) == pytest.approx(
        100 * flops_eig.heev_vectors(n, 128) / 197e12 / busy)
    for share in (eig_band_reduce_peak_share, eig_chase_peak_share,
                  eig_back_hbm_share, eig_mxu_peak_share):
        assert 0 < share.compute(run) < 100
    # the span's wall: from before the leaves to after the last merge
    assert eig_tridiag_s.compute(run) == pytest.approx(
        0.0005 + 0.01 + 0.001 + 0.001 + 0.001 + 0.08 + 0.001 + 0.12
        + 0.001 + 0.05)
    assert eig_host_syncs_per_solve.compute(run) == 3


def test_the_readers_leave_out_what_they_cannot_read():
    red, spans = hand_trace()
    untraced = {"trace": None, "device": V5E, "spec": {
        "config": {"n": 8192, "dtype": "float32"},
        "traffic": {"routine": "heev"}}}
    for reader in READERS:
        assert reader.compute(untraced) is None
    # a program without captured spans (no session): the device readers
    # still read, the span readers and the ones that need the band do not
    bare = run_of(red, spans=[])
    assert eig_chase_s.compute(bare) == pytest.approx(2.40)
    for reader in (eig_tridiag_s, eig_host_syncs_per_solve,
                   eig_chase_peak_share, eig_back_hbm_share,
                   eig_mxu_peak_share):
        assert reader.compute(bare) is None
    # a rehearsal's backend has no published peak
    cpu = run_of(red, {"platform": "cpu", "kind": "cpu", "count": 1}, spans)
    for reader in (eig_band_reduce_peak_share, eig_chase_peak_share,
                   eig_back_hbm_share, eig_mxu_peak_share):
        assert reader.compute(cpu) is None
    # a demoted chase is read under the wave's name
    wave = tr.Reduced(devices={0: tr.DeviceTrace(
        ops=red.first.ops, modules=[
            ("jit__hb2st_wave_jit" if m[0] == "jit__hb2st_vmem_jit"
             else m[0],) + m[1:] for m in red.first.modules])},
        solves=red.solves)
    assert eig_chase_s.compute(run_of(wave, spans=spans)) \
        == pytest.approx(2.40)
    # a call that opened no heev.tridiag span is a fault, not a zero
    no_tridiag = [s for s in spans if s["name"] != "heev.tridiag"]
    with pytest.raises(ValueError, match="heev.tridiag"):
        eig_tridiag_s.compute(run_of(red, spans=no_tridiag))


def recorded(name):
    with open(os.path.join(HERE, name), encoding="utf-8") as f:
        return tr.reduce(json.load(f))


def test_the_readers_on_the_trace_recorded_on_the_chip():
    """The first 11 ms of one traced call (my chip run, PR 41): the
    re-tiling of A from 256 to the chase band (eight trivial programs,
    5.7 ms), then the start of the band reduction: its first panel's
    QR, column by column. Nothing past stage 1 has run."""
    red = recorded("recorded_heev_8192_vec_1x1.json")
    assert sorted(red.devices) == [0] and len(red.solves) == 1
    dev0 = red.first
    assert len(dev0.ops) == 1800
    names = [m[0] for m in dev0.modules]
    assert names[-1] == "jit__he2hb_jit"
    assert set(names[:-1]) == {"jit_reshape", "jit_transpose"}
    assert tr.total(dev0.where(tr.is_kernel)) == 0      # no Pallas yet
    run = run_of(red, spans=[])
    stage1 = eig_band_reduce_s.compute(run)
    he2hb = dev0.modules[-1]
    assert 0.004 < stage1 <= he2hb[2] - he2hb[1]
    assert eig_band_reduce_peak_share.compute(run) == pytest.approx(
        100 * flops_eig.he2hb(8192) / 197e12 / stage1)
    for reader in (eig_chase_s, eig_merge_device_s, eig_back_transform_s,
                   eig_back_hbm_share):
        assert reader.compute(run) is None              # not reached yet
    # the re-tiling is on the device's clock too, and in no stage
    busy = tr.total(dev0.busy())
    assert 0.004 < busy - stage1 < 0.007


def test_the_fast_walk_reads_what_module_seconds_reads():
    from benchmarks.harness import busy_inside, module_seconds
    hand, _ = hand_trace()
    for red in (hand, recorded("recorded_heev_8192_vec_1x1.json"),
                recorded("recorded_gesv_16k_1x1.json")):
        for prefixes in (("jit__he2hb_jit",), ("jit__getrf",),
                         ("jit__secular_jit", "jit__merge_jit"),
                         ("jit_",), ("no_such_program",)):
            slow = module_seconds.per_solve(red, prefixes)
            fast = busy_inside.per_solve(red, prefixes)
            assert (slow is None) == (fast is None)
            if slow is not None:
                assert fast == pytest.approx(slow, rel=1e-12)
    assert busy_inside.overlap([(0, 2), (3, 5)], [(1, 4)]) == 2
    assert busy_inside.overlap([], [(1, 4)]) == 0


def test_another_cells_trace_gives_the_readers_nothing():
    with open(os.path.join(HERE, "recorded_gesv_16k_1x1.json"),
              encoding="utf-8") as f:
        gesv = run_of(tr.reduce(json.load(f)), spans=[])
    for reader in READERS:
        assert reader.compute(gesv) is None
