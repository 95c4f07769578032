"""The cell ``gesvd_12288x8192_vec_1x1`` (PR 48) where no chip is there:
the contract (the cell is the issue's, its files found by name),
``flops_svd``'s closed forms, the plain reference
(``harness/plain_svd.py``) against the textbook and as the control (a
band reduction at ``bf16_3x`` fails by the residual what one at f32
passes), a rehearsal whose answer is broken (a zeroed column of U, a
swapped pair, sigma ascending, a stale answer, a demoted rung) comes out
``correct: false``, a program without the root span is refused at
session open, ``control.py`` sweeps the kind as it stands, and the ten
readers the cell brought, on a hand-made trace that carries the module
names the chip printed and on the start of a trace recorded on the chip
(``recorded_gesvd_12288x8192_vec_1x1.json``: the first device ops of a
traced call at m=12288, n=8192 on one TPU v5 lite, cut with
``cut_trace.py``)."""

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import slate_tpu as slate
from slate_tpu.linalg import svd
from slate_tpu.robust import ladder
from benchmarks import control
from benchmarks import run as bench_run
from benchmarks.harness import cells, flops_svd, plain_svd
from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import (svd_back_hbm_share,
                                      svd_back_transform_s,
                                      svd_band_reduce_peak_share,
                                      svd_band_reduce_s, svd_bidiag_device_s,
                                      svd_bidiag_s, svd_chase_peak_share,
                                      svd_chase_s, svd_host_syncs_per_solve,
                                      svd_mxu_peak_share)
from benchmarks.tests.test_gesv_10000_nb384 import span
from benchmarks.traffic import closed_loop_svd

HERE = os.path.dirname(os.path.abspath(__file__))
CELL, CONFIG = "gesvd_12288x8192_vec_1x1", "gesvd_twostage_f32_1x1"
READERS = (svd_band_reduce_s, svd_band_reduce_peak_share, svd_chase_s,
           svd_chase_peak_share, svd_bidiag_s, svd_bidiag_device_s,
           svd_back_transform_s, svd_back_hbm_share,
           svd_host_syncs_per_solve, svd_mxu_peak_share)
N, NB = 256, 64                 # the rehearsal: m = 384
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
EPS = 2.0 ** -24


def test_the_cell_is_the_issues_and_is_found_by_name():
    spec = cells.load_cell(CELL)
    config, traffic = spec["config"], spec["traffic"]
    assert (config["m"], config["n"], config["m_over_n"], config["nb"],
            config["dtype"]) == (12288, 8192, 1.5, 256, "float32")
    assert (config["jobu"], config["jobvt"], config["method_svd"]) == (
        "S", "S", "TwoStage")
    assert config["tier"] == "bf16_6x" and config["grid"] == [1, 1]
    assert spec["chips"] == 1 and config["architecture"] is None
    assert config["reduced"] == ["m", "n"]
    assert set(config["assumed"]) == {"m_over_n", "nb", "dtype", "matrix"}
    # the band, the rung and the route are the library's choice
    assert not {"band", "eig_band", "tb2bd", "chase_backend", "bidiag",
                "env"} & (set(config) | set(traffic))
    assert (traffic["kind"], traffic["routine"], traffic["jobu"],
            traffic["jobvt"], traffic["callers"],
            traffic["warm_up_calls"], traffic["seed_offset"]) == (
        "closed_loop_svd", "gesvd", "S", "S", 1, 2, 0)
    assert spec["cell"]["control_tier"] == "bf16_3x"
    assert {"why", "who", "exercises", "bypasses"} <= set(spec["cell"])
    contract = cells.contract()
    (entry,) = [c for c in contract["configs"] if c["name"] == CONFIG]
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200 and entry["reduced"] == ["m", "n"]
    (cell,) = [w for w in contract["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": CONFIG,
                    "traffic": "closed_loop_gesvd_vec", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in contract["workloads"]) == 2
    # the ten metrics the cell brought, each for this cell alone
    by_name = {m["name"]: m for m in contract["per_layer"]}
    for reader in READERS:
        m = by_name[reader.HEADER["name"]]
        assert m["workloads"] == [CELL] and m["moves"] == "solve_s"
        assert m["layer"] == "svd"
        assert reader.HEADER == {k: v for k, v in m.items()
                                 if k != "workloads"}
    mine = {m["name"] for m in spec["per_layer"]}
    assert mine == {r.HEADER["name"] for r in READERS} | {
        "first_call_s", "backend_compile_s", "compiles_in_window",
        "launches_per_solve", "host_gap_s", "device_idle_share",
        "trace_lower_s"}
    assert {m["name"] for m in spec["end_to_end"]} == {
        "solve_s", "solve_p90_s", "peak_hbm_gib", "setup_s"}
    # no accepted metric took this cell into its list
    assert all(CELL not in m.get("workloads", ())
               for m in contract["per_layer"]
               if not m["name"].startswith("svd_"))


def test_the_closed_forms():
    m, n, b = 12288, 8192, 128
    assert flops_svd.ge2tb(m, n) == pytest.approx(
        4 * m * n * n - 4 * n ** 3 / 3)
    assert flops_svd.ge2tb(n, n) == pytest.approx(8 * n ** 3 / 3)
    assert flops_svd.tb2bd(n, b) == pytest.approx(8 * n * n * b)
    assert flops_svd.bdsdc(n) == pytest.approx(8 * n ** 3 / 3)
    assert flops_svd.unmbr_tb2bd(n) == pytest.approx(4 * n ** 3)
    assert flops_svd.unmbr_ge2tb(m, n) == pytest.approx(4 * m * n * n)
    assert flops_svd.gesvd_vectors(m, n, b) == pytest.approx(
        8 * m * n * n + (16 / 3) * n ** 3 + 8 * n * n * b)
    assert flops_svd.unmbr_tb2bd_bytes(n, b) == pytest.approx(
        16 * n ** 3 / b)
    # 84 ms at the published bandwidth for both sides
    assert flops_svd.unmbr_tb2bd_bytes(n, b) / 819e9 == pytest.approx(
        0.0839, abs=1e-4)
    # 49 ms of a call at the bf16 peak
    assert flops_svd.gesvd_vectors(m, n, b) / 197e12 == pytest.approx(
        0.0487, abs=1e-3)


# ------------------------------------------------ the plain reference

def normal(seed, m=384, n=256):
    return np.random.default_rng(seed).standard_normal(
        (m, n)).astype(np.float32)


def in_eps(a, s, u, vt):
    numbers = plain_svd.equations(jnp.asarray(a), s, jnp.asarray(u),
                                  jnp.asarray(vt), block=96)
    numbers["values_max"] = plain_svd.values_error(
        s, plain_svd.reference_values(a))
    return {k: v / EPS for k, v in numbers.items()}


def test_the_equations_against_the_textbook():
    a = normal(5)
    u, s, vt = np.linalg.svd(a.astype(np.float64), full_matrices=False)
    u, vt = u.astype(np.float32), vt.astype(np.float32)
    exact = in_eps(a, s, u, vt)
    assert all(v < 8 for v in exact.values()), exact
    ref = plain_svd.reference_values(a)
    assert np.abs(ref - s).max() < 1e-12 * s[0]    # the Gram route
    wide = plain_svd.reference_values(a.T)
    assert np.allclose(wide, ref, rtol=1e-13)
    # each number reads the fault it is there for
    u1 = u.copy()
    u1[:, 7] = 0.0                              # a zeroed column of U
    zeroed = in_eps(a, s, u1, vt)
    assert zeroed["orth_u"] > 1e5 and zeroed["residual_max"] > 1e4
    assert zeroed["orth_v"] == pytest.approx(exact["orth_v"])
    vt2 = vt.copy()
    vt2[[3, 200]] = vt2[[200, 3]]               # a swapped pair
    swapped = in_eps(a, s, u, vt2)
    assert swapped["residual_max"] > 1e5 and swapped["residual_fro"] > 1e5
    assert swapped["orth_v"] == pytest.approx(exact["orth_v"], rel=0.05)
    up = s[::-1].copy()                         # sigma ascending
    assert not plain_svd.descending(up) and plain_svd.descending(s)
    assert in_eps(a, up, u, vt)["values_max"] > 1e5
    assert not plain_svd.descending(np.array([1.0, np.nan]))
    assert not plain_svd.descending(np.array([1.0, -1e-3]))
    assert np.isnan(plain_svd.values_error(s[:-1], ref))
    assert all(np.isnan(v) for v in plain_svd.equations(
        jnp.asarray(a), s, jnp.asarray(u[:, :-1]),
        jnp.asarray(vt)).values())


@pytest.mark.parametrize("seed", (3, 2_147_483_659, 4_000_000_007))
def test_f32_passes_and_a_tier_down_fails_by_the_residual(seed):
    """The control where no chip is there. The cell's limits are set at
    its own size on the chip; at this size the principle is what is
    held: the residuals and the values tell the tiers apart, the
    orthogonalities do not."""
    a = normal(seed)
    sound = in_eps(a, *plain_svd.svd_via_band(a, 32, "f32"))
    lower = in_eps(a, *plain_svd.svd_via_band(a, 32, "bf16_3x"))
    assert sound["residual_fro"] < 20 and sound["values_max"] < 8
    assert lower["residual_fro"] > 4 * sound["residual_fro"]
    assert lower["residual_max"] > 4 * sound["residual_max"]
    for number in ("orth_u", "orth_v"):
        assert lower[number] < 8 and sound[number] < 8


# ------------------------------------------------- a broken timed path

def drive(monkeypatch, tmp_path, broken=None):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    spec = cells.load_cell(CELL, n=N, nb=NB)
    # a rehearsal's limits: the chip's are for n=8192 on the MXU
    spec["cell"].update(tol_eps=16.0, tol_fro_eps=128.0,
                        tol_orth_u_eps=256.0, tol_orth_v_eps=256.0,
                        tol_values_eps=32.0)
    if broken is not None:
        monkeypatch.setattr(slate, "gesvd", broken(slate.gesvd))
    args = argparse.Namespace(seed=2_400_000_011, seconds=0.5, trace=0,
                              keep_trace=None)
    rows = []
    monkeypatch.setattr(bench_run, "say", lambda **line: rows.append(line))
    return bench_run.run_cell(spec, jax.devices(), args,
                              rehearsal=True), rows


def one_column_of_u_zeroed(solve):
    def wrapped(A, opts=None, **kw):
        s, U, VT = solve(A, opts, **kw)
        return s, U._replace(data=U.data.at[..., 0].set(0.0)), VT
    return wrapped


def a_pair_swapped(solve):
    """Two rows of VT change places (one tile row apart)."""
    def wrapped(A, opts=None, **kw):
        s, U, VT = solve(A, opts, **kw)
        d = VT.data
        d = d.at[0, 0, 0, :, 1, :].set(VT.data[0, 0, 1, :, 1, :])
        d = d.at[0, 0, 1, :, 1, :].set(VT.data[0, 0, 0, :, 1, :])
        return s, U, VT._replace(data=d)
    return wrapped


def sigma_ascending(solve):
    def wrapped(A, opts=None, **kw):
        s, U, VT = solve(A, opts, **kw)
        return np.array(s)[::-1].copy(), U, VT
    return wrapped


def stale_after_warm_up(solve):
    """After the first call U is A's own tiles."""
    calls = []

    def wrapped(A, opts=None, **kw):
        s, U, VT = solve(A, opts, **kw)
        calls.append(1)
        return (s, U, VT) if len(calls) == 1 else (
            s, U._replace(data=A.retile(U.nb).data), VT)
    return wrapped


def demoted_rung(solve):
    """A right answer that is not this deployment's: the rung the
    ladder preferred was stepped past."""
    def wrapped(A, opts=None, **kw):
        ladder.record_demotion(ladder.Demotion(
            "tb2bd", "vmem", "wave", "made to raise"))
        return solve(A, opts, **kw)
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
        "svd_")]) == 10             # five numbers, two answers
    said = row(rows, "svd.program")
    assert said["path"] == {"two_stage": 2}         # the warm-ups
    assert said["route"] == {"gk_stedc": 2}
    assert sum(said["rung"].values()) == 2 and len(said["rung"]) == 1
    assert said["merges"] >= 14 and 0 <= said["deflated_share"] < 1
    assert row(rows, "svd.demotions")["value"] == 0
    assert row(rows, "svd.descending")["value"] == 2
    assert row(rows, "svd.shape")["value"] == 2
    # the counters were on for the warm-up alone
    from slate_tpu import obs
    assert not obs.metrics_enabled()


@pytest.mark.parametrize("broken, failing", [
    (one_column_of_u_zeroed, "svd_orth_u.last"),
    (a_pair_swapped, "svd_residual_max.last"),
    (sigma_ascending, "svd.descending"),
    (stale_after_warm_up, "svd_residual_fro.last"),
    (demoted_rung, "svd.demotions")])
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
    if broken is a_pair_swapped:        # VT is still orthogonal
        assert row(rows, "svd_orth_v.last")["ok"] is True
    if broken is stale_after_warm_up:
        assert row(rows, "svd_residual_fro.warm_up")["ok"] is True


def test_a_program_without_the_root_span_is_refused(monkeypatch):
    """The parent commit (the driver tries the new cell on it first):
    non-zero at session open, before any operand is made."""
    spec = cells.load_cell(CELL, n=N, nb=NB)
    made = []
    monkeypatch.setattr(slate, "random_matrix",
                        lambda *a, **k: made.append(1))
    for missing in ("SPANS", "COUNTERS"):
        with monkeypatch.context() as m:
            m.delattr(svd, missing)
            with pytest.raises(SystemExit) as refusal:
                closed_loop_svd.open_session(spec, jax.devices(), 7)
        assert refusal.value.code not in (0, None)
        assert "slate.gesvd" in str(refusal.value.code) and not made


def test_control_py_sweeps_the_cell_as_it_stands(monkeypatch, capsys,
                                                 tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    seen = []
    real = slate.gesvd
    monkeypatch.setattr(slate, "gesvd", lambda A, opts=None, **kw: (
        seen.append((dict(opts), kw)), real(A, opts, **kw))[1])
    assert control.main(["--workload", CELL, "--seeds", "1", "--tiers",
                         "bf16_3x", "--rehearse-on-cpu", "--n", str(N),
                         "--nb", str(NB)]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    # the timed call is given the method, the tier and the two jobs
    two = slate.MethodSVD.TwoStage
    both = {"want_u": True, "want_vt": True}
    assert seen[0] == ({slate.Option.MethodSVD: two,
                        slate.Option.TrailingPrecision: "bf16_6x"}, both)
    assert seen[-1] == ({slate.Option.MethodSVD: two,
                         slate.Option.TrailingPrecision: "bf16_3x"}, both)
    assert len(seen) == 4           # two warm-ups, one call, the control
    readings = [ln for ln in lines if "tier" in ln]
    assert [r["tier"] for r in readings] == ["bf16_6x", "bf16_3x"]
    assert all(0 < r["in_eps"]["fro"] < 128 for r in readings)
    errors = [ln for ln in lines if ln.get("step") == "svd_errors"]
    assert [e["answer"] for e in errors] == ["warm_up", "last", "control"]
    assert all(set(e["in_eps"]) == set(closed_loop_svd.LIMITS)
               for e in errors)
    # inf and fro are the two residual numbers of the same line
    assert readings[1]["in_eps"]["inf"] == pytest.approx(
        errors[2]["in_eps"]["residual_max"])
    assert readings[1]["in_eps"]["fro"] == pytest.approx(
        errors[2]["in_eps"]["residual_fro"])
    assert lines[-1]["bf16_6x"]["role"] == "sound"


# --------------------------------------------- the readers, on a trace

STAGES = (("jit_transpose", 0.010), ("jit__ge2tb_jit", 0.54),
          ("jit__gather_tiles_jit", 0.001), ("jit__tb2bd_vmem_jit", 1.01),
          ("jit__leaves_jit", 0.01), ("jit__zrows_jit", 0.001),
          ("jit__secular_jit", 0.15), ("jit__merge_jit", 0.45),
          ("jit__gk_halves_jit", 0.004), ("jit__apply_bulge_jit", 0.17),
          ("jit__rows_padded_jit", 0.003), ("jit__unmqr_jit", 0.14),
          ("jit__apply_bulge_jit", 0.17), ("jit__unmbr_v_jit", 0.09),
          ("jit_transpose", 0.006))
OUTSIDE = 0.010 + 0.001 + 0.003 + 0.006     # re-tiling, gather, embeds


def hand_trace():
    """Two calls on device 0 with the programs the chip printed for
    this cell, one after the other with 1 ms between, and the program's
    spans of them (the root with its labels, ``gesvd.bidiag`` and four
    blocking reads under it)."""
    ops, mods, solves, spans = [], [], [], []
    for i, base in enumerate((0.0, 20.0)):
        t = base + 0.002
        sid = 100 * (i + 1)
        bid = [None, None]
        for name, dur in STAGES:
            if name == "jit__leaves_jit":
                bid[0] = t - 0.0005
            mods.append((name, t, t + dur))
            ops.append((f"fusion.{len(ops)}", t, t + dur,
                        {"opcode": "fusion"}))
            t += dur + 0.001
            if name == "jit__gk_halves_jit":
                bid[1] = t + 0.05       # the host walks on a little
                t = bid[1]
        solves.append((base, t + 0.001))
        spans.append(span("slate.gesvd", sid, 0, i + 1, base + 0.0005, t,
                          routine="gesvd", m=12288, n=8192, nb=256,
                          grid="1x1", jobu="S", jobvt="S",
                          method="TwoStage", path="two_stage", band=128,
                          chase_backend="vmem", bidiag="gk_stedc"))
        spans.append(span("gesvd.bidiag", sid + 1, sid, i + 1, *bid,
                          phase="bdsdc", n=8192))
        for j, site in enumerate(("stedc.zrow", "stedc.roots",
                                  "stedc.zrow", "gesvd.values")):
            spans.append(span(site, sid + 2 + j, sid + 1, i + 1,
                              bid[0] + 0.01 * j, bid[0] + 0.01 * j + 0.001,
                              sync=1))
    red = tr.Reduced(devices={0: tr.DeviceTrace(ops=ops, modules=mods)},
                     solves=solves)
    return red, spans


def run_of(trace, device=V5E, spans=None):
    run = {"trace": trace, "device": device, "spec": {
        "config": {"n": 8192, "m_over_n": 1.5, "dtype": "float32"},
        "traffic": {"routine": "gesvd"}}}
    if spans is not None:
        run["program_spans"] = spans
    return run


def test_the_readers_split_a_call_by_its_stages():
    red, spans = hand_trace()
    run = run_of(red, spans=spans)
    m, n = 12288, 8192
    assert svd_band_reduce_s.compute(run) == pytest.approx(0.54)
    assert svd_chase_s.compute(run) == pytest.approx(1.01)
    assert svd_bidiag_device_s.compute(run) == pytest.approx(
        0.01 + 0.001 + 0.15 + 0.45 + 0.004)
    assert svd_back_transform_s.compute(run) == pytest.approx(
        0.17 + 0.14 + 0.17 + 0.09)
    busy = sum(dur for _, dur in STAGES)
    staged = (svd_band_reduce_s.compute(run) + svd_chase_s.compute(run)
              + svd_bidiag_device_s.compute(run)
              + svd_back_transform_s.compute(run))
    assert staged == pytest.approx(busy - OUTSIDE)
    assert staged > 0.95 * busy
    assert svd_band_reduce_peak_share.compute(run) == pytest.approx(
        100 * flops_svd.ge2tb(m, n) / 197e12 / 0.54)
    assert svd_chase_peak_share.compute(run) == pytest.approx(
        100 * 8 * n * n * 128 / 197e12 / 1.01)
    assert svd_back_hbm_share.compute(run) == pytest.approx(
        100 * (16 * n ** 3 / 128 / 819e9) / 0.34)
    assert svd_mxu_peak_share.compute(run) == pytest.approx(
        100 * flops_svd.gesvd_vectors(m, n, 128) / 197e12 / busy)
    for share in (svd_band_reduce_peak_share, svd_chase_peak_share,
                  svd_back_hbm_share, svd_mxu_peak_share):
        assert 0 < share.compute(run) < 100
    # the span's wall: from before the leaves to after the halves
    assert svd_bidiag_s.compute(run) == pytest.approx(
        0.0005 + 0.01 + 0.001 + 0.001 + 0.001 + 0.15 + 0.001 + 0.45
        + 0.001 + 0.004 + 0.001 + 0.05)
    assert svd_host_syncs_per_solve.compute(run) == 4


def test_the_readers_leave_out_what_they_cannot_read():
    red, spans = hand_trace()
    untraced = dict(run_of(None), trace=None)
    for reader in READERS:
        assert reader.compute(untraced) is None
    # a program without captured spans (no session): the device readers
    # still read, the span readers and the ones that need the band do not
    bare = run_of(red, spans=[])
    assert svd_chase_s.compute(bare) == pytest.approx(1.01)
    for reader in (svd_bidiag_s, svd_host_syncs_per_solve,
                   svd_chase_peak_share, svd_back_hbm_share,
                   svd_mxu_peak_share):
        assert reader.compute(bare) is None
    # a rehearsal's backend has no published peak
    cpu = run_of(red, {"platform": "cpu", "kind": "cpu", "count": 1}, spans)
    for reader in (svd_band_reduce_peak_share, svd_chase_peak_share,
                   svd_back_hbm_share, svd_mxu_peak_share):
        assert reader.compute(cpu) is None
    # a demoted chase is read under the wave's name
    wave = tr.Reduced(devices={0: tr.DeviceTrace(
        ops=red.first.ops, modules=[
            ("jit__tb2bd_wave_jit" if mod[0] == "jit__tb2bd_vmem_jit"
             else mod[0],) + mod[1:] for mod in red.first.modules])},
        solves=red.solves)
    assert svd_chase_s.compute(run_of(wave, spans=spans)) \
        == pytest.approx(1.01)
    # a call that opened no gesvd.bidiag span is a fault, not a zero
    no_bidiag = [s for s in spans if s["name"] != "gesvd.bidiag"]
    with pytest.raises(ValueError, match="gesvd.bidiag"):
        svd_bidiag_s.compute(run_of(red, spans=no_bidiag))


def recorded(name):
    with open(os.path.join(HERE, name), encoding="utf-8") as f:
        return tr.reduce(json.load(f))


def test_the_readers_on_the_trace_recorded_on_the_chip():
    """The first 11.7 ms of one traced call (my chip run, PR 48, call 2,
    seed 4242424242): the re-tiling of the 12288 x 8192 A from 256 to
    the chase band (eight trivial programs, 9.2 ms), then the start of
    the band reduction: its first panel's QR, column by column. Nothing
    past stage 1 has run."""
    red = recorded("recorded_gesvd_12288x8192_vec_1x1.json")
    assert sorted(red.devices) == [0] and len(red.solves) == 1
    dev0 = red.first
    assert len(dev0.ops) == 1800
    names = [m[0] for m in dev0.modules]
    assert names[-1] == "jit__ge2tb_jit"
    assert set(names[:-1]) == {"jit_reshape", "jit_transpose"}
    assert tr.total(dev0.where(tr.is_kernel)) == 0      # no Pallas yet
    run = run_of(red, spans=[])
    stage1 = svd_band_reduce_s.compute(run)
    ge2tb = dev0.modules[-1]
    assert 0.002 < stage1 <= ge2tb[2] - ge2tb[1]
    assert svd_band_reduce_peak_share.compute(run) == pytest.approx(
        100 * flops_svd.ge2tb(12288, 8192) / 197e12 / stage1)
    for reader in (svd_chase_s, svd_bidiag_device_s, svd_back_transform_s,
                   svd_back_hbm_share):
        assert reader.compute(run) is None              # not reached yet
    # the re-tiling is on the device's clock too, and in no stage
    busy = tr.total(dev0.busy())
    assert 0.008 < busy - stage1 < 0.011


def test_another_cells_trace_gives_the_readers_nothing():
    """The symmetric cell's recorded trace names none of this cell's
    stage programs (it stops inside ``jit__he2hb_jit``)."""
    heev = run_of(recorded("recorded_heev_8192_vec_1x1.json"), spans=[])
    for reader in READERS:
        assert reader.compute(heev) is None
