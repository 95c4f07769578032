"""slateprobe (slate_tpu.obs) contract suite.

Pins the observability layer the PR-4 acceptance names: span
nesting + thread safety, the disabled-mode zero-overhead contract
(``span()`` hands back ONE shared no-op object), the flop table
against the LAWN-41 closed forms, the ``finish()`` session-clock
reset (the old ``utils/trace.py`` ``_t0`` bug), the report CLI
(golden table geometry), env activation, and the integration
counters: ladder demotions, injected faults, collectives, watchdog
section records, and bench's ``detail.obs`` embedding.
"""

import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import obs
from slate_tpu.obs import flops, metrics, report, tracing
from slate_tpu.robust import faults, ladder, watchdog
from tests.conftest import spd, rand

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _obs_isolation():
    """Each test starts from everything-off/empty; the pre-test
    activation state (the CI tier-1 job runs with SLATE_TPU_TRACE +
    SLATE_TPU_METRICS armed) is restored afterwards so this suite
    doesn't blind the rest of the session's artifacts.  The flight
    recorder (on by default) is switched off too so the disabled-mode
    identity assertions see the true all-off hot path."""
    was_tracing = obs.tracing_enabled()
    was_metrics = obs.metrics_enabled()
    was_flight = obs.flight.enabled()
    obs.trace_off()
    obs.metrics_off()
    obs.flight.disable()
    obs.reset()
    yield
    obs.trace_off()
    obs.metrics_off()
    obs.flight.disable()
    obs.reset()
    if was_tracing:
        obs.trace_on()
    if was_metrics:
        obs.metrics_on()
    if was_flight:
        obs.flight.enable()


# ---------------------------------------------------------------------------
# disabled mode: the zero-overhead contract
# ---------------------------------------------------------------------------

def test_disabled_span_is_one_shared_noop():
    s1 = obs.span("potrf", routine="potrf", n=4096)
    s2 = obs.span("anything")
    assert s1 is s2 is tracing._NOOP          # no per-call allocation
    with s1:
        pass
    obs.record_span("x", 1.0)
    obs.instant("y")
    obs.count("c")
    obs.gauge("g", 1.0)
    obs.observe("h", 1.0)
    assert tracing.events() == []
    snap = metrics.snapshot()
    assert snap["counters"] == snap["gauges"] == snap["spans"] == []
    assert obs.finish_trace("/nonexistent/never-written.json") is None


def test_enabled_flag_reflects_either_subsystem():
    assert not obs.enabled()
    obs.trace_on()
    assert obs.enabled()
    obs.trace_off()
    obs.metrics_on()
    assert obs.enabled()


# ---------------------------------------------------------------------------
# spans, instants, nesting, the finish() clock reset
# ---------------------------------------------------------------------------

def test_span_nesting_orders_events_and_keeps_labels():
    obs.trace_on()
    with obs.span("outer", routine="potrf", n=64):
        with obs.span("inner", phase="panel", k0=0):
            time.sleep(0.002)
    evs = tracing.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # exit order
    inner, outer = evs
    assert inner["ph"] == outer["ph"] == "X"
    assert inner["args"] == {"phase": "panel", "k0": 0}
    assert outer["args"] == {"routine": "potrf", "n": 64}
    # containment: outer starts no later and ends no earlier
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert inner["dur"] >= 2000                 # ≥ the 2 ms sleep, in µs


def test_instant_event_shape():
    obs.trace_on()
    obs.instant("ladder.demotion", from_rung="vmem", to_rung="wave")
    (ev,) = tracing.events()
    assert ev["ph"] == "i" and ev["s"] == "g"
    assert ev["args"] == {"from_rung": "vmem", "to_rung": "wave"}


def test_finish_writes_chrome_trace_and_resets_clock(tmp_path):
    obs.trace_on()
    time.sleep(0.05)
    with obs.span("first"):
        pass
    ts_first = tracing.events()[0]["ts"]
    out = obs.finish_trace(str(tmp_path / "t1.json"))
    assert out is not None
    doc = json.loads((tmp_path / "t1.json").read_text())
    assert [e["name"] for e in doc["traceEvents"]] == ["first"]
    # the old utils/trace.py bug: _t0 survived finish(), so a second
    # session inherited the first session's offset
    assert tracing.is_on()                     # finish ≠ off
    with obs.span("second"):
        pass
    ts_second = tracing.events()[0]["ts"]
    assert ts_second < ts_first, "session clock must restart at finish"


def test_span_aggregates_feed_metrics_without_tracing():
    obs.metrics_on()
    for _ in range(3):
        with obs.span("phase", routine="gemm", m=8, n=8, k=8):
            pass
    assert tracing.events() == []              # tracing stays off
    (agg,) = metrics.snapshot()["spans"]
    assert agg["name"] == "phase" and agg["count"] == 3
    assert agg["labels"] == {"routine": "gemm", "m": 8, "n": 8, "k": 8}


def test_thread_safety_under_contention():
    obs.trace_on()
    obs.metrics_on()
    n_threads, n_iter = 8, 50
    barrier = threading.Barrier(n_threads)

    def work(tid):
        barrier.wait()
        for i in range(n_iter):
            with obs.span("work", thread=tid):
                obs.count("work.iters")
            obs.observe("work.h", float(i))

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * n_iter
    assert obs.counter_value("work.iters") == total
    assert len(tracing.events()) == total
    snap = metrics.snapshot()
    assert sum(s["count"] for s in snap["spans"]) == total
    (h,) = snap["histograms"]
    assert h["count"] == total and h["min"] == 0.0
    assert h["max"] == float(n_iter - 1)


def test_gauge_last_write_wins():
    obs.metrics_on()
    obs.gauge("bench.roundtrip_latency_s", 0.2)
    obs.gauge("bench.roundtrip_latency_s", 0.1)
    (g,) = metrics.snapshot()["gauges"]
    assert g["value"] == 0.1


# ---------------------------------------------------------------------------
# flop table vs the closed forms (LAWN 41 conventions)
# ---------------------------------------------------------------------------

def test_flop_table_closed_forms():
    assert flops.flop_count("gemm", m=4, n=5, k=6) == 2 * 4 * 5 * 6
    assert flops.flop_count("potrf", n=1024) == 1024 ** 3 / 3
    n = 512
    assert flops.flop_count("getrf", n=n) == n ** 3 - n ** 3 / 3
    m = 1024
    assert flops.flop_count("getrf", m=m, n=n) == m * n ** 2 - n ** 3 / 3
    assert (flops.flop_count("geqrf", m=m, n=n)
            == 2 * m * n ** 2 - 2 * n ** 3 / 3)
    assert (flops.flop_count("gelqf", m=m, n=n)
            == flops.flop_count("geqrf", m=n, n=m))
    assert flops.flop_count("he2hb", n=n) == 4 * n ** 3 / 3
    assert flops.flop_count("hb2st", n=n, b=64) == 6 * n ** 2 * 64
    assert (flops.flop_count("ge2tb", m=n, n=n)
            == pytest.approx(8 * n ** 3 / 3))


def test_flop_count_is_forgiving():
    assert flops.flop_count("unknown_routine", n=8) is None
    assert flops.flop_count("pbtrf", n=8) is None       # listed, no formula
    assert flops.flop_count("gemm", m=4, n=5) is None   # missing dim
    # span labels carry dims the formula doesn't take (nb, platform
    # extras) — they are filtered, not fatal
    assert flops.flop_count("potrf", n=64, nb=8) == 64 ** 3 / 3


def test_peak_gflops_table_and_env_override(monkeypatch):
    monkeypatch.delenv("SLATE_TPU_PEAK_GFLOPS", raising=False)
    assert flops.peak_gflops("tpu", "bfloat16") == 197e3
    assert flops.peak_gflops("cpu", "float32") is None
    assert flops.peak_gflops(None, "bfloat16") is None
    monkeypatch.setenv("SLATE_TPU_PEAK_GFLOPS", "123.5")
    assert flops.peak_gflops("cpu", "float32") == 123.5


def test_enrich_span_attaches_gflops_and_pct_peak():
    e = report.enrich_span({"name": "bench.potrf",
                            "labels": {"routine": "potrf", "n": 8192,
                                       "nb": 512, "platform": "tpu",
                                       "dtype": "bfloat16"},
                            "count": 2, "total_s": 1.0})
    expect = (8192 ** 3 / 3) / 0.5 / 1e9
    assert e["gflops"] == pytest.approx(expect)
    assert e["pct_peak"] == pytest.approx(100 * expect / 197e3)
    # no routine label but the span NAME is a flop-table routine
    e2 = report.enrich_span({"name": "potrf", "labels": {"n": 64},
                             "count": 1, "total_s": 0.5})
    assert e2["gflops"] == pytest.approx((64 ** 3 / 3) / 0.5 / 1e9)
    # unknown routine: untouched, no crash
    e3 = report.enrich_span({"name": "bench.setup", "labels": {},
                             "count": 1, "total_s": 1.0})
    assert "gflops" not in e3


# ---------------------------------------------------------------------------
# report CLI: golden table + exit codes, both export formats
# ---------------------------------------------------------------------------

def test_format_report_golden():
    doc = {"spans": [{"name": "potrf",
                      "labels": {"routine": "potrf", "n": 1024},
                      "count": 2, "total_s": 1.0}],
           "counters": [{"name": "faults.injected",
                         "labels": {"kind": "nan_tile"}, "value": 1.0}],
           "instants": [{"name": "ladder.demotion", "labels": {},
                         "count": 1}]}
    out = report.format_report(doc)
    hdr = (f"  {'span':<46} {'count':>5} {'total_s':>9} "
           f"{'mean_ms':>10} {'GF/s':>8} {'%peak':>6} "
           f"{'AI':>8} {'bound':>8}")
    # AI = (1024³/3 flops) / (1024²·4 bytes) = 85.33; no platform
    # label → numerics but no machine model → bound "unknown"
    assert out.splitlines() == [
        "per-phase spans",
        hdr,
        "  " + "-" * (len(hdr) - 2),
        f"  {'potrf{n=1024}':<46} {2:>5} {1.0:>9.3f} {500.0:>10.3f} "
        f"{'0.7':>8} {'-':>6} {'85.33':>8} {'unknown':>8}",
        "",
        "counters",
        f"  {'faults.injected{kind=nan_tile}':<60} {1:>10}",
        "",
        "instants",
        f"  {'ladder.demotion':<60} {1:>10}",
    ]


def _cli(*args):
    return subprocess.run([sys.executable, "-m", "slate_tpu.obs", *args],
                          cwd=REPO, capture_output=True, text=True)


def test_report_cli_on_both_export_formats(tmp_path):
    obs.metrics_on()
    obs.trace_on()
    obs.record_span("bench.potrf", 0.5, routine="potrf", n=8192, nb=512)
    obs.count("faults.injected", kind="nan_tile", where="potrf")
    obs.instant("fault.nan_tile", where="potrf")
    mpath = tmp_path / "metrics.json"
    obs.dump_json(str(mpath))
    tpath = tmp_path / "trace.json"
    assert obs.finish_trace(str(tpath)) == str(tpath)

    for path in (mpath, tpath):
        r = _cli("report", str(path))
        assert r.returncode == 0, r.stderr
        assert "per-phase spans" in r.stdout
        assert "bench.potrf{n=8192,nb=512}" in r.stdout
        # (8192³/3)/0.5 s = 366.5 GF/s from the flop table
        assert "366.5" in r.stdout
    # counters live only in the metrics snapshot; the trace format
    # still carries the fault instant
    assert ("faults.injected{kind=nan_tile,where=potrf}"
            in _cli("report", str(mpath)).stdout)
    assert "fault.nan_tile{where=potrf}" in _cli("report",
                                                 str(tpath)).stdout

    assert _cli("report", str(tmp_path / "missing.json")).returncode == 1
    assert _cli().returncode == 2


def test_env_activation_writes_both_exports(tmp_path):
    """SLATE_TPU_TRACE=path + SLATE_TPU_METRICS=path arm the layer at
    import and write both exports at process exit, no code changes."""
    tpath, mpath = tmp_path / "trace.json", tmp_path / "metrics.json"
    code = ("from slate_tpu import obs\n"
            "assert obs.tracing_enabled() and obs.metrics_enabled()\n"
            "with obs.span('potrf', routine='potrf', n=256):\n"
            "    pass\n")
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, text=True,
        capture_output=True,
        env={**__import__("os").environ,
             "JAX_PLATFORMS": "cpu",
             "SLATE_TPU_TRACE": str(tpath),
             "SLATE_TPU_METRICS": str(mpath)})
    assert r.returncode == 0, r.stderr
    trace_doc = json.loads(tpath.read_text())
    assert [e["name"] for e in trace_doc["traceEvents"]] == ["potrf"]
    snap = json.loads(mpath.read_text())
    (span,) = [s for s in snap["spans"] if s["name"] == "potrf"]
    assert "gflops" in span                   # enriched at dump time


def test_utils_trace_shim_is_the_obs_layer():
    from slate_tpu.utils import trace
    assert trace.block is tracing.block
    assert trace.finish is tracing.finish


# ---------------------------------------------------------------------------
# integration: ladder, faults, comm, watchdog, jit events, bench
# ---------------------------------------------------------------------------

def test_ladder_demotion_emits_instant_and_counter():
    obs.trace_on()
    obs.metrics_on()
    ladder.clear_demotion_log()

    def broken(*a):
        raise ValueError("injected rung failure")

    lad = ladder.BackendLadder("probe_ladder", [
        ladder.Rung(name="native", run=broken),
        ladder.Rung(name="numpy", run=lambda *a: "ok"),
    ])
    assert lad.run() == "ok"
    assert obs.counter_value("ladder.demotions", ladder="probe_ladder",
                             from_rung="native", to_rung="numpy",
                             reason="raised ValueError") == 1
    # probes counted per rung, attempts include the one retry
    assert obs.counter_value("ladder.probes", ladder="probe_ladder",
                             rung="native", ok=True) == 1
    assert obs.counter_value("ladder.attempts", ladder="probe_ladder",
                             rung="native") == 2
    names = [e["name"] for e in tracing.events()]
    assert "ladder.demotion" in names          # the instant
    assert "ladder.probe_ladder" in names      # the rung span


def test_fault_injection_emits_instant_and_counter():
    obs.trace_on()
    obs.metrics_on()
    faults.clear_log()
    faults.record("nan_tile", where="potrf", detail="tile (0,0)")
    assert obs.counter_value("faults.injected", kind="nan_tile",
                             where="potrf") == 1
    (ev,) = [e for e in tracing.events() if e["ph"] == "i"]
    assert ev["name"] == "fault.nan_tile"
    assert ev["args"]["where"] == "potrf"


def test_comm_event_counts_collectives_and_bytes():
    obs.metrics_on()
    x = np.zeros((4, 4), np.float32)
    obs.comm_event("psum", "x", x)
    obs.comm_event("psum", "x", x)
    assert obs.counter_value("comm.collectives", kind="psum",
                             axis="x") == 2
    assert obs.counter_value("comm.bytes", kind="psum") == 2 * 64.0


def test_watchdog_section_record_becomes_span():
    obs.metrics_on()
    rec = watchdog.run_watched("obs_probe", lambda: 42, cap_s=30)
    assert rec.ok
    (agg,) = [s for s in metrics.snapshot()["spans"]
              if s["name"] == "section.obs_probe"]
    assert agg["labels"] == {"outcome": "ok"} and agg["count"] == 1


def test_jit_events_counted_via_monitoring_hooks():
    obs.metrics_on()
    import jax
    import jax.numpy as jnp

    @jax.jit
    def probe(x):
        return (x * 2.0 + 1.0).sum()

    probe(jnp.ones((7, 13))).block_until_ready()
    if obs.jit_event_total() == 0:
        pytest.skip("jax.monitoring emits no events on this build")
    assert obs.jit_event_total() > 0


# ---------------------------------------------------------------------------
# the chaos contract: every injected fault is visible in obs
# ---------------------------------------------------------------------------

@pytest.mark.chaos_env
def test_chaos_injections_all_visible_as_obs_counters():
    """CI chaos matrix: with metrics armed, EVERY fault the env spec
    fires must show up as a ``faults.injected`` counter (kind + where)
    — chaos runs are diagnosable from the obs stream alone.  With no
    spec armed this asserts vacuously."""
    obs.metrics_on()
    faults.clear_log()
    g1 = st.single_device_grid()
    armed = {s.kind for s in faults.active()}

    def _poke(fn):
        try:
            fn()
        except AttributeError as e:            # seed-broken shard_map
            if "shard_map" not in str(e):
                raise
        except Exception:
            pass                               # outcome pinned elsewhere

    if {"nan_tile", "inf_tile"} & armed:
        A = st.HermitianMatrix.from_dense(spd(32, seed=7), nb=8, grid=g1)
        _poke(lambda: st.potrf(A))
    if "singular_pivot" in armed:
        B = st.Matrix.from_dense(rand(32, 32, seed=8), nb=8, grid=g1)
        _poke(lambda: st.getrf(B))
    if "native_missing" in armed:
        from slate_tpu.internal import band_bulge_native
        _poke(lambda: band_bulge_native.get_lib())

    fired = faults.injection_log()
    if armed & {"nan_tile", "inf_tile", "singular_pivot"}:
        assert fired, "armed operand faults must fire on these ops"
    for rec in fired:
        assert obs.counter_value("faults.injected", kind=rec.kind,
                                 where=rec.where) >= 1, rec
    if fired:
        assert obs.count_total("faults.injected") >= len(fired)


def test_collective_stats_sums_a_combined_all_reduce():
    """XLA combines all-reduces into one op with a tuple result, in an
    order it picks: the bytes are every member's (the first shape alone
    gave 2 KiB or 64 KiB here by that order), and the shapes are handed
    out per op."""
    from slate_tpu.obs import costmodel
    hlo = "\n".join([
        "ENTRY main {",
        "  %ar.1 = (f32[4,128]{1,0}, f32[128,128]{1,0:T(8,128)}, s32[]) "
        "all-reduce(%a, %b, %c), channel_id=1, to_apply=%add",
        "  %ar.2 = f32[16]{0} all-reduce-start(%d), to_apply=%add",
        "  %ar.3 = f32[16]{0} all-reduce-done(%ar.2)",
        "  %ag = (f32[8,64]{1,0}, f32[32,64]{1,0}) all-gather-start(%e)",
        "  %cp = (bf16[64], bf16[64], u32[], u32[]) "
        "collective-permute-start(%f)",
        "}"])
    stats = costmodel.collective_stats(hlo)
    assert stats["all-reduce"] == {
        "count": 2, "bytes": (4 * 128 + 128 * 128 + 1 + 16) * 4.0}
    # an async start lists its operand before its result: the result
    assert stats["all-gather"] == {"count": 1, "bytes": 32 * 64 * 4.0}
    assert stats["collective-permute"] == {"count": 1, "bytes": 64 * 2.0}
    assert costmodel.collective_shapes(hlo)["all-reduce"] == [
        [(4, (4, 128)), (4, (128, 128)), (4, ())], [(4, (16,))]]
