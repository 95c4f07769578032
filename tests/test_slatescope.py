"""slatescope contract suite: cost model, roofline attribution, HBM
telemetry, timing clamp, percentiles, and the cache-hit attribution
restore.

Everything here runs on the CPU backend: the cost model captures real
``cost_analysis()`` numbers from real compiled programs, HBM stats are
injected via ``hbm.set_stats_fn`` (CPU devices report none), and the
bench roofline rows are driven through ``run_section`` directly.
"""

import json

import pytest

from slate_tpu import obs
from slate_tpu.obs import costmodel, hbm, metrics, report, roofline

REPO_POTRF_FLOPS = 1024 ** 3 / 3


@pytest.fixture(autouse=True)
def _obs_isolation():
    was_tracing = obs.tracing_enabled()
    was_metrics = obs.metrics_enabled()
    obs.trace_off()
    obs.metrics_off()
    obs.reset()
    hbm.set_stats_fn(None)
    yield
    obs.trace_off()
    obs.metrics_off()
    obs.reset()
    hbm.set_stats_fn(None)
    if was_tracing:
        obs.trace_on()
    if was_metrics:
        obs.metrics_on()


# ---------------------------------------------------------------------------
# cost model: capture, registry, reconcile
# ---------------------------------------------------------------------------

def _compiled_gemm(n=64):
    import jax
    import jax.numpy as jnp
    x = jnp.ones((n, n), jnp.float32)
    return jax.jit(lambda a, b: a @ b).lower(x, x).compile()


def test_capture_real_compiled_program():
    cost = costmodel.capture(_compiled_gemm(64))
    assert cost is not None
    # XLA counts exactly 2n³ flops for a matmul
    assert cost["flops"] == pytest.approx(2 * 64 ** 3)
    assert cost["bytes_accessed"] > 0
    mem = cost["memory"]
    assert mem["argument_bytes"] == 2 * 64 * 64 * 4
    assert mem["output_bytes"] == 64 * 64 * 4
    assert mem["peak_bytes"] >= mem["output_bytes"]


def test_capture_never_raises_on_dark_platform():
    class Dark:
        def cost_analysis(self):
            raise RuntimeError("unimplemented")

        def memory_analysis(self):
            raise RuntimeError("unimplemented")

        def as_text(self):
            raise RuntimeError("unimplemented")

    assert costmodel.capture(Dark()) is None


def test_record_lookup_and_prefix_fallback():
    obs.metrics_on()
    costmodel.record("gemm.chunk_core", {"flops": 1e6})
    assert costmodel.lookup("gemm.chunk_core")["flops"] == 1e6
    assert costmodel.lookup("gemm") is None
    assert costmodel.lookup_prefix("gemm")["flops"] == 1e6
    assert metrics.counter_value("costmodel.captured",
                                 routine="gemm.chunk_core",
                                 source="compile") == 1


def test_snapshot_roundtrip_through_dump():
    obs.metrics_on()
    costmodel.record("potrf", {"flops": 2.0, "bytes_accessed": 4.0})
    snap = obs.dump()
    assert snap["costmodel"]["potrf"]["flops"] == 2.0
    costmodel.reset()
    costmodel.load_snapshot(snap["costmodel"])
    assert costmodel.lookup("potrf")["bytes_accessed"] == 4.0


def test_reconcile_model_vs_xla():
    cost = costmodel.capture(_compiled_gemm(64))
    costmodel.record("gemm", cost)
    rec = costmodel.reconcile("gemm", dtype="float32", m=64, n=64, k=64)
    assert rec["flops_ratio"] == pytest.approx(1.0)
    # XLA never moves less than ~half the closed-form floor here and
    # shouldn't blow it up by an order of magnitude either
    assert 0.25 < rec["bytes_ratio"] < 4.0
    assert costmodel.reconcile("never_compiled", n=8) is None


def test_min_bytes_closed_forms():
    assert costmodel.min_bytes("gemm", m=2, n=3, k=4) == (
        2 * 4 + 4 * 3 + 2 * 2 * 3) * 4
    assert costmodel.min_bytes("potrf", n=64) == 64 ** 2 * 4
    assert costmodel.min_bytes("potrf", dtype="float64", n=64) == (
        64 ** 2 * 8)
    left = costmodel.min_bytes("trsm", m=8, n=16, side="left")
    right = costmodel.min_bytes("trsm", m=8, n=16, side="right")
    assert left == (8 ** 2 / 2 + 2 * 8 * 16) * 4
    assert right == (16 ** 2 / 2 + 2 * 8 * 16) * 4
    assert costmodel.min_bytes("unknown", n=8) is None


def test_collective_stats_parses_hlo():
    hlo = "\n".join([
        "ENTRY main {",
        "  p0 = f32[64,64] parameter(0)",
        "  ar = f32[64,64] all-reduce(p0), to_apply=add",
        "  ags = f32[8,64] all-gather-start(p0)",
        "  agd = f32[8,64] all-gather-done(ags)",
        "  cp = bf16[64,64] collective-permute(p0)",
        "}",
    ])
    stats = costmodel.collective_stats(hlo)
    assert stats["all-reduce"] == {"count": 1,
                                   "bytes": 64 * 64 * 4.0}
    # -start counted once, -done skipped: no double counting
    assert stats["all-gather"]["count"] == 1
    assert stats["all-gather"]["bytes"] == 8 * 64 * 4.0
    assert stats["collective-permute"]["bytes"] == 64 * 64 * 2.0


def test_record_counts_hlo_collectives():
    obs.metrics_on()
    costmodel.record("gemm", {
        "flops": 1.0,
        "collectives": {"all-reduce": {"count": 3, "bytes": 96.0}}})
    assert metrics.counter_value("comm.hlo_collectives",
                                 kind="all-reduce", routine="gemm") == 3
    assert metrics.counter_value("comm.hlo_bytes",
                                 kind="all-reduce",
                                 routine="gemm") == 96.0


# ---------------------------------------------------------------------------
# roofline attribution
# ---------------------------------------------------------------------------

def test_attribute_compute_bound():
    a = roofline.attribute({"routine": "gemm", "m": 1024, "n": 1024,
                            "k": 1024, "platform": "cpu",
                            "dtype": "float32"}, 0.05)
    assert a["bound"] == "compute"
    assert a["ai"] > a["ridge_ai"]
    assert 0 < a["roofline_frac"] <= 1.0


def test_attribute_memory_bound():
    a = roofline.attribute({"routine": "potrs", "n": 1024, "nrhs": 1,
                            "platform": "cpu", "dtype": "float32"},
                           1e-3)
    assert a["bound"] == "memory"
    assert a["ai"] < a["ridge_ai"]


def test_attribute_latency_bound():
    # a 64³ matmul cannot explain a full second of wall on any machine
    a = roofline.attribute({"routine": "gemm", "m": 64, "n": 64,
                            "k": 64, "platform": "cpu",
                            "dtype": "float32"}, 1.0)
    assert a["bound"] == "latency"
    assert a["expected_s"] < roofline.LATENCY_FRACTION * 1.0


def test_attribute_host_and_unknown():
    host = roofline.attribute({}, 1.0, span="bench.setup")
    assert host["bound"] == "host"
    assert host["span"] == "bench.setup"
    unk = roofline.attribute({"routine": "potrf", "n": 64}, 1.0)
    assert unk["bound"] == "unknown"          # numerics, no machine model
    assert unk["ai"] is not None


def test_attribute_uses_xla_cost_over_closed_form():
    a = roofline.attribute({"routine": "gemm", "m": 64, "n": 64,
                            "k": 64},
                           cost={"flops": 5.0, "bytes_accessed": 10.0})
    assert a["bytes"] == 10.0
    assert a["bytes_source"] == "xla"
    # closed-form flops win when dims are present; XLA fills bytes
    assert a["flops"] == pytest.approx(2 * 64 ** 3)


def test_mem_bw_env_override(monkeypatch):
    monkeypatch.setenv("SLATE_TPU_MEM_BW_GBS", "123.0")
    assert roofline.mem_bw_gbs("cpu") == 123.0
    monkeypatch.delenv("SLATE_TPU_MEM_BW_GBS")
    assert roofline.mem_bw_gbs("tpu") == 819.0
    assert roofline.mem_bw_gbs(None) is None


def test_tpu_f32_classification_peak_is_6x_tier():
    # flops.peak_gflops stays None for (tpu, f32) without a precision
    # label; the roofline classification default is the bf16_6x tier
    assert roofline.compute_peak_gflops("tpu", "float32") == (
        pytest.approx(197e3 / 6))


# ---------------------------------------------------------------------------
# enrich_span: costmodel fallback = no blank rows on cache hits
# ---------------------------------------------------------------------------

def test_enrich_span_roofline_columns():
    e = report.enrich_span({"name": "bench.potrf",
                            "labels": {"routine": "potrf", "n": 1024},
                            "count": 2, "total_s": 1.0})
    assert e["bytes"] == 1024 ** 2 * 4
    assert e["ai"] == pytest.approx(REPO_POTRF_FLOPS / (1024 ** 2 * 4))
    assert e["bound"] == "unknown"


def test_enrich_span_dimless_labels_fall_back_to_costmodel():
    # the blank-attribution-row class: a cached-run span whose labels
    # carry no dims — the persisted XLA cost supplies flops AND bytes
    e = report.enrich_span(
        {"name": "solve", "labels": {"routine": "mystery"},
         "count": 1, "total_s": 0.01},
        costs={"mystery": {"flops": 1e6, "bytes_accessed": 1e5}})
    assert e["gflops"] == pytest.approx(0.1)
    assert e["ai"] == pytest.approx(10.0)
    e2 = report.enrich_span(
        {"name": "solve", "labels": {"routine": "mystery"},
         "count": 1, "total_s": 0.01},
        costs={"mystery.chunk": {"flops": 1e6}})
    assert e2["gflops"] == pytest.approx(0.1)    # dotted-prefix match


def test_enrich_span_registry_fallback_without_costs_arg():
    costmodel.record("mystery2", {"flops": 2e6})
    e = report.enrich_span({"name": "solve",
                            "labels": {"routine": "mystery2"},
                            "count": 1, "total_s": 0.01})
    assert e["gflops"] == pytest.approx(0.2)


# ---------------------------------------------------------------------------
# metrics percentiles
# ---------------------------------------------------------------------------

def test_histogram_percentiles():
    obs.metrics_on()
    for v in range(1, 101):
        metrics.observe("lat_ms", float(v))
    (h,) = metrics.snapshot()["histograms"]
    assert h["p50"] == pytest.approx(50.5)
    assert h["p90"] == pytest.approx(90.1)
    assert h["p99"] == pytest.approx(99.01)
    assert h["min"] == 1.0 and h["max"] == 100.0


def test_histogram_sample_cap_bounds_memory():
    obs.metrics_on()
    for v in range(2000):
        metrics.observe("big", float(v))
    (h,) = metrics.snapshot()["histograms"]
    assert h["count"] == 2000
    assert h["max"] == 1999.0                    # summary exact
    # the percentile window is bounded: recent values dominate
    assert h["p50"] > 500.0


def test_percentile_single_sample():
    assert metrics.percentile([7.0], 0.99) == 7.0


def test_report_renders_histogram_percentiles():
    out = report.format_report({
        "spans": [],
        "histograms": [{"name": "cache.compile_ms", "labels": {},
                        "count": 3, "sum": 60.0, "min": 10.0,
                        "max": 30.0, "p50": 20.0, "p90": 28.0,
                        "p99": 29.8}]})
    assert "histograms" in out
    assert "cache.compile_ms" in out
    assert "p99" in out


# ---------------------------------------------------------------------------
# timing clamp (satellite: round-trip subtraction can never go negative)
# ---------------------------------------------------------------------------

def test_timing_clamp_floors_at_zero_and_counts():
    obs.metrics_on()
    t = obs.timed_scalar_median(lambda: 0.0, warmup=0, iters=3,
                                t_rt=10.0, name="bench.clamped",
                                labels={"routine": "potrf", "n": 8})
    assert t == 1e-9                             # floored, not negative
    assert metrics.counter_total("timing.clamped") >= 3
    # the all-clamped median suppresses its span: no nonsense GF/s row
    assert all(s["name"] != "bench.clamped"
               for s in metrics.snapshot()["spans"])


def test_timing_unclamped_path_records_span():
    obs.metrics_on()
    import time as _time
    t = obs.timed_scalar_median(lambda: _time.sleep(0.002) or 0.0,
                                warmup=0, iters=1, t_rt=0.0,
                                name="bench.ok",
                                labels={"routine": "potrf", "n": 8})
    assert t >= 0.002
    assert metrics.counter_total("timing.clamped") == 0
    (s,) = [s for s in metrics.snapshot()["spans"]
            if s["name"] == "bench.ok"]
    assert s["count"] == 1


# ---------------------------------------------------------------------------
# HBM telemetry (stats injected — CPU devices report none)
# ---------------------------------------------------------------------------

def test_hbm_watch_gauges_and_leak_counter():
    obs.metrics_on()
    feed = iter([
        {"bytes_in_use": 100, "peak_bytes_in_use": 100},
        {"bytes_in_use": 100 + 64 * 1024 * 1024,
         "peak_bytes_in_use": 5 * 10 ** 9},
    ])
    hbm.set_stats_fn(lambda: next(feed))
    with hbm.watch("bench.potrf_16k") as w:
        pass
    assert w.stats["delta_bytes"] == 64 * 1024 * 1024
    assert w.stats["peak_bytes"] == 5 * 10 ** 9
    assert metrics.counter_value(
        "hbm.leak_bytes",
        section="bench.potrf_16k") == 64 * 1024 * 1024
    snap = metrics.snapshot()
    gauges = {(g["name"], g["labels"].get("edge")): g["value"]
              for g in snap["gauges"]}
    assert gauges[("hbm.bytes_in_use", "pre")] == 100.0
    assert gauges[("hbm.peak_bytes", None)] == 5e9


def test_hbm_small_delta_is_not_a_leak():
    obs.metrics_on()
    feed = iter([{"bytes_in_use": 100, "peak_bytes_in_use": 200},
                 {"bytes_in_use": 200, "peak_bytes_in_use": 200}])
    hbm.set_stats_fn(lambda: next(feed))
    with hbm.watch("quiet"):
        pass
    assert metrics.counter_total("hbm.leak_bytes") == 0


def test_hbm_degrades_to_none_without_stats():
    hbm.set_stats_fn(lambda: None)
    assert hbm.sample("anywhere") is None
    with hbm.watch("dark") as w:
        pass
    assert w.stats is None


# ---------------------------------------------------------------------------
# cache integration: compile captures cost, disk hit restores it
# ---------------------------------------------------------------------------

def test_disk_hit_restores_cost_attribution(tmp_path):
    obs.metrics_on()
    import jax.numpy as jnp
    from slate_tpu.cache import jitcache
    from slate_tpu.cache import store as cstore
    cstore.set_cache_dir(str(tmp_path))
    try:
        f = jitcache.cached_jit(lambda a: a @ a,
                                routine="scopetest.gemm")
        x = jnp.ones((32, 32), jnp.float32)
        f(x)                                     # compile + persist
        compiled_cost = costmodel.lookup("scopetest.gemm")
        assert compiled_cost is not None
        assert compiled_cost["flops"] == pytest.approx(2 * 32 ** 3)
        assert metrics.counter_value("costmodel.captured",
                                     routine="scopetest.gemm",
                                     source="compile") == 1
        # the persisted meta.json carries the analysis verbatim
        metas = list(tmp_path.rglob("*.meta.json"))
        assert metas, "store must persist a meta.json"
        meta = json.loads(metas[0].read_text())
        assert meta["cost_analysis"]["flops"] == pytest.approx(
            2 * 32 ** 3)
        # fresh-process simulation: memo + registry gone, disk remains
        jitcache._MEMO.clear()
        costmodel.reset()
        assert costmodel.lookup("scopetest.gemm") is None
        f(x)                                     # disk hit
        assert metrics.counter_value("cache.hit",
                                     routine="scopetest.gemm",
                                     tier="disk") == 1
        restored = costmodel.lookup("scopetest.gemm")
        assert restored is not None, "disk hit must restore attribution"
        assert restored["flops"] == compiled_cost["flops"]
        assert metrics.counter_value("costmodel.captured",
                                     routine="scopetest.gemm",
                                     source="disk") == 1
    finally:
        jitcache.clear_in_process()
        cstore.reset_cache_dir()

