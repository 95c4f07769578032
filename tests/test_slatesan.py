"""slatesan tests: seeded violation twins for each analysis (caught
at the exact equation, with a clean twin alongside), the cached_jit
hook (SLATE_TPU_SAN arming, verdict persistence through the disk
tier — including the ISSUE 12 two-process proof — and the unset
no-op), and the driver-surface sweep."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import slate_tpu as st  # noqa: F401  (installs jax.shard_map shim)
from slate_tpu import cache as slc
from slate_tpu.cache import jitcache, store
from slate_tpu.obs import metrics

from tools.slatesan import SanReport, verify_jaxpr
from tools.slatesan import runtime as san_rt
from tools.slatesan import vmem as san_vmem
from tools.slatesan.ir import make_closed, walk

REPO = Path(__file__).resolve().parents[1]


def _mesh():
    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    return Mesh(devs, ("p", "q"))


def _findings(report, analysis):
    return [f for f in report.findings if f.analysis == analysis]


# ---------------------------------------------------------------------------
# analysis (a): collective consistency
# ---------------------------------------------------------------------------

def test_ppermute_broken_bijection_exact_eqn():
    mesh = _mesh()
    x = jnp.zeros((4, 8), jnp.float32)

    def shift_bad(v):  # drops the 3 -> 0 wraparound pair
        return jax.lax.ppermute(v, "q", [(0, 1), (1, 2), (2, 3)])

    f = jax.shard_map(shift_bad, mesh=mesh, in_specs=P("p", "q"),
                      out_specs=P("p", "q"), check_vma=False)
    rep = verify_jaxpr(make_closed(f, x))
    got = _findings(rep, "collective")
    assert len(got) == 1
    assert got[0].primitive == "ppermute"
    assert got[0].path == "shard_map" and got[0].eqn == 0
    assert "not a full bijection" in got[0].message


def test_ppermute_full_ring_clean():
    mesh = _mesh()
    x = jnp.zeros((4, 8), jnp.float32)

    def shift_ok(v):
        return jax.lax.ppermute(v, "q",
                                [(i, (i + 1) % 4) for i in range(4)])

    f = jax.shard_map(shift_ok, mesh=mesh, in_specs=P("p", "q"),
                      out_specs=P("p", "q"), check_vma=False)
    rep = verify_jaxpr(make_closed(f, x))
    assert _findings(rep, "collective") == []


def test_collective_over_unbound_axis():
    # psum over an axis no enclosing shard_map binds
    def loose(v):
        return jax.lax.psum(v, "z")

    mesh = _mesh()
    f = jax.shard_map(loose, mesh=mesh, in_specs=P("p", "q"),
                      out_specs=P(None, "q"), check_vma=False)
    try:
        closed = make_closed(f, jnp.zeros((4, 8), jnp.float32))
    except NameError:
        pytest.skip("jax rejects the unbound axis at trace time")
    rep = verify_jaxpr(closed)
    assert any("names mesh axis 'z'" in f.message
               for f in _findings(rep, "collective"))


def test_branch_divergent_collective_sequence():
    mesh = _mesh()
    x = jnp.zeros((4, 8), jnp.float32)

    def branchy(pred, v):
        return jax.lax.cond(pred,
                            lambda u: jax.lax.psum(u, "p"),
                            lambda u: jax.lax.psum(u, "q"), v)

    f = jax.shard_map(branchy, mesh=mesh, in_specs=(P(), P("p", "q")),
                      out_specs=P(), check_vma=False)
    rep = verify_jaxpr(make_closed(f, True, x))
    got = [g for g in _findings(rep, "collective")
           if g.primitive == "cond"]
    assert len(got) == 1
    assert "differs across branch arms" in got[0].message
    assert "br0" in got[0].message and "br1" in got[0].message


def test_branch_same_sequence_clean():
    mesh = _mesh()
    x = jnp.zeros((4, 8), jnp.float32)

    def branchy(pred, v):
        return jax.lax.cond(pred,
                            lambda u: jax.lax.psum(u * 2, "p"),
                            lambda u: jax.lax.psum(u + 1, "p"), v)

    f = jax.shard_map(branchy, mesh=mesh, in_specs=(P(), P("p", "q")),
                      out_specs=P(None, "q"), check_vma=False)
    rep = verify_jaxpr(make_closed(f, True, x))
    assert [g for g in _findings(rep, "collective")
            if g.primitive == "cond"] == []


# ---------------------------------------------------------------------------
# analysis (b): donation safety
# ---------------------------------------------------------------------------

def _donate_bad(a):
    b = a * 2.0        # eqn 0 produces the aval-matching output
    s = a.sum()        # eqn 1 reads the donated buffer afterwards
    return b, s


def _donate_ok(a):
    s = a.sum()        # last read happens before the alias is live
    b = a * 2.0
    return b, s


def test_read_after_donate_exact_eqn():
    jb = jax.jit(_donate_bad, donate_argnums=0)
    rep = verify_jaxpr(make_closed(lambda a: jb(a),
                                   jnp.ones((4, 8), jnp.float32)))
    got = _findings(rep, "donation")
    assert len(got) == 1
    assert got[0].eqn == 1 and got[0].path.startswith("jit:")
    assert "donated invar #0" in got[0].message


def test_donate_last_read_before_alias_clean():
    jo = jax.jit(_donate_ok, donate_argnums=0)
    rep = verify_jaxpr(make_closed(lambda a: jo(a),
                                   jnp.ones((4, 8), jnp.float32)))
    assert _findings(rep, "donation") == []


# ---------------------------------------------------------------------------
# analysis (c): precision-tier flow
# ---------------------------------------------------------------------------

def _two_dots(u, v):
    hi = jnp.dot(u, v, precision=jax.lax.Precision.HIGHEST)
    lo = jnp.dot(u, v, precision=jax.lax.Precision.DEFAULT)
    return hi + lo


def test_precision_tier_leak_exact_eqn():
    u = jnp.zeros((8, 8), jnp.float32)
    rep = verify_jaxpr(make_closed(_two_dots, u, u), tier="bf16_6x")
    got = _findings(rep, "precision")
    assert len(got) == 1
    assert got[0].eqn == 1 and got[0].primitive == "dot_general"
    assert "precision-tier leak" in got[0].message


def test_precision_matching_tier_clean():
    # at the mxu_bf16 tier a DEFAULT trailing dot is the contract
    u = jnp.zeros((8, 8), jnp.float32)
    rep = verify_jaxpr(make_closed(_two_dots, u, u), tier="mxu_bf16")
    assert _findings(rep, "precision") == []


def test_precision_without_tier_is_skipped_not_clean():
    u = jnp.zeros((8, 8), jnp.float32)
    rep = verify_jaxpr(make_closed(_two_dots, u, u))
    assert "precision" in rep.skipped
    assert rep.verdict_for("precision") == "skip"
    assert rep.ok  # skipped is not a finding


def test_bf16_dots_below_ladder_concern():
    u = jnp.zeros((8, 8), jnp.bfloat16)

    def dots(a, b):
        return jnp.dot(a, b, precision=jax.lax.Precision.DEFAULT)

    rep = verify_jaxpr(make_closed(dots, u, u), tier="bf16_6x")
    assert _findings(rep, "precision") == []


# ---------------------------------------------------------------------------
# analysis (d): VMEM footprint and estimator drift
# ---------------------------------------------------------------------------

def _pallas_closed(n=64):
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    f = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=True)
    return make_closed(f, jnp.zeros((n, n), jnp.float32))


def test_vmem_resident_bytes_from_trace():
    closed = _pallas_closed(64)
    sites = list(san_vmem.pallas_sites(closed))
    assert len(sites) == 1
    _, _, resident = sites[0]
    assert resident == 2 * 64 * 64 * 4  # in ref + out ref


def test_vmem_over_budget_flagged_at_eqn():
    closed = _pallas_closed(64)
    got = list(san_vmem.analyze(closed, budget=1024))
    assert len(got) == 1
    assert got[0].primitive == "pallas_call"
    assert "budget is 1024" in got[0].message
    # and the default ribbon budget is not exceeded by a 32 KiB kernel
    assert list(san_vmem.analyze(closed)) == []


def test_vmem_estimator_drift_undercount():
    closed = _pallas_closed(64)
    resident = 2 * 64 * 64 * 4
    # estimator says "fits" but the traced refs exceed the budget:
    # the dangerous direction, flagged
    got = list(san_vmem.gate_drift(closed, True,
                                   estimator="vmem_applies",
                                   budget=resident - 1))
    assert len(got) == 1 and "drifted" in got[0].message
    # estimator agreeing with the trace: clean in both directions
    assert list(san_vmem.gate_drift(closed, True,
                                    estimator="vmem_applies",
                                    budget=resident)) == []
    # conservative refusal is by design, never a finding
    assert list(san_vmem.gate_drift(closed, False,
                                    estimator="vmem_applies",
                                    budget=resident - 1)) == []


def test_vmem_gate_matches_traced_footprint():
    """The hand-maintained hb2st estimator agrees with the traced
    Ref avals of the kernel it gates (the drift SL003 cannot see)."""
    from slate_tpu.internal import band_wave_vmem as bwv
    n, band = 256, 8
    gate_ok = bwv.vmem_applies(n, band, jnp.float32)
    fn = getattr(bwv, "_hb2st_vmem_jit", None)
    if fn is None or not gate_ok:
        pytest.skip("hb2st vmem path not available at this shape")
    ab = jnp.zeros((band + 1, n), jnp.float32)
    try:
        closed = make_closed(lambda a: fn(a, band, n, True), ab)
    except Exception:
        pytest.skip("hb2st kernel does not trace on this backend")
    assert list(san_vmem.gate_drift(
        closed, gate_ok, estimator="band_wave_vmem.vmem_applies")) == []


def _kernel_suite_cases():
    """(name, closed, estimate) for every slatetune kernel: the traced
    program plus the registered VMEM_FOOTPRINTS estimate for its
    shape."""
    from slate_tpu.internal import pallas_kernels as pk
    h, w = 256, 128
    n, m = 128, 256
    mk = (64, 128, 32)
    a = jnp.zeros((h, w), jnp.float32)
    l = jnp.eye(n, dtype=jnp.float32)
    b = jnp.zeros((n, m), jnp.float32)
    c = jnp.zeros((mk[0], mk[1]), jnp.float32)
    p = jnp.zeros((mk[0], mk[2]), jnp.float32)
    q = jnp.zeros((mk[2], mk[1]), jnp.float32)
    est = pk.VMEM_FOOTPRINTS
    return [
        ("panel_plu",
         make_closed(lambda x: pk.panel_plu_pallas(x, interpret=True),
                     a),
         est["panel_plu"](h, w)),
        ("trsm",
         make_closed(lambda t, y: pk.trsm_left_lower_pallas(
             t, y, interpret=True), l, b),
         est["trsm"](n, m)),
        ("rank_k",
         make_closed(lambda x, y, z: pk.rank_k_tail_pallas(
             x, y, z, interpret=True), c, p, q),
         est["rank_k"](*mk)),
    ]


def test_kernel_suite_estimators_cover_traced_residency():
    """Every registered slatetune footprint estimator bounds the
    traced Ref residency of its kernel, and gate_drift agrees — the
    runtime cross-check SL003's syntactic conservation law cannot
    do."""
    for name, closed, estimate in _kernel_suite_cases():
        sites = list(san_vmem.pallas_sites(closed))
        assert sites, name
        resident = max(r for _, _, r in sites)
        assert resident <= estimate, (name, resident, estimate)
        assert list(san_vmem.gate_drift(
            closed, True, estimator=f"pallas_kernels.{name}",
            budget=estimate)) == [], name


def test_kernel_suite_gate_drift_detects_undercount():
    """Shrinking each estimate below the traced residency makes
    gate_drift flag the kernel — the estimators are load-bearing, not
    vacuously large."""
    for name, closed, _ in _kernel_suite_cases():
        resident = max(r for _, _, r in
                       san_vmem.pallas_sites(closed))
        got = list(san_vmem.gate_drift(
            closed, True, estimator=f"pallas_kernels.{name}",
            budget=resident - 1))
        assert len(got) >= 1 and "drifted" in got[0].message, name


# ---------------------------------------------------------------------------
# report model round-trip
# ---------------------------------------------------------------------------

def test_report_roundtrips_through_json():
    jb = jax.jit(_donate_bad, donate_argnums=0)
    rep = verify_jaxpr(make_closed(lambda a: jb(a),
                                   jnp.ones((4, 8), jnp.float32)))
    d = json.loads(json.dumps(rep.to_dict()))
    back = SanReport.from_dict(d)
    assert back.findings == rep.findings
    assert back.skipped == rep.skipped
    assert d["verdict"] == "fail" and d["counts"] == {"donation": 1}


# ---------------------------------------------------------------------------
# the cached_jit hook: arming, persistence, no-op
# ---------------------------------------------------------------------------

def _hook_fn(x, y, *, tier="bf16_6x"):
    z = jnp.linalg.cholesky(x @ x.T + 4 * jnp.eye(x.shape[0],
                                                  dtype=x.dtype))
    return z + y


@pytest.fixture
def armed_san(tmp_path, monkeypatch):
    monkeypatch.setenv(san_rt.ENV_SAN, "1")
    was_enabled = metrics.enabled()
    metrics.enable()
    metrics.reset()
    slc.set_cache_dir(tmp_path / "exec")
    san_rt.reset()
    yield tmp_path / "exec"
    slc.reset_cache_dir()
    jitcache.clear_in_process()
    san_rt.reset()
    metrics.reset()
    if not was_enabled:
        metrics.disable()


def test_hook_verifies_miss_and_persists_verdict(armed_san):
    f = jitcache.cached_jit(_hook_fn, routine="t.san1",
                            static_argnames=("tier",))
    x = jnp.ones((6, 6))
    f(x, x)
    recs = [r for r in san_rt.records() if r[0] == "t.san1"]
    assert [(r[0], r[1]) for r in recs] == [("t.san1", "trace")]
    assert recs[0][2].ok and recs[0][2].tier == "bf16_6x"
    assert metrics.counter_value("san.verify", source="trace",
                                 routine="t.san1") == 1
    assert metrics.counter_value("san.check", analysis="precision",
                                 verdict="ok", routine="t.san1") == 1
    metas = list(Path(armed_san).rglob("*.meta.json"))
    assert metas, "store should hold the entry's meta.json"
    meta = json.loads(metas[0].read_text())
    assert meta["san"]["verdict"] == "ok"
    assert meta["san"]["tier"] == "bf16_6x"

    # simulated fresh process: disk hit restores the verdict without
    # re-tracing (source == "disk")
    jitcache.clear_in_process()
    san_rt.reset()
    f = jitcache.cached_jit(_hook_fn, routine="t.san1",
                            static_argnames=("tier",))
    f(x, x)
    assert metrics.counter_value("cache.hit", routine="t.san1",
                                 tier="disk") >= 1
    recs = [r for r in san_rt.records() if r[0] == "t.san1"]
    assert [(r[0], r[1]) for r in recs] == [("t.san1", "disk")]
    assert recs[0][2].ok and recs[0][2].tier == "bf16_6x"


def test_hook_unset_is_noop(tmp_path, monkeypatch):
    monkeypatch.delenv(san_rt.ENV_SAN, raising=False)
    slc.set_cache_dir(tmp_path / "exec")
    san_rt.reset()
    try:
        f = jitcache.cached_jit(_hook_fn, routine="t.san0",
                                static_argnames=("tier",))
        x = jnp.ones((5, 5))
        f(x, x)
        assert san_rt.records() == []
        metas = list((tmp_path / "exec").rglob("*.meta.json"))
        assert metas
        assert "san" not in json.loads(metas[0].read_text())
    finally:
        slc.reset_cache_dir()
        jitcache.clear_in_process()


_SAN_PROC_SCRIPT = """
import sys
import jax.numpy as jnp
import slate_tpu  # noqa: F401
from slate_tpu.cache import jitcache
from slate_tpu.obs import metrics
from tools.slatesan import runtime as san_rt
metrics.enable()

def hook_fn(x, y, *, tier="bf16_6x"):
    z = jnp.linalg.cholesky(x @ x.T + 4 * jnp.eye(x.shape[0],
                                                  dtype=x.dtype))
    return z + y

f = jitcache.cached_jit(hook_fn, routine="t.san2p",
                        static_argnames=("tier",))
x = jnp.ones((6, 6))
f(x, x)
for routine, source, rep in san_rt.records():
    print("REC", routine, source, "ok" if rep.ok else "fail", rep.tier)
print("TRACED", metrics.counter_value("san.verify", source="trace",
                                      routine="t.san2p"))
print("DISK", metrics.counter_value("san.verify", source="disk",
                                    routine="t.san2p"))
"""


def test_two_process_verdict_persists_through_disk_tier(tmp_path):
    """ISSUE 12 acceptance: process A compiles under SLATE_TPU_SAN=1
    and persists the verdict; fresh process B restores it from the
    disk tier without re-tracing (verify{source=disk}, no trace)."""
    env = dict(os.environ)
    env.pop("SLATE_TPU_CACHE", None)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "").replace(
        "--xla_force_host_platform_device_count=8", "").strip()
    if flags:
        env["XLA_FLAGS"] = flags
    else:
        env.pop("XLA_FLAGS", None)
    env["SLATE_TPU_CACHE_DIR"] = str(tmp_path / "exec")
    env["SLATE_TPU_SAN"] = "1"

    def run():
        r = subprocess.run([sys.executable, "-c", _SAN_PROC_SCRIPT],
                           cwd=REPO, env=env, capture_output=True,
                           text=True, timeout=600)
        assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
        return r.stdout

    out_a = run()
    assert "REC t.san2p trace ok bf16_6x" in out_a
    assert "TRACED 1.0" in out_a and "DISK 0.0" in out_a
    out_b = run()
    assert "REC t.san2p disk ok bf16_6x" in out_b
    assert "TRACED 0.0" in out_b and "DISK 1.0" in out_b


# ---------------------------------------------------------------------------
# CLI exit-code contract and the driver-surface sweep
# ---------------------------------------------------------------------------

def test_cli_exits_nonzero_on_findings(monkeypatch):
    from tools.slatesan import __main__ as cli
    from tools.slatesan import surface
    from tools.slatesan.model import SanFinding

    bad = SanReport(findings=[SanFinding(
        "collective", "shard_map", 3, "ppermute", "seeded", "potrf")])
    monkeypatch.setattr(surface, "sweep",
                        lambda **kw: [("potrf", "trace", bad)])
    assert cli.main(["--routine", "potrf", "--depths", "0"]) == 1
    monkeypatch.setattr(surface, "sweep",
                        lambda **kw: [("potrf", "trace", SanReport())])
    assert cli.main(["--routine", "potrf", "--depths", "0"]) == 0
    assert cli.main(["--routine", "nope"]) == 2


def test_sweep_potrf_sequential_clean():
    from tools.slatesan import surface
    from slate_tpu import Grid
    recs = surface.sweep(routines=("potrf",), depths=(0,),
                         grid=Grid(2, 4))
    assert recs, "sweep must verify at least one program"
    assert all(rep.ok for _, _, rep in recs), [
        f.format() for _, _, rep in recs for f in rep.findings]
    assert all(source == "trace" for _, source, _ in recs)
    assert all("precision" not in rep.skipped for _, _, rep in recs)


@pytest.mark.slow
def test_sweep_full_surface_clean():
    from tools.slatesan import surface
    recs = surface.sweep()
    assert all(rep.ok for _, _, rep in recs), [
        f.format() for _, _, rep in recs for f in rep.findings]
    routines = {r for r, _, _ in recs}
    assert {"potrf", "getrf"} <= routines


# ---------------------------------------------------------------------------
# analysis (e): host-schedule liveness (the slaterace static half)
# ---------------------------------------------------------------------------

from slate_tpu.runtime.dag import TaskKey, TileDag  # noqa: E402
from tools.slatesan import schedule as san_sched  # noqa: E402


class _CyclicDag(TileDag):
    """Program-order edge inference is forward-only, so a cycle can't
    arise from ``add()`` — this twin injects the back edge a buggy
    hand-patched scheduler could, turning the chain into a ring."""

    def edges(self):
        out = super().edges()
        if len(self.tasks) >= 2:
            out.append((self.tasks[-1].index, 0))
        return out


def test_schedule_cyclic_dag_rejected():
    g = _CyclicDag()
    k0 = g.add(TaskKey((0, 0), 0, "factor"), writes=[("panel", 0)])
    g.add(TaskKey((1, 1), 0, "trailing"), reads=[("panel", 0)],
          writes=[("tile", 1, 1)])
    assert k0 in g._by_key
    found = san_sched.analyze_tile_dag(g, "twin:cycle", "potrf")
    assert len(found) == 1, [f.format() for f in found]
    assert found[0].analysis == "schedule"
    assert found[0].eqn == -1
    assert "not schedulable" in found[0].message
    assert "deadlocks the native pool" in found[0].message
    # the straight chain without the injected edge is clean
    h = TileDag()
    h.add(TaskKey((0, 0), 0, "factor"), writes=[("panel", 0)])
    h.add(TaskKey((1, 1), 0, "trailing"), reads=[("panel", 0)],
          writes=[("tile", 1, 1)])
    assert san_sched.analyze_tile_dag(h, "twin:chain", "potrf") == []


def test_schedule_overcapacity_ring_rejected():
    """Three panels in flight against a depth-1 (two-slot) ring: the
    third factor must be flagged at its exact op index."""
    ops = [("factor", 0), ("factor", 1), ("factor", 2),
           ("consume", 0), ("trailing", 0, 0),
           ("consume", 1), ("trailing", 1, 0),
           ("consume", 2), ("trailing", 2, 0)]
    found = san_sched.analyze_ops("potrf", 0, 3, 1, ops)
    assert [f.eqn for f in found] == [2], [f.format() for f in found]
    assert found[0].primitive == "factor"
    assert "exceed the depth-1 ring capacity 2" in found[0].message
    # retiring panel 0 before the third factor fits the ring: clean
    ok = [("factor", 0), ("factor", 1),
          ("consume", 0), ("trailing", 0, 0),
          ("factor", 2),
          ("consume", 1), ("trailing", 1, 0),
          ("consume", 2), ("trailing", 2, 0)]
    assert san_sched.analyze_ops("potrf", 0, 3, 1, ok) == []


def test_schedule_consume_before_produce_rejected():
    ops = [("consume", 0), ("factor", 0), ("trailing", 0, 0)]
    found = san_sched.analyze_ops("potrf", 0, 1, 1, ops)
    assert found and found[0].eqn == 0
    assert found[0].primitive == "consume"
    assert "consume-before-produce" in found[0].message


def test_schedule_out_of_order_consume_rejected():
    ops = [("factor", 0), ("factor", 1),
           ("consume", 1), ("consume", 0),
           ("trailing", 0, 0), ("trailing", 1, 0)]
    found = san_sched.analyze_ops("potrf", 0, 2, 1, ops)
    assert any("out of step order" in f.message for f in found), [
        f.format() for f in found]


def test_schedule_unwritten_read_rejected_unless_external():
    g = TileDag()
    g.add(TaskKey((0, 0), 0, "trailing"), reads=[("col", 3), ("ghost", 9)],
          writes=[("tile", 0, 0)])
    found = san_sched.analyze_tile_dag(
        g, "twin:orphan", "getrf", external=lambda r: r[0] == "col")
    assert len(found) == 1, [f.format() for f in found]
    assert "('ghost', 9)" in found[0].message
    assert "never-signaled" in found[0].message


def test_schedule_chunk_plan_grid_clean():
    """Acceptance: every routine x depth 0-3 chunk plan and every
    superstep geometry verifies clean."""
    recs = san_sched.sweep_records()
    assert all(rep.ok for _, _, rep in recs), [
        f.format() for _, _, rep in recs for f in rep.findings]
    sources = [src for _, src, _ in recs]
    for d in (0, 1, 2, 3):
        assert any(f"/d={d}" in s for s in sources)
    assert any(s.startswith("superstep:") for s in sources)
    routines = {r for r, _, _ in recs}
    assert {"potrf", "getrf", "geqrf"} <= routines


def test_schedule_marked_skipped_on_jaxpr_reports():
    """The fifth analysis is host-level; jaxpr verification reports it
    as skipped, not silently clean."""
    closed = make_closed(lambda v: v + 1.0, jnp.zeros((4,), jnp.float32))
    rep = verify_jaxpr(closed)
    assert "schedule" in rep.skipped
