"""slatecache tests: bucket rounding, pad-and-crop vs unbucketed,
executable store round trips, fingerprint/corruption demotion, and
the two-process warmup→hit proof (ISSUE 6 acceptance criteria)."""

import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import slate_tpu as st
from slate_tpu import cache as slc
from slate_tpu.cache import buckets, jitcache, store
from slate_tpu.obs import metrics

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def armed(tmp_path):
    """Arm the cache at a fresh store, metrics on; restore after."""
    was_enabled = metrics.enabled()
    metrics.enable()
    metrics.reset()
    slc.set_cache_dir(tmp_path / "exec")
    yield tmp_path / "exec"
    slc.reset_cache_dir()
    jitcache.clear_in_process()
    metrics.reset()
    if not was_enabled:
        metrics.disable()


# ---------------------------------------------------------------------------
# bucket table and rounding
# ---------------------------------------------------------------------------

def test_bucket_for_exact_edge():
    table = (64, 128, 256)
    assert buckets.bucket_for(64, table) == 64
    assert buckets.bucket_for(128, table) == 128
    assert buckets.bucket_for(256, table) == 256


def test_bucket_for_below_smallest_and_between():
    table = (64, 128, 256)
    assert buckets.bucket_for(1, table) == 64
    assert buckets.bucket_for(63, table) == 64
    assert buckets.bucket_for(65, table) == 128
    assert buckets.bucket_for(97, table) == 128   # prime
    assert buckets.bucket_for(129, table) == 256


def test_bucket_for_above_largest_rounds_to_tile_multiple():
    table = (64, 128)
    assert buckets.bucket_for(150, table, nb=32) == 160
    assert buckets.bucket_for(160, table, nb=32) == 160
    assert buckets.bucket_for(1000, table) % buckets.default_nb(1000) == 0
    assert buckets.bucket_for(1000, table) >= 1000


def test_bucket_for_rejects_nonpositive():
    with pytest.raises(ValueError):
        buckets.bucket_for(0)


def test_bucket_table_env_override(monkeypatch):
    monkeypatch.setenv(buckets.ENV_BUCKETS, "512, 128,64")
    assert buckets.bucket_table() == (64, 128, 512)
    monkeypatch.setenv(buckets.ENV_BUCKETS, "not-numbers")
    assert buckets.bucket_table() == buckets.DEFAULT_TABLE


def test_pad_embed_and_rhs():
    a = np.arange(9, dtype=np.float32).reshape(3, 3)
    p = buckets.pad_embed(a, 5)
    assert p.shape == (5, 5)
    np.testing.assert_array_equal(p[:3, :3], a)
    np.testing.assert_array_equal(p[3:, 3:], np.eye(2, dtype=np.float32))
    assert not p[:3, 3:].any() and not p[3:, :3].any()
    b = buckets.pad_rhs(np.ones(3, np.float32), 5)
    assert b.shape == (5, 1)
    assert b[:3].all() and not b[3:].any()
    with pytest.raises(ValueError):
        buckets.pad_embed(a, 2)


# ---------------------------------------------------------------------------
# pad-and-crop dispatch vs unbucketed results
# ---------------------------------------------------------------------------

def _spd(n, seed):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(np.float32)
    return (a @ a.T) / n + np.eye(n, dtype=np.float32)


def _diagdom(n, seed):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((n, n)).astype(np.float32)
            + n * np.eye(n, dtype=np.float32))


def test_bucketed_posv_prime_n_matches_unbucketed(grid24):
    n = 89                                 # prime: always padded
    a, b = _spd(n, 5), np.ones((n, 3), np.float32)
    x, info = buckets.bucketed_posv(a, b, nb=32, grid=grid24,
                                    table=(64, 128))
    assert info == 0 and x.shape == (n, 3)
    A = st.HermitianMatrix.from_dense(a, nb=32, grid=grid24)
    B = st.Matrix.from_dense(b, nb=32, grid=grid24)
    X0, _, info0 = st.posv(A, B)
    assert int(info0) == 0
    np.testing.assert_allclose(x, np.asarray(X0.to_dense())[:n],
                               rtol=2e-4, atol=2e-5)
    resid = np.linalg.norm(a @ x - b) / np.linalg.norm(b)
    assert resid < 1e-4


def test_bucketed_gesv_prime_n_matches_unbucketed(grid24):
    n = 89
    a, b = _diagdom(n, 7), np.ones((n, 2), np.float32)
    x, info = buckets.bucketed_gesv(a, b, nb=32, grid=grid24,
                                    table=(64, 128))
    assert info == 0 and x.shape == (n, 2)
    A = st.Matrix.from_dense(a, nb=32, grid=grid24)
    B = st.Matrix.from_dense(b, nb=32, grid=grid24)
    X0, _, _, info0 = st.gesv(A, B)
    assert int(info0) == 0
    np.testing.assert_allclose(x, np.asarray(X0.to_dense())[:n],
                               rtol=2e-4, atol=2e-5)


def test_bucketed_posv_exact_bucket_no_padding(grid24):
    n = 64                                  # on the bucket edge
    a, b = _spd(n, 9), np.ones(n, np.float32)
    x, info = buckets.bucketed_posv(a, b, nb=32, grid=grid24,
                                    table=(64, 128))
    assert info == 0 and x.shape == (n,)
    resid = np.linalg.norm(a @ x - b) / np.linalg.norm(b)
    assert resid < 1e-4


def test_bucketed_rejects_bad_shapes():
    with pytest.raises(Exception):
        buckets.bucketed_posv(np.ones((4, 5), np.float32),
                              np.ones(4, np.float32))
    with pytest.raises(ValueError):
        buckets.bucketed_gesv(_diagdom(8, 1), np.ones(5, np.float32))


# ---------------------------------------------------------------------------
# cached_jit: memo/disk tiers, counters, passthrough
# ---------------------------------------------------------------------------

def _demo_fn(x, y, *, flip=False):
    z = jnp.linalg.cholesky(x @ x.T + 4 * jnp.eye(x.shape[0],
                                                  dtype=x.dtype))
    return (z - y) if flip else (z + y)


def test_cached_jit_unarmed_is_passthrough(monkeypatch):
    monkeypatch.delenv(store.ENV_CACHE_DIR, raising=False)
    slc.reset_cache_dir()
    was_enabled = metrics.enabled()
    metrics.enable()
    metrics.reset()
    try:
        assert store.cache_dir() is None
        f = jitcache.cached_jit(_demo_fn, routine="t.demo",
                                static_argnames=("flip",))
        x = jnp.ones((4, 4))
        out = f(x, x, flip=True)
        assert np.isfinite(np.asarray(out)).all()
        assert metrics.counter_total("cache.hit") == 0
        assert metrics.counter_total("cache.miss") == 0
    finally:
        metrics.reset()
        if not was_enabled:
            metrics.disable()


def test_cached_jit_miss_then_memory_hit_then_disk(armed):
    f = jitcache.cached_jit(_demo_fn, routine="t.demo2",
                            static_argnames=("flip",))
    x = jnp.ones((6, 6))
    r1 = f(x, x)
    assert metrics.counter_value("cache.miss", routine="t.demo2") == 1
    r2 = f(x, x)
    assert metrics.counter_value("cache.hit", routine="t.demo2",
                                 tier="memory") == 1
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    assert slc.stats()["entries"] == 1
    # a fresh process is simulated by dropping the in-process tiers:
    # the next call must come back from disk
    jitcache.clear_in_process()
    f = jitcache.cached_jit(_demo_fn, routine="t.demo2",
                            static_argnames=("flip",))
    r3 = f(x, x)
    assert metrics.counter_value("cache.hit", routine="t.demo2",
                                 tier="disk") == 1
    assert metrics.counter_value("cache.miss", routine="t.demo2") == 1
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r3))


def test_cached_jit_distinguishes_statics_and_shapes(armed):
    f = jitcache.cached_jit(_demo_fn, routine="t.demo3",
                            static_argnames=("flip",))
    x = jnp.ones((4, 4))
    f(x, x)
    f(x, x, flip=True)                       # static changes -> miss
    f(jnp.ones((5, 5)), jnp.ones((5, 5)))    # shape changes -> miss
    assert metrics.counter_value("cache.miss", routine="t.demo3") == 3
    assert slc.stats()["entries"] == 3


def test_cached_jit_tracer_args_pass_through(armed):
    f = jitcache.cached_jit(lambda x: x * 2, routine="t.inner")
    out = jax.jit(lambda x: f(x) + 1)(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(out),
                               np.arange(4.0) * 2 + 1)
    # traced call never consults the cache
    assert metrics.counter_value("cache.miss", routine="t.inner") == 0


def test_env_kill_switch(monkeypatch, tmp_path):
    monkeypatch.setenv(store.ENV_CACHE, "0")
    monkeypatch.setenv(store.ENV_CACHE_DIR, str(tmp_path))
    assert store.cache_dir() is None
    monkeypatch.setenv(store.ENV_CACHE, "1")
    slc.reset_cache_dir()
    assert store.cache_dir() == str(tmp_path)
    slc.reset_cache_dir()


def test_fingerprint_tracks_pallas_forces(monkeypatch):
    """A SLATE_PALLAS_* force changes which kernels a trace emits, so
    it must fork the store generation: an executable compiled with the
    force armed can never be replayed by a process without it."""
    for env in ("SLATE_PALLAS_TILE", "SLATE_PALLAS_PANEL",
                "SLATE_PALLAS_TRSM", "SLATE_PALLAS_RANKK"):
        monkeypatch.delenv(env, raising=False)
    store._reset_fingerprint_for_tests()
    try:
        base = store.fp_digest()
        assert store.fingerprint()["pallas_forces"] == ""
        monkeypatch.setenv("SLATE_PALLAS_TRSM", "1")
        store._reset_fingerprint_for_tests()
        assert store.fingerprint()["pallas_forces"] == "trsm"
        assert store.fp_digest() != base
        monkeypatch.setenv("SLATE_PALLAS_PANEL", "1")
        store._reset_fingerprint_for_tests()
        assert store.fingerprint()["pallas_forces"] == "panel_plu,trsm"
        # "0" is not a force — same generation as unset
        monkeypatch.setenv("SLATE_PALLAS_TRSM", "0")
        monkeypatch.delenv("SLATE_PALLAS_PANEL")
        store._reset_fingerprint_for_tests()
        assert store.fp_digest() == base
    finally:
        monkeypatch.undo()
        store._reset_fingerprint_for_tests()


# ---------------------------------------------------------------------------
# invalidation: stale fingerprint, corrupt payload — demote, never crash
# ---------------------------------------------------------------------------

def _store_files(root, suffix):
    return sorted((root / store.STORE_VERSION / store.fp_digest())
                  .glob("*" + suffix))


def test_stale_fingerprint_demotes_to_recompile(armed):
    f = jitcache.cached_jit(_demo_fn, routine="t.stale",
                            static_argnames=("flip",))
    x = jnp.ones((7, 7))
    r1 = np.asarray(f(x, x))
    [mpath] = _store_files(armed, ".meta.json")
    meta = json.loads(mpath.read_text())
    meta["fingerprint"]["jax"] = "0.0.0-other"
    mpath.write_text(json.dumps(meta))
    jitcache.clear_in_process()
    f = jitcache.cached_jit(_demo_fn, routine="t.stale",
                            static_argnames=("flip",))
    r2 = np.asarray(f(x, x))                 # recompiles, no crash
    np.testing.assert_array_equal(r1, r2)
    assert metrics.counter_value("cache.stale", routine="t.stale") == 1
    assert metrics.counter_value("cache.miss", routine="t.stale") == 2
    assert (armed / "quarantine").is_dir()


def test_corrupt_payload_quarantined_and_recompiled(armed):
    f = jitcache.cached_jit(_demo_fn, routine="t.corrupt",
                            static_argnames=("flip",))
    x = jnp.ones((9, 9))
    r1 = np.asarray(f(x, x))
    [bpath] = _store_files(armed, ".bin")
    bpath.write_bytes(b"garbage not an executable")
    jitcache.clear_in_process()
    f = jitcache.cached_jit(_demo_fn, routine="t.corrupt",
                            static_argnames=("flip",))
    r2 = np.asarray(f(x, x))
    np.testing.assert_array_equal(r1, r2)
    assert metrics.counter_value("cache.corrupt",
                                 routine="t.corrupt") == 1
    qfiles = list((armed / "quarantine").iterdir())
    assert any(p.name.endswith(".bin") for p in qfiles)
    # the quarantined entry is out of the serving path: stats sees a
    # store with no live entry for it
    assert slc.stats()["quarantined"] == 1


def test_clear_cache_scrubs_disk_entries(armed):
    """clear_cache means 'force a retrace': with the store armed it
    must also forget the persisted executable, or a monkeypatched
    trace-time constant would be masked by a disk hit."""
    f = jitcache.cached_jit(_demo_fn, routine="t.scrub",
                            static_argnames=("flip",))
    x = jnp.ones((8, 8))
    f(x, x)
    assert slc.stats()["entries"] == 1
    f.clear_cache()
    assert slc.stats()["entries"] == 0
    f(x, x)                                  # recompiles, repersists
    assert metrics.counter_value("cache.miss", routine="t.scrub") == 2
    assert metrics.counter_value("cache.hit", routine="t.scrub",
                                 tier="disk") == 0
    assert slc.stats()["entries"] == 1


def test_store_clear_stale_keeps_current_generation(armed):
    f = jitcache.cached_jit(_demo_fn, routine="t.gen",
                            static_argnames=("flip",))
    f(jnp.ones((5, 5)), jnp.ones((5, 5)))
    # fabricate a stale generation directory
    stale = armed / store.STORE_VERSION / "deadbeef0123"
    stale.mkdir(parents=True)
    (stale / "x.meta.json").write_text("{}")
    assert store.clear(stale_only=True) == 1
    assert not stale.exists()
    assert slc.stats()["entries"] == 1


# ---------------------------------------------------------------------------
# driver integration: posv through the armed cache in-process
# ---------------------------------------------------------------------------

def test_potrf_second_call_all_hits(armed, grid24):
    A1 = st.random_spd(128, 32, grid24, seed=11)
    st.potrf(A1)
    m1 = metrics.counter_total("cache.miss")
    assert m1 >= 1
    A2 = st.random_spd(128, 32, grid24, seed=12)
    st.potrf(A2)
    assert metrics.counter_total("cache.miss") == m1
    assert metrics.counter_total("cache.hit") >= 1


# ---------------------------------------------------------------------------
# the two-process proof (acceptance): warmup in A, first solve in B is
# hit >= 1 / miss == 0, numerics bitwise-identical to the uncached path
# ---------------------------------------------------------------------------

_SOLVE_SCRIPT = """
import hashlib, sys
import numpy as np
from slate_tpu.cache import buckets
from slate_tpu.obs import metrics
metrics.enable()
routine, n = sys.argv[1], int(sys.argv[2])
rng = np.random.default_rng(1 + 64)
a = rng.standard_normal((64, 64)).astype(np.float32)[:n, :n]
if routine == "posv":
    a = (a @ a.T) / n + np.eye(n, dtype=np.float32)
else:
    a = a + n * np.eye(n, dtype=np.float32)
b = np.ones((n, 2), np.float32)
fn = buckets.bucketed_posv if routine == "posv" else buckets.bucketed_gesv
x, info = fn(a, b, nb=32, table=(64,))
print("INFO", info)
print("HIT", metrics.counter_total("cache.hit"))
print("MISS", metrics.counter_total("cache.miss"))
print("XDIGEST", hashlib.sha256(np.ascontiguousarray(x).tobytes()).hexdigest())
"""


def _subproc_env(cache_root):
    """Subprocess env: 1 CPU device (drop the 8-device test flag so
    warmup compiles fast; all subprocesses share one fingerprint)."""
    env = dict(os.environ)
    env.pop("SLATE_TPU_CACHE", None)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "").replace(
        "--xla_force_host_platform_device_count=8", "").strip()
    if flags:
        env["XLA_FLAGS"] = flags
    else:
        env.pop("XLA_FLAGS", None)
    env["SLATE_TPU_CACHE_DIR"] = str(cache_root)
    # jax's own persistent cache beside it, not in the checkout: an
    # entry a child of an earlier run left in <checkout>/.jax_cache is
    # not always loadable by a child of this one (``Function ... not
    # found``)
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_root.parent / "jax")
    return env


def _run(cmd, env):
    r = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, (cmd, r.stdout[-2000:], r.stderr[-2000:])
    return r.stdout


def _parsed(out):
    d = {}
    for ln in out.splitlines():
        parts = ln.split()
        if parts and parts[0] in ("INFO", "HIT", "MISS", "XDIGEST"):
            d[parts[0]] = parts[1]
    return d


@pytest.fixture(scope="module")
def warmed(tmp_path_factory):
    """A cache directory in which one child process has warmed the
    64-bucket of both routines (a cold interpreter and jax start, then
    the compiles), made once for the tests that read it from children
    of their own."""
    root = tmp_path_factory.mktemp("warmed") / "exec"
    out = _run([sys.executable, "-m", "slate_tpu.cache", "warmup",
                "--routines", "posv,gesv", "--buckets", "64", "--nb", "32"],
               _subproc_env(root))
    assert "compiled=" in out
    return root


@pytest.mark.parametrize("routine", ["posv", "gesv"])
def test_two_process_warmup_then_hit(routine, warmed):
    # process A, the fixture's: warmed the 64-bucket for this routine
    env = _subproc_env(warmed)
    # process B: first solve must be all hits, zero compiles
    out_b = _parsed(_run(
        [sys.executable, "-c", _SOLVE_SCRIPT, routine, "37"], env))
    assert out_b["INFO"] == "0"
    assert float(out_b["HIT"]) >= 1, out_b
    assert float(out_b["MISS"]) == 0, out_b
    # process C: identical solve with the cache disabled — numerics
    # must match process B bitwise
    env_c = dict(env)
    env_c["SLATE_TPU_CACHE"] = "0"
    out_c = _parsed(_run(
        [sys.executable, "-c", _SOLVE_SCRIPT, routine, "37"], env_c))
    assert out_c["HIT"] == "0" and out_c["MISS"] == "0"
    assert out_b["XDIGEST"] == out_c["XDIGEST"]
    # the check CLI agrees end-to-end
    out_d = _run([sys.executable, "-m", "slate_tpu.cache", "check",
                  "--routine", routine, "--n", "37", "--nb", "32"],
                 {**env, "SLATE_TPU_CACHE_BUCKETS": "64"})
    assert "OK" in out_d


def test_cli_stats_and_clear(warmed, tmp_path):
    # a copy of the warmed directory: ``clear`` must not empty the one
    # the other tests read
    import shutil
    shutil.copytree(warmed, tmp_path / "exec")
    env = _subproc_env(tmp_path / "exec")
    out = _run([sys.executable, "-m", "slate_tpu.cache", "stats",
                "--json"], env)
    st_json = json.loads(out)
    assert st_json["entries"] >= 1
    assert st_json["generations"][0]["current"]
    out = _run([sys.executable, "-m", "slate_tpu.cache", "clear"], env)
    assert "removed" in out
    out = _run([sys.executable, "-m", "slate_tpu.cache", "stats",
                "--json"], env)
    assert json.loads(out)["entries"] == 0


# ---------------------------------------------------------------------------
# where jax's own persistent compile cache goes (cache/xla_cache.py)
# ---------------------------------------------------------------------------

def test_cache_placement_env_set_sets_nothing_in_code(monkeypatch):
    from slate_tpu.cache import xla_cache
    monkeypatch.setenv(xla_cache.ENV, "/some/dir")
    prev = getattr(jax.config, xla_cache.OPTION)
    assert xla_cache.place_jax_compile_cache() == "/some/dir"
    assert getattr(jax.config, xla_cache.OPTION) == prev


def test_cache_placement_env_unset_uses_the_checkout(monkeypatch):
    from slate_tpu.cache import xla_cache
    monkeypatch.delenv(xla_cache.ENV, raising=False)
    prev = getattr(jax.config, xla_cache.OPTION)
    want = os.path.join(xla_cache.CHECKOUT, ".jax_cache")
    try:
        assert xla_cache.place_jax_compile_cache() == want
        assert getattr(jax.config, xla_cache.OPTION) == want
    finally:
        jax.config.update(xla_cache.OPTION, prev)
    assert Path(xla_cache.CHECKOUT) == Path(__file__).resolve().parents[1]
