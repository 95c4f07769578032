"""slatetune tests: tuning-table round trips (persist → fresh load →
stale-fingerprint invalidation → corrupt quarantine), driver_config
pinning semantics, the cached_jit key token, the two-process pinning
proof (process A sweeps and persists; a fresh process B resolves the
tuned config with ``tune.pinned`` ≥ 1 and zero sweeps, and its
persisted executable keys carry the table token)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

import slate_tpu as st
from slate_tpu import tune
from slate_tpu.cache import jitcache, store
from slate_tpu.obs import metrics
from slate_tpu.tune import table as ttable
from slate_tpu.types import Option
from tests.conftest import spd

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def armed(tmp_path):
    """Arm the cache at a fresh store, metrics on; restore after."""
    was_enabled = metrics.enabled()
    metrics.enable()
    metrics.reset()
    store.set_cache_dir(tmp_path / "exec")
    tune.invalidate_cache()
    yield tmp_path / "exec"
    store.reset_cache_dir()
    tune.invalidate_cache()
    jitcache.clear_in_process()
    metrics.reset()
    if not was_enabled:
        metrics.disable()


def _seed_table(root, entries):
    path = ttable.save(entries, str(root))
    tune.invalidate_cache()
    return path


# ---------------------------------------------------------------------------
# table round trip, stale invalidation, corrupt quarantine
# ---------------------------------------------------------------------------

def test_table_round_trip(armed):
    entries = {"potrf:256": {"nb": 64, "rung": "xla", "tier": "bf16_3x",
                             "pipeline_depth": 1, "ms": 1.5}}
    path = _seed_table(armed, entries)
    assert Path(path).name == "tuning.json"
    assert ttable.load(str(armed)) == entries
    # the digest is content-stable, not insertion-order-stable
    reordered = {"potrf:256": dict(reversed(list(
        entries["potrf:256"].items())))}
    assert ttable.entries_digest(entries) == \
        ttable.entries_digest(reordered)


def test_table_stale_fingerprint_quarantined(armed):
    path = Path(_seed_table(armed, {"getrf:256": {"nb": 128}}))
    doc = json.loads(path.read_text())
    doc["fingerprint"]["jax"] = "0.0.0-stale"
    path.write_text(json.dumps(doc))
    assert ttable.load(str(armed)) == {}
    assert not path.exists()
    q = armed / "quarantine" / "tuning.json"
    assert q.exists()
    assert "fingerprint" in \
        (armed / "quarantine" / "tuning.reason.txt").read_text()
    assert metrics.counter_total("tune.stale") >= 1


def test_table_corrupt_quarantined(armed):
    path = Path(_seed_table(armed, {"getrf:256": {"nb": 128}}))
    path.write_text("{not json")
    assert ttable.load(str(armed)) == {}
    assert not path.exists()
    assert (armed / "quarantine" / "tuning.json").exists()
    assert metrics.counter_total("tune.corrupt") >= 1


def test_key_token_off_when_unarmed_or_empty(armed):
    assert tune.key_token() == "tune:off"          # armed, no table
    store.reset_cache_dir()
    tune.invalidate_cache()
    assert tune.key_token() == "tune:off"          # unarmed


def test_key_token_tracks_table_content(armed):
    _seed_table(armed, {"potrf:256": {"nb": 64}})
    t1 = tune.key_token()
    assert t1.startswith("tune:") and t1 != "tune:off"
    _seed_table(armed, {"potrf:256": {"nb": 128}})
    t2 = tune.key_token()
    assert t2 != t1 and t2 != "tune:off"


# ---------------------------------------------------------------------------
# driver_config pinning semantics
# ---------------------------------------------------------------------------

def test_driver_config_unarmed_is_defaults():
    store.reset_cache_dir()
    tune.invalidate_cache()
    tier, depth = tune.driver_config("potrf", 192)
    assert tier == "bf16_6x" and depth == 0


def test_driver_config_pins_from_table(armed):
    _seed_table(armed, {"potrf:256": {"nb": 64, "rung": "xla",
                                      "tier": "bf16_3x",
                                      "pipeline_depth": 1}})
    tier, depth = tune.driver_config("potrf", 192)   # 192 → bucket 256
    assert (tier, depth) == ("bf16_3x", 1)
    assert metrics.counter_total("tune.pinned") >= 1
    # other routines and other buckets stay on defaults
    assert tune.driver_config("getrf", 192) == ("bf16_6x", 0)


def test_driver_config_explicit_options_win(armed):
    _seed_table(armed, {"potrf:256": {"tier": "bf16_3x",
                                      "pipeline_depth": 1}})
    opts = {Option.TrailingPrecision: "mxu_bf16",
            Option.PipelineDepth: 2}
    assert tune.driver_config("potrf", 192, opts) == ("mxu_bf16", 2)


def test_driver_config_ignores_junk_tier(armed):
    _seed_table(armed, {"potrf:256": {"tier": "float128",
                                      "pipeline_depth": 1}})
    tier, depth = tune.driver_config("potrf", 192)
    assert tier == "bf16_6x" and depth == 1


def test_driver_config_no_entry_disarms_leaked_rung(armed):
    """An untuned routine×bucket must disarm whatever a previous tuned
    call armed: the traced program may depend only on (routine, bucket,
    table content) — never on call order — or two processes with the
    same table could persist numerically different executables under
    one cached_jit key."""
    from slate_tpu.internal import pallas_kernels as pk
    _seed_table(armed, {"potrf:256": {"rung": "pallas"},
                        "getrf:512": {"pipeline_depth": 1}})
    try:
        tune.driver_config("potrf", 192)
        assert pk.rung_enabled("trsm")
        tune.driver_config("getrf", 192)         # no table entry
        assert not pk.rung_enabled("trsm")
        tune.driver_config("potrf", 192)
        assert pk.rung_enabled("panel_plu")
        tune.driver_config("getrf", 384)         # entry without a rung
        assert not pk.rung_enabled("panel_plu")
    finally:
        for k in ("panel_plu", "trsm", "rank_k"):
            pk.set_rung(k, None)


def test_pinned_counted_only_when_table_decides(armed):
    _seed_table(armed, {"potrf:256": {"tier": "bf16_3x",
                                      "pipeline_depth": 1}})
    before = metrics.counter_total("tune.pinned")
    opts = {Option.TrailingPrecision: "mxu_bf16",
            Option.PipelineDepth: 2}
    # explicit Options pin every knob and the entry carries no rung:
    # the table decided nothing, so the counter must not move
    tune.driver_config("potrf", 192, opts)
    assert metrics.counter_total("tune.pinned") == before
    # drop one explicit pin → the table fills it → counted
    tune.driver_config("potrf", 192,
                       {Option.TrailingPrecision: "mxu_bf16"})
    assert metrics.counter_total("tune.pinned") == before + 1


def test_recommended_nb(armed):
    _seed_table(armed, {"potrf:256": {"nb": 64}})
    assert tune.recommended_nb("potrf", 192) == 64
    assert tune.recommended_nb("getrf", 192, default=96) == 96


def test_driver_pins_through_potrf(armed, grid11):
    """End to end in-process: an armed winner reaches st.potrf."""
    _seed_table(armed, {"potrf:256": {"nb": 64, "rung": "xla",
                                      "tier": "bf16_3x",
                                      "pipeline_depth": 0}})
    before = metrics.counter_total("tune.pinned")
    a = spd(192, np.float64, seed=3)
    A = st.HermitianMatrix.from_dense(a, nb=64, grid=grid11)
    L, info = st.potrf(A)
    assert int(info) == 0
    assert metrics.counter_total("tune.pinned") > before


# ---------------------------------------------------------------------------
# the two-process pinning proof (ISSUE 14 acceptance)
# ---------------------------------------------------------------------------

def _subproc_env(cache_root):
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
    return env


def _run(cmd, env):
    r = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, (cmd, r.stdout[-2000:], r.stderr[-2000:])
    return r.stdout


_PINNED_SCRIPT = r"""
import numpy as np
import slate_tpu as st
from slate_tpu import tune
from slate_tpu.obs import metrics
metrics.enable()
n, nb = 192, tune.recommended_nb("potrf", 192)
g = np.random.default_rng(0).standard_normal((n, n)).astype(np.float32)
a = (g @ g.T / n + 2.0 * np.eye(n, dtype=np.float32))
A = st.HermitianMatrix.from_dense(a, nb=nb)
L, info = st.potrf(A)
print("INFO", int(info))
print("NB", nb)
print("PINNED", metrics.counter_total("tune.pinned"))
print("SWEEPS", metrics.counter_total("tune.sweep"))
print("TOKEN", tune.key_token())
"""


def test_two_process_sweep_then_pinned(tmp_path):
    env = _subproc_env(tmp_path / "exec")
    # process A: sweep and persist winners for the potrf 256-bucket
    out_a = _run([sys.executable, "-m", "slate_tpu.tune",
                  "--routine", "potrf", "--sizes", "192", "--nb", "64",
                  "--budget-s", "300"], env)
    facts = dict(ln.split("=", 1) for ln in out_a.splitlines()
                 if "=" in ln and not ln.startswith(("{", " ", "}")))
    assert int(facts["WINNERS"]) >= 1, out_a
    assert float(facts["SWEEP_COUNT"]) >= 1, out_a
    table = Path(facts["TABLE"])
    assert table.exists() and table.name == "tuning.json"
    # process B: fresh process resolves the tuned config — pinned,
    # zero sweeps
    out_b = _run([sys.executable, "-c", _PINNED_SCRIPT], env)
    got = dict(ln.split(None, 1) for ln in out_b.splitlines())
    assert got["INFO"] == "0"
    assert float(got["PINNED"]) >= 1, out_b
    assert float(got["SWEEPS"]) == 0, out_b
    assert got["TOKEN"].startswith("tune:") and \
        got["TOKEN"] != "tune:off"
    # B's persisted executable keys carry the table token: re-tuning
    # can never replay a stale binary
    metas = list((tmp_path / "exec").rglob("*.meta.json"))
    assert metas, "process B persisted no executables"
    tokens = set()
    for mp in metas:
        key = json.loads(mp.read_text()).get("key", [])
        tokens.update(k for k in key if isinstance(k, str)
                      and k.startswith("tune:"))
    assert got["TOKEN"] in tokens, (got["TOKEN"], tokens)

