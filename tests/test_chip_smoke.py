"""chip_smoke.py rehearsed in-process on the CPU mesh: the phases run
at a tiny size, every step line parses, and the verdict is a FAILURE
because the device is not a TPU — a CPU run can never pass."""

import json
import os

import jax
import pytest

import chip_smoke
from slate_tpu import obs
from slate_tpu.cache import xla_cache

TINY = ["--n", "512", "--nb", "128"]


@pytest.fixture
def smoke_env(monkeypatch):
    """chip_smoke.main sets process-wide state a script may and a test
    worker may not keep: jax's cache directory and obs metrics."""
    prev = getattr(jax.config, xla_cache.OPTION)
    # steer the LU onto the Pallas fast path (interpret mode here), the
    # control flow the chip takes at n=16384
    monkeypatch.setenv("SLATE_LU_FAST", "1")
    yield
    jax.config.update(xla_cache.OPTION, prev)
    obs.metrics_off()
    obs.reset()


def _run(capsys, argv):
    rc = chip_smoke.main(argv)
    out = capsys.readouterr().out.splitlines()
    return rc, [json.loads(line) for line in out]


def test_default_phase_rehearses_and_fails_on_cpu(smoke_env, capsys):
    rc, lines = _run(capsys, TINY + ["--rehearse-on-cpu"])
    assert rc != 0
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    steps = [ln["step"] for ln in lines[:-1]]
    assert steps == (["device", "posv", "gesv"] + ["served"] * 8
                     + ["served_summary", "done"])
    by = {ln["step"]: ln for ln in lines[:-1]}
    for routine in ("posv", "gesv"):
        ln = by[routine]
        assert (ln["n"], ln["nb"], ln["info"]) == (512, 128, 0)
        assert ln["backward_error_slate"] <= ln["bound"]
        assert ln["backward_error_plain"] <= ln["bound"]
    assert by["posv"]["path"] == "potrf:one_program"
    assert by["gesv"]["path"] == "getrf:fast_path/interpret"
    served = [ln for ln in lines[:-1] if ln["step"] == "served"]
    assert {ln["bucket"] for ln in served} == {256}
    assert all(40 <= ln["n"] <= 250 for ln in served)
    assert {ln["tenant"] for ln in served} == {"tenant-a", "tenant-b"}
    assert by["served_summary"]["resolved_once"] == 8
    assert by["served_summary"]["shed"] == 0


def test_four_chip_phase_rehearses_and_fails_on_cpu(smoke_env, capsys):
    rc, lines = _run(capsys, TINY + ["--rehearse-on-cpu", "--chips", "4"])
    assert rc != 0 and lines[-1]["ok"] is False
    assert [ln["step"] for ln in lines[:-1]] == [
        "device", "grid", "posv", "gesv", "memory", "done"]
    for ln in lines[2:4]:
        assert ln["grid"] == "2x2" and ln["info"] == 0
        assert ln["path"].endswith(":spmd_chunk")
        assert all(p["devices"] == 4 and p["shard_bytes"] * 4 == p["bytes"]
                   for p in ln["placement"].values())
        assert sum(ln["chunk_collectives"].values()) > 0
        assert ln["backward_error_plain"] <= ln["bound"]


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one_chip", "four_chips"])
def test_without_the_flag_it_stops_at_the_device_check(smoke_env, capsys,
                                                       argv):
    assert chip_smoke.main(argv) != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "TPU" in captured.err


def test_cache_placement_env_set_sets_nothing_in_code(monkeypatch):
    monkeypatch.setenv(xla_cache.ENV, "/some/dir")
    prev = getattr(jax.config, xla_cache.OPTION)
    assert xla_cache.place_jax_compile_cache() == "/some/dir"
    assert getattr(jax.config, xla_cache.OPTION) == prev


def test_cache_placement_env_unset_uses_the_checkout(monkeypatch,
                                                     smoke_env):
    monkeypatch.delenv(xla_cache.ENV, raising=False)
    checkout = os.path.dirname(os.path.abspath(chip_smoke.__file__))
    want = os.path.join(checkout, ".jax_cache")
    assert xla_cache.place_jax_compile_cache() == want
    assert getattr(jax.config, xla_cache.OPTION) == want
