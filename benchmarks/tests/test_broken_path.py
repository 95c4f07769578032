"""Drive a whole run (everything after the look for a chip) with the
timed path broken underneath, and see ``correct`` come out false."""

import argparse

import jax
import pytest

import slate_tpu as slate
from benchmarks import run as bench_run
from benchmarks.harness import cells


def small(cell, n=256, nb=64):
    spec = cells.load_cell(cell, n=n, nb=nb)
    # the cell's Frobenius limit is set at n=16384 on the chip, where
    # the check's own rounding is small; at this size on the CPU a sound
    # answer reads 0.2 eps. The faults below miss it by orders.
    spec["cell"]["tol_fro_eps"] = max(spec["cell"]["tol_fro_eps"], 1.0)
    return spec


def drive(spec, monkeypatch, tmp_path, broken=None):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    routine = spec["traffic"]["routine"]
    if broken is not None:
        monkeypatch.setattr(slate, routine, broken(getattr(slate, routine)))
    args = argparse.Namespace(seed=11, seconds=0.5, trace=0,
                              keep_trace=None)
    return bench_run.run_cell(spec, jax.devices(), args, rehearsal=True)


def altered_answer(solve):
    """An answer altered where it is produced: one entry of X moved by
    a thousandth of its size."""
    def wrapped(A, B, opts=None):
        out = solve(A, B, opts)
        X = out[0]
        data = X.data.at[(0,) * X.data.ndim].multiply(1.001)
        return (X._replace(data=data),) + tuple(out[1:])
    return wrapped


def nonzero_info(solve):
    def wrapped(A, B, opts=None):
        out = solve(A, B, opts)
        return tuple(out[:-1]) + (out[-1] + 3,)
    return wrapped


def stale_after_warm_up(solve):
    """A step that returns its state unchanged: after the first call
    every answer is B itself."""
    calls = []

    def wrapped(A, B, opts=None):
        out = solve(A, B, opts)
        calls.append(1)
        if len(calls) == 1:
            return out
        return (B,) + tuple(out[1:])
    return wrapped


@pytest.mark.parametrize("cell", ["posv_16k_1x1", "gesv_16k_1x1"])
def test_a_sound_run_is_correct(cell, monkeypatch, tmp_path):
    result = drive(small(cell), monkeypatch, tmp_path)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("broken", [altered_answer, stale_after_warm_up])
def test_a_broken_answer_is_not_correct(broken, monkeypatch, tmp_path):
    result = drive(small("posv_16k_1x1"), monkeypatch, tmp_path, broken)
    assert result["correct"] is False
    assert result["failed"] == 0        # the calls ran; the check caught it


def test_a_nonzero_info_fails_the_run(monkeypatch, tmp_path):
    with pytest.raises(SystemExit):     # the warm-up itself answers info 3
        drive(small("gesv_16k_1x1"), monkeypatch, tmp_path, nonzero_info)
