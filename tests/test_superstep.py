"""linalg/_superstep.py against fakes: the donation rule, the abft
strike protocol and the save-after-verify ordering, which potrf and
getrf both run through and which the whole-factorization tests
(test_abft, test_ckpt, test_pipeline) only see from outside.  Every
case runs for both carried-state shapes: potrf's ``(info,)`` and
getrf's ``(piv, info)``."""

import types

import numpy as np
import pytest

import jax.numpy as jnp

from slate_tpu import obs
from slate_tpu.linalg import _superstep as ss
from slate_tpu.robust import abft

KT, S = 12, 4                      # three chunks: k0 = 0, 4, 8

SHAPES = pytest.mark.parametrize(
    "routine,names", [("potrf", ("info",)), ("getrf", ("piv", "info"))],
    ids=["info", "piv_info"])


def fresh_of(names):
    return tuple("fresh_" + nm if nm != "info" else 0 for nm in names)


def operand(data="A0"):
    return types.SimpleNamespace(
        data=data, nb=4, mt=KT, grid=types.SimpleNamespace(p=2, q=2))


class Step:
    """A chunk executable that only records: the output buffer is named
    after its input and k0, so a test can tell which buffer a launch
    started from."""

    def __init__(self, names, log, info_at=None):
        self.names, self.log, self.calls = names, log, []
        self.info_at = info_at or {}

    def __call__(self, data, carried, k0, klen, donate):
        self.calls.append(types.SimpleNamespace(
            data=data, carried=carried, k0=k0, klen=klen, donate=donate))
        self.log.append(("step", k0))
        state = tuple(f"{nm}@{k0 + klen}" for nm in self.names[:-1])
        return (f"{data}>{k0 + klen}", *state, self.info_at.get(k0, 0))


class Ckpt:
    def __init__(self, log, unsafe=()):
        self.log, self.unsafe, self.saved = log, set(unsafe), []

    def check_preempt(self, k0):
        self.log.append(("preempt?", k0))

    def donation_safe(self, arr):
        return arr not in self.unsafe

    def due(self, k0, klen):
        return True

    def save_async(self, k_next, **arrays):
        self.saved.append((k_next, arrays))
        self.log.append(("save", k_next))


class Abft:
    """``bad``: the (k1, attempt) verifications that fail; ``strikes``:
    what strike() answers, in order."""

    def __init__(self, log, bad=(), strikes=()):
        self.log, self.bad, self.strikes = log, set(bad), list(strikes)
        self.seen, self.inits, self.noted, self.struck = {}, [], 0, []

    def init(self, data):
        self.inits.append(data)

    def verify(self, data, k1, phase="chunk"):
        n = self.seen[k1] = self.seen.get(k1, 0) + 1
        ok = (k1, n) not in self.bad
        self.log.append(("verify", k1, phase, ok))
        return abft.ChunkVerdict(ok=ok, resid=0.0 if ok else 0.25,
                                 tile_col=-1 if ok else 7)

    def strike(self, k0):
        self.struck.append(k0)
        return self.strikes.pop(0)

    def note(self):
        self.noted += 1


def run(routine, names, *, ck=None, ab=None, overwrite_a=False, log=None,
        step=None, resume=None, A=None):
    log = [] if log is None else log
    step = step or Step(names, log)
    out = ss.run_chunks(ss.Guard(routine, ck, ab), A or operand(), step,
                        fresh_of(names), names, KT, S, overwrite_a, resume)
    return out, step, log


@SHAPES
@pytest.mark.parametrize("overwrite_a", [False, True])
def test_first_chunk_donates_only_with_overwrite_a(routine, names,
                                                   overwrite_a):
    out, step, _ = run(routine, names, overwrite_a=overwrite_a)
    assert [c.k0 for c in step.calls] == [0, 4, 8]
    assert [c.klen for c in step.calls] == [4, 4, 4]
    assert [c.donate for c in step.calls] == [overwrite_a, True, True]
    # the chain of buffers: each chunk starts from the one before
    assert step.calls[0].data == "A0" and out[0] == "A0>4>8>12"
    assert step.calls[0].carried == fresh_of(names)
    assert out[-1] == 0 and len(out) == 1 + len(names)


@SHAPES
def test_a_buffer_a_save_still_reads_is_not_donated(routine, names):
    log = []
    ck = Ckpt(log, unsafe={"A0>4"})
    _, step, _ = run(routine, names, ck=ck, overwrite_a=True, log=log)
    assert [c.donate for c in step.calls] == [True, False, True]
    assert [e for e in log if e[0] == "preempt?"] == [
        ("preempt?", 0), ("preempt?", 4), ("preempt?", 8)]


@SHAPES
def test_abft_never_donates_and_brackets_the_run(routine, names):
    log = []
    ab = Abft(log)
    _, step, _ = run(routine, names, ab=ab, overwrite_a=True, log=log)
    assert [c.donate for c in step.calls] == [False, False, False]
    assert ab.inits == ["A0"] and ab.noted == 1
    assert [e[1:] for e in log if e[0] == "verify"] == [
        (4, "chunk", True), (8, "chunk", True), (12, "chunk", True)]


@SHAPES
def test_retry_reruns_the_same_k0_from_the_chunk_entry_buffer(routine,
                                                              names):
    log = []
    ab = Abft(log, bad={(8, 1)}, strikes=["retry"])
    out, step, _ = run(routine, names, ab=ab, log=log)
    assert [c.k0 for c in step.calls] == [0, 4, 4, 8]
    first, again = step.calls[1], step.calls[2]
    assert first.data == again.data == "A0>4"
    assert first.carried == again.carried
    assert ab.struck == [4] and out[0] == "A0>4>8>12"


@SHAPES
def test_scratch_restarts_at_chunk_0_from_the_callers_state(routine,
                                                            names):
    log = []
    ab = Abft(log, bad={(8, 1), (8, 2)}, strikes=["retry", "scratch"])
    out, step, _ = run(routine, names, ab=ab, log=log)
    assert [c.k0 for c in step.calls] == [0, 4, 4, 0, 4, 8]
    restart = step.calls[3]
    assert restart.data == "A0" and restart.carried == fresh_of(names)
    assert out[0] == "A0>4>8>12" and ab.noted == 1


@SHAPES
def test_fail_raises_sdc_with_the_verifiers_tile_and_residual(routine,
                                                              names):
    ab = Abft([], bad={(4, 1)}, strikes=["fail"])
    with pytest.raises(abft.SdcDetected) as ei:
        run(routine, names, ab=ab)
    e = ei.value
    assert (e.routine, e.phase, e.tile_col, e.resid) == (
        routine, "chunk", 7, 0.25)
    assert ab.noted == 0


@SHAPES
def test_only_verified_states_are_saved_and_before_the_next_launch(
        routine, names):
    log = []
    ck = Ckpt(log)
    ab = Abft(log, bad={(8, 1)}, strikes=["retry"])
    run(routine, names, ck=ck, ab=ab, log=log)
    order = [e[:2] for e in log if e[0] in ("step", "verify", "save")]
    assert order == [
        ("step", 0), ("verify", 4), ("save", 4),
        ("step", 4), ("verify", 8),              # failed: no save
        ("step", 4), ("verify", 8), ("save", 8),
        ("step", 8), ("verify", 12), ("save", 12)]
    k_next, arrays = ck.saved[1]
    assert k_next == 8 and arrays["data"] == "A0>4>8"
    assert set(arrays) == {"data", *names} and arrays["info"] == 0
    if "piv" in names:
        assert arrays["piv"] == "piv@8"


@SHAPES
def test_a_nonzero_info_is_not_verified(routine, names):
    """The factorization already reports a failure: there is no factor
    for the checksums to hold on."""
    log = []
    ab = Abft(log)
    step = Step(names, log, info_at={4: 3})
    run(routine, names, ab=ab, log=log, step=step)
    assert [e[1] for e in log if e[0] == "verify"] == [4, 12]
    assert [c.k0 for c in step.calls] == [0, 4, 8]


@SHAPES
def test_unguarded_loop_never_reads_the_device(routine, names,
                                               monkeypatch):
    def no_sync(*a, **k):
        raise AssertionError("host sync on the unguarded path")
    monkeypatch.setattr(obs, "sync_read", no_sync)
    _, step, _ = run(routine, names)
    assert len(step.calls) == 3


@SHAPES
def test_resume_reenters_at_the_saved_boundary(routine, names):
    A = operand(jnp.zeros((2, 2)))
    arrays = {"data": np.ones((2, 2)), "info": np.int32(0)}
    if "piv" in names:
        arrays["piv"] = np.arange(3, dtype=np.int32)
    _, step, _ = run(routine, names, A=A,
                     resume={"arrays": arrays, "k_next": 8})
    (call,) = step.calls
    assert call.k0 == 8 and call.donate is True   # an intermediate buffer
    np.testing.assert_array_equal(np.asarray(call.data), arrays["data"])
    assert len(call.carried) == len(names)
    for nm, got in zip(names, call.carried):
        np.testing.assert_array_equal(np.asarray(got), arrays[nm])


def launch_of(names, calls, infos=()):
    infos = list(infos)

    def launch(donate):
        calls.append(donate)
        state = tuple(nm + "!" for nm in names[:-1])
        return (f"F{len(calls)}", *state, infos.pop(0) if infos else 0)
    return launch


@SHAPES
@pytest.mark.parametrize("overwrite_a", [False, True])
def test_one_program_unguarded_is_one_launch(routine, names, overwrite_a,
                                             monkeypatch):
    monkeypatch.setattr(obs, "sync_read", None)     # calling it fails
    calls = []
    out = ss.run_one_program(ss.Guard(routine, None, None), operand(),
                             launch_of(names, calls), KT, overwrite_a)
    assert calls == [overwrite_a]
    assert out[0] == "F1" and out[-1] == 0 and len(out) == 1 + len(names)


@SHAPES
def test_one_program_relaunches_until_verified_or_failed(routine, names):
    log, calls = [], []
    ab = Abft(log, bad={(KT, 1), (KT, 2)}, strikes=["retry", "scratch"])
    out = ss.run_one_program(ss.Guard(routine, None, ab), operand(),
                             launch_of(names, calls), KT, True)
    assert calls == [False, False, False] and out[0] == "F3"
    assert ab.struck == [0, 0] and ab.noted == 1 and ab.inits == ["A0"]
    assert {e[2] for e in log if e[0] == "verify"} == {"final"}

    ab = Abft([], bad={(KT, 1)}, strikes=["fail"])
    with pytest.raises(abft.SdcDetected) as ei:
        ss.run_one_program(ss.Guard(routine, None, ab), operand(),
                           launch_of(names, []), KT, False)
    assert (ei.value.routine, ei.value.phase, ei.value.tile_col) == (
        routine, "final", 7)
