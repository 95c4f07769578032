"""The seven readers of the program's compile ledger on a hand-made
ledger: the window's cut, the phases summing to ``setup_s``, and ``None``
on a program without ``compile_ledger``."""

import json

import pytest

from benchmarks.harness import setup_ledger
from benchmarks.layer_metrics import (cache_miss_compile_s,
                                      calls_that_compiled, operands_s,
                                      programs_traced, setup_lower_s,
                                      setup_trace_s, setup_unattributed_s)

READERS = (setup_trace_s, setup_lower_s, programs_traced,
           cache_miss_compile_s, operands_s, calls_that_compiled,
           setup_unattributed_s)
T0 = 500.0                  # perf_counter seconds at process start


def rec(name, sid, solve, start, end, **labels):
    return {"name": name, "id": sid, "parent": solve and sid - 1,
            "solve": solve, "start_ns": int((T0 + start) * 1e9),
            "end_ns": int((T0 + end) * 1e9), "labels": labels}


def root(name, sid, solve, start, end, compiled, **labels):
    return {**rec(name, sid, solve, start, end, **labels), "parent": 0,
            "compiled": compiled}


def hand_run(call=None):
    """A set-up of 30 s: the import [2, 4], the A generator [5, 8] (one
    trace, lowering and cache load under it), B's [8, 9], two warm-up
    calls [10, 24] and [24.5, 25]; a compile outside every root
    [4.5, 5.5] whose last half second overlaps the generator's root;
    then the window: a traced solve that lowers again, and the check's
    compile outside every root."""
    name = "slate." + (call or "gesv")
    records = [
        rec("slate.import", 1, 0, 2.0, 4.0, jax_preloaded=True),
        rec("compile.backend", 2, 0, 4.5, 5.5, program="iota",
            cache="hit", retrieval_s=0.9),
        rec("compile.trace", 11, 1, 5.5, 6.5, program="_random_bc",
            inner_traces=40),
        rec("compile.lower", 12, 1, 6.5, 7.0, program="_random_bc"),
        rec("compile.backend", 13, 1, 7.0, 7.5, program="_random_bc",
            cache="hit", retrieval_s=0.4),
        rec("compile.trace", 21, 2, 8.1, 8.3, program="_random_bc",
            inner_traces=40),
        rec("compile.trace", 31, 3, 10.0, 14.0, program="_lu",
            inner_traces=4000),
        rec("compile.lower", 32, 3, 14.0, 20.0, program="_lu"),
        rec("compile.backend", 33, 3, 20.0, 23.0, program="_lu",
            cache="miss"),
        rec("compile.backend", 34, 3, 23.0, 23.5, program="_trsm",
            cache="off"),
        # after the cut
        rec("compile.lower", 51, 5, 31.0, 31.25, program="_lu"),
        rec("compile.trace", 61, 0, 80.0, 80.5, program="norm",
            inner_traces=0),
        rec("compile.backend", 62, 0, 80.5, 81.0, program="norm",
            cache="miss"),
    ]
    roots = [
        root("slate.random_matrix", 10, 1, 5.0, 8.0, True, m=64, n=64),
        root("slate.random_matrix", 20, 2, 8.0, 9.0, True, m=64, n=8),
        root(name, 30, 3, 10.0, 24.0, True, routine="gesv"),
        root(name, 40, 4, 24.5, 25.0, False, routine="gesv"),
        root(name, 50, 5, 31.0, 31.5, True, routine="gesv"),
    ]
    by_program = {
        "iota": {"backend_compile": [1.0, 1], "cache_retrieval": [0.9, 1],
                 "cache": {"hit": 1}},
        "_random_bc": {"trace": [1.2, 2], "lower": [0.5, 1],
                       "backend_compile": [0.5, 1],
                       "cache_retrieval": [0.4, 1], "cache": {"hit": 1}},
        "_lu": {"trace": [4.0, 1], "lower": [6.25, 2],
                "backend_compile": [3.0, 1], "cache": {"miss": 1}},
        "_trsm": {"backend_compile": [0.5, 1], "cache": {"off": 1}},
        # three traces of it past the ledger's bound
        "norm": {"trace": [0.5 + 0.3, 1 + 3], "backend_compile": [0.5, 1],
                 "cache": {"miss": 1}},
    }
    traffic = {"routine": "gesv", "warm_up_calls": 2}
    if call:
        traffic["call"] = call
    return {"spec": {"traffic": traffic}, "setup_s": 30.0,
            "device": {"platform": "tpu", "kind": "hand", "count": 1},
            "compile_ledger": {"records": records, "roots": roots,
                               "by_program": by_program, "dropped": 3,
                               "listener_s": 0.01}}


@pytest.mark.parametrize("call", [None, "gesv_mixed_gmres"])
def test_the_cut_is_the_end_of_the_last_warm_up_call(call):
    setup = setup_ledger.cut(hand_run(call))
    assert setup.cut_ns == int((T0 + 25.0) * 1e9)
    assert [r["id"] for r in setup.calls] == [30, 40]
    assert [r["id"] for r in setup.roots] == [10, 20, 30, 40]
    assert [r["id"] for r in setup.after] == [51, 61, 62]
    assert [r["id"] for r in setup.generators()] == [10, 20]


def test_the_seven_readers(capsys):
    run = hand_run()
    assert setup_trace_s.compute(run) == pytest.approx(1.0 + 0.2 + 4.0)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["step"] == "compile_ledger" and line["kind"] == "hand"
    assert line["dropped"] == 3 and line["listener_s"] == 0.01
    assert line["seconds_after_cut"] == pytest.approx(
        {"lower": 0.25, "trace": 0.5, "backend_compile": 0.5})
    first = line["programs"][0]
    assert first["program"] == "_lu"
    assert first["total_s"] == pytest.approx(13.25)
    assert first["cache"] == {"miss": 1}
    assert first["paid_by"] == pytest.approx(
        {"slate.gesv[2]": 13.0, "slate.gesv[4]": 0.25})
    by_name = {row["program"]: row for row in line["programs"]}
    assert by_name["iota"]["paid_by"] == {"(outside)": 1.0}
    assert [(r["name"], r["compiled"], r["before_window"])
            for r in line["roots"]][-2:] == [
        ("slate.gesv", False, True), ("slate.gesv", True, False)]
    assert setup_lower_s.compute(run) == pytest.approx(0.5 + 6.0)
    # four kept before the cut, and the three the ledger dropped
    assert programs_traced.compute(run) == 3 + 3
    assert cache_miss_compile_s.compute(run) == pytest.approx(3.0)
    assert operands_s.compute(run) == pytest.approx(3.0 + 1.0)
    assert calls_that_compiled.compute(run) == 4
    # one accounting: before + after the cut is the program's own sum
    # of the two kinds (trace_lower_s), the dropped traces aside
    totals = run["compile_ledger"]["by_program"]
    whole = sum(t[k][0] for t in totals.values()
                for k in ("trace", "lower") if k in t) - 0.3
    assert (setup_trace_s.compute(run) + setup_lower_s.compute(run)
            + line["seconds_after_cut"]["trace"]
            + line["seconds_after_cut"]["lower"]) == pytest.approx(whole)


def test_the_phases_sum_to_setup_s(capsys):
    run = hand_run()
    left = setup_unattributed_s.compute(run)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["step"] == "setup_by_phase" and line["setup_s"] == 30.0
    phases = line["seconds"]
    assert phases == pytest.approx({
        "import": 2.0, "operands": 4.0, "warm_up_1": 14.0,
        "warm_up_2": 0.5, "other_roots": 0.0,
        # [4.5, 5.5] less the half second under the generator's root
        "compile_outside_roots": 0.5, "unattributed": 9.0})
    assert sum(phases.values()) == pytest.approx(run["setup_s"])
    assert left == pytest.approx(9.0)
    # ... and where they lie: 1 s from the import's end to the generator
    # less the half second that compiled there, half a second between
    # the warm-up calls, and the rest before the import or after the cut
    assert line["unattributed_where"] == pytest.approx({
        "import_to_operands": 0.5, "operands_to_operands": 0.0,
        "operands_to_warm_up_1": 1.0, "warm_up_1_to_warm_up_2": 0.5,
        "before_import_or_after_warm_up": 7.0})
    # a root that is neither a generator's nor the mix's call
    run["compile_ledger"]["roots"].insert(2, root(
        "matrix.materialize", 25, 9, 9.25, 9.75, False))
    phases = setup_ledger.by_phase(run)
    assert phases["other_roots"] == pytest.approx(0.5)
    assert phases["unattributed"] == pytest.approx(8.5)
    assert sum(phases.values()) == pytest.approx(run["setup_s"])


def test_programs_past_the_top_are_summed():
    rows = setup_ledger.by_program(setup_ledger.cut(hand_run()), top=2)
    assert [row["program"] for row in rows] == ["_lu", "_random_bc",
                                                "(others)"]
    assert rows[-1]["programs"] == 3
    assert rows[-1]["total_s"] == pytest.approx(1.0 + 0.5 + 1.3)


def test_a_ledger_without_the_warm_up_calls_is_an_error():
    run = hand_run()
    run["compile_ledger"]["roots"] = run["compile_ledger"]["roots"][:3]
    with pytest.raises(ValueError, match="1 kept root.s. named slate.gesv"):
        setup_lower_s.compute(run)


@pytest.mark.parametrize("reader", READERS)
def test_a_program_without_the_ledger_reads_nothing(reader, monkeypatch):
    """The parent commit: ``obs`` has no ``compile_ledger``."""
    from slate_tpu import obs
    monkeypatch.delattr(obs, "compile_ledger", raising=False)
    run = hand_run()
    del run["compile_ledger"]
    assert reader.compute(run) is None


def test_the_programs_own_ledger_is_read(monkeypatch):
    """No ``run["compile_ledger"]``: the readers ask the program, whose
    generators and solver open the roots the cut needs."""
    import jax
    import numpy as np
    import slate_tpu as slate
    from slate_tpu import obs
    if not hasattr(obs, "compile_ledger"):
        pytest.skip("a program from before the ledger")
    obs.reset()
    grid = slate.Grid(1, 1, devices=jax.devices()[:1])
    A = slate.random_spd(128, nb=32, grid=grid, dtype=np.float32, seed=1)
    B = slate.random_matrix(128, 2, 32, grid, np.float32, seed=2)
    for _ in range(3):
        jax.block_until_ready(slate.posv(A, B))
    run = {"spec": {"traffic": {"routine": "posv", "warm_up_calls": 2}},
           "setup_s": 1e4, "device": {}}
    setup = setup_ledger.cut(run)
    assert [r["name"] for r in setup.roots] == [
        "slate.random_spd", "slate.random_matrix", "slate.posv",
        "slate.posv"]
    assert calls_that_compiled.compute(run) == 3
    assert operands_s.compute(run) > 0.0
    assert setup_trace_s.compute(run) > 0.0
    assert setup_lower_s.compute(run) > 0.0
    assert programs_traced.compute(run) > 3
    misses = sum(setup_ledger.seconds(r) for r in setup.before
                 if r["labels"].get("cache") == "miss")
    assert cache_miss_compile_s.compute(run) == pytest.approx(misses)
    phases = setup_ledger.by_phase(run)
    assert sum(phases.values()) == pytest.approx(1e4)
    for r in setup.ledger["records"]:
        if r["name"].startswith("compile."):
            assert r["labels"]["program"]
            if r["name"] == "compile.backend":
                assert r["labels"]["cache"] in ("hit", "miss", "off")
