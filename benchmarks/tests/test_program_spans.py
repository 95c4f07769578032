"""The five readers of the program's spans on a hand-made ``Reduced``
and span list with a known gap, and ``span_report``'s arithmetic."""

import json

import pytest

from benchmarks import span_report
from benchmarks.harness import program_spans, trace_reduce as tr
from benchmarks.layer_metrics import (host_syncs_per_solve,
                                      idle_attributed_share,
                                      relayout_bytes_per_solve,
                                      relayout_host_s, trace_lower_s)

OFFSET = -1000.0            # trace axis = perf_counter seconds - 1000


def span(name, sid, parent, solve, start, end, **labels):
    return {"name": name, "id": sid, "parent": parent, "solve": solve,
            "start_ns": int((start - OFFSET) * 1e9),
            "end_ns": int((end - OFFSET) * 1e9), "labels": labels}


def hand_run(shift=0.0):
    """Two solves of 1 s on the trace's axis, [0, 1] and [2, 3]. Device
    0 is busy [0.1, 0.3] and [0.8, 0.95] of each: idle 0.1 before the
    first program (under ``potrf``), 0.5 in the middle (0.4 of it under
    ``materialize.device_put``, 0.05 each side under ``trsm`` alone),
    0.05 after the root span ends at 0.95."""
    ops, solves, spans = [], [], []
    for i, base in enumerate((0.0, 2.0)):
        solves.append((base, base + 1.0))
        ops += [("fusion.1", base + 0.1, base + 0.3, {"opcode": "fusion"}),
                ("fusion.2", base + 0.8, base + 0.95,
                 {"opcode": "fusion"})]
        s, k = i + 1, 10 * i
        b = base + (shift if i else 0.0)
        spans += [
            span("slate.posv", k + 1, 0, s, b, b + 0.95),
            span("potrf", k + 2, k + 1, s, b + 0.002, b + 0.25),
            span("trsm", k + 3, k + 1, s, b + 0.25, b + 0.9),
            span("matrix.materialize", k + 4, k + 3, s, b + 0.35, b + 0.75,
                 bytes=1 << 30),
            span("materialize.device_put", k + 5, k + 4, s, b + 0.35,
                 b + 0.75),
            span("gesv.order_to_ipiv", k + 6, k + 1, s, b + 0.9, b + 0.94,
                 sync=1),
            span("compile", k + 7, k + 3, s, b + 0.3, b + 0.3)]
    trace = tr.Reduced(devices={0: tr.DeviceTrace(ops=ops)}, solves=solves)
    return {"trace": trace, "program_spans": spans,
            "spec": {"traffic": {"routine": "posv"}},
            "device": {"platform": "tpu", "kind": "hand", "count": 1}}


def test_idle_is_attributed_to_the_innermost_span(capsys):
    run = hand_run()
    by_span = program_spans.idle_by_span(run)
    assert by_span["materialize.device_put"] == pytest.approx(0.8)
    assert by_span["trsm"] == pytest.approx(0.2)
    assert by_span["potrf"] == pytest.approx(2 * 0.098)
    assert by_span["matrix.materialize"] == pytest.approx(0.0)
    assert by_span["(root)"] == pytest.approx(2 * 0.002)
    assert by_span["(outside)"] == pytest.approx(2 * 0.05)
    assert sum(by_span.values()) == pytest.approx(2 * 0.65)
    share = idle_attributed_share.compute(run)
    assert share == pytest.approx(100 * (0.8 + 0.2 + 0.196) / 1.3)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["step"] == "idle_by_span" and line["solves"] == 2
    assert list(line["seconds"])[0] == "materialize.device_put"
    assert line["kind"] == "hand"


def test_relayout_syncs_and_bytes_per_solve():
    run = hand_run()
    assert relayout_host_s.compute(run) == pytest.approx(0.4)
    assert relayout_bytes_per_solve.compute(run) == 1 << 30
    assert host_syncs_per_solve.compute(run) == 1
    # a redistribute around the materialize counts its wall once and
    # both byte labels
    run["program_spans"].append(span(
        "matrix.redistribute", 8, 3, 1, 0.3, 0.8, bytes=1 << 20))
    run["program_spans"].append(span(
        "matrix.redistribute", 18, 13, 2, 2.3, 2.8, bytes=1 << 20))
    assert relayout_host_s.compute(run) == pytest.approx(0.5)
    assert relayout_bytes_per_solve.compute(run) == (1 << 30) + (1 << 20)


def test_pairing_is_asserted():
    run = hand_run(shift=0.002)             # second root 2 ms off
    with pytest.raises(ValueError, match="line up"):
        idle_attributed_share.compute(run)
    run = hand_run()
    run["program_spans"] = [s for s in run["program_spans"]
                            if s["solve"] == 1]
    with pytest.raises(ValueError, match="1 captured slate.posv"):
        relayout_host_s.compute(run)


@pytest.mark.parametrize("reader", [idle_attributed_share, relayout_host_s,
                                    relayout_bytes_per_solve,
                                    host_syncs_per_solve, trace_lower_s])
def test_nothing_without_a_trace_or_without_spans(reader):
    run = hand_run()
    run["trace"] = None                     # --trace 0
    assert reader.compute(run) is None
    if reader is not trace_lower_s:
        run = hand_run()
        run["program_spans"] = []           # nothing captured
        assert reader.compute(run) is None


def test_a_program_without_the_spans_reads_nothing(monkeypatch):
    """The parent commit: ``obs`` has neither function."""
    from slate_tpu import obs
    monkeypatch.delattr(obs, "captured_spans", raising=False)
    monkeypatch.delattr(obs, "compile_seconds", raising=False)
    run = hand_run()
    del run["program_spans"]
    assert idle_attributed_share.compute(run) is None
    assert relayout_host_s.compute(run) is None
    assert trace_lower_s.compute(run) is None


def test_trace_lower_reads_the_programs_counter(monkeypatch, capsys):
    from slate_tpu import obs
    monkeypatch.setattr(obs, "compile_seconds", lambda: {
        "seconds": {"trace": 30.0, "lower": 5.5, "backend_compile": 6.0},
        "counts": {"trace": 9}, "top": [["_getrf_fast_core", 33.0]]})
    assert trace_lower_s.compute(hand_run()) == 35.5
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["step"] == "compile_seconds"
    assert line["top"] == [["_getrf_fast_core", 33.0]]


def test_span_report_agrees_with_the_reader():
    run = hand_run()
    solves = run["trace"].solves
    spans = [{"name": s["name"].removeprefix("slate."), "id": s["id"],
              "parent": s["parent"], "solve": s["solve"],
              "start": s["start_ns"] * 1e-9 + OFFSET,
              "end": s["end_ns"] * 1e-9 + OFFSET}
             for s in run["program_spans"] if s["end_ns"] > s["start_ns"]]
    idle = span_report.idle_by_annotation(
        solves, spans, run["trace"].first.busy())
    mine = program_spans.idle_by_span(run)
    assert idle["materialize.device_put"] == pytest.approx(
        mine["materialize.device_put"])
    assert idle["trsm"] == pytest.approx(mine["trsm"])
    assert idle["slate.posv"] == pytest.approx(mine["(root)"])
    assert idle["(no span)"] == pytest.approx(mine["(outside)"])
    scopes = span_report.busy_by_scope([
        ("%while.1 = while() jit(f)/shard_map/while/body", 0.0, 1.0),
        ("%fusion.2 jit(f)/while/body/closed_call/trailing/dot", 0.1, 0.6),
        ("%all-gather.3 jit(f)/while/body/panel_bcast/all_gather",
         0.6, 0.9)])
    assert scopes == {"(no scope)": pytest.approx(0.2),
                      "trailing": pytest.approx(0.5),
                      "panel_bcast": pytest.approx(0.3)}
