"""The reduction from a trace to numbers, on intervals made by hand
and on a small trace recorded on the chip (``recorded_*.json``: cut
from a real ``--trace 1`` run of PR 24 with ``cut_trace.py``), and the
flop formulas against hand values."""

import json
import os

import pytest

from benchmarks.harness import flops, trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def test_interval_arithmetic():
    merged = tr.merge([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert merged == [(0, 3), (5, 7)]
    assert tr.total(merged) == 5
    assert tr.clip(merged, 2, 6) == [(2, 3), (5, 6)]
    assert tr.subtract([(0, 10)], merged) == [(3, 5), (7, 10)]
    assert tr.subtract(merged, [(1, 6)]) == [(0, 1), (6, 7)]


def test_self_time_charges_a_parent_only_its_own_part():
    events = [("while", 0.0, 10.0), ("fusion.1", 1.0, 4.0),
              ("fusion.2", 4.0, 9.0), ("copy", 12.0, 13.0)]
    own = tr.self_times(events)
    assert own == {"while": 2.0, "fusion.1": 3.0, "fusion.2": 5.0,
                   "copy": 1.0}


def hand_trace():
    """Two solves of 10 ms; device 0 runs 2 modules a solve, a custom
    call, and an all-reduce half hidden behind a fusion."""
    ms = 1e6

    def ev(name, start, dur, **stats):
        return [name, start * ms, dur * ms, stats]

    def op(name, opcode, start, dur, **stats):
        return ev(name, start, dur, opcode=opcode, **stats)

    ops, mods, ann = [], [], []
    for base in (0.0, 12.0):
        ann.append(ev("bench.solve", base, 10.0))
        mods += [ev("jit_factor", base + 1, 6), ev("jit_solve", base + 8, 1)]
        ops += [op("while.3", "while", base + 1, 6),
                op("kernel.7", "custom-call", base + 1, 2,
                   target="tpu_custom_call"),
                op("fusion.1", "fusion", base + 3, 3),
                op("all-reduce.2", "all-reduce", base + 5, 2),
                op("fusion.9", "fusion", base + 8, 1)]
    return {"planes": [
        {"name": "/host:CPU",
         "lines": [{"name": "python", "events": ann}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": mods}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [
                op("fusion.1", "fusion", 0 + 1, 4),
                op("fusion.1", "fusion", 12 + 1, 4)]}]}]}


def test_reduce_a_hand_made_trace():
    red = tr.reduce(hand_trace())
    assert sorted(red.devices) == [0, 1]
    assert red.window_s == pytest.approx(0.022)
    dev0 = red.first
    assert tr.total(dev0.busy()) == pytest.approx(0.014)
    # busy mean over devices: (14 + 8) / 2 ms
    assert red.busy_s() == pytest.approx(0.011)
    assert red.per_solve(dev0.where(tr.is_kernel)) == pytest.approx(0.002)
    coll = dev0.where(tr.is_collective)
    assert red.per_solve(coll) == pytest.approx(0.002)
    # fusion.1 covers [3, 6): the all-reduce [5, 7) is exposed for 1 ms;
    # while.3 only spans other ops and hides nothing
    others = dev0.leaf_busy(keep=lambda st: not tr.is_collective(st))
    assert red.per_solve(tr.subtract(coll, others)) == pytest.approx(0.001)
    assert len(dev0.modules) / len(red.solves) == 2
    bd = tr.breakdown(red)
    ops = dict(bd["device_ops"])
    assert ops["jit_solve/fusion.9 (fusion)"] == pytest.approx(0.002)
    assert ops["jit_factor/kernel.7 (custom-call)"] == pytest.approx(0.004)
    # device 0 is idle from 9 ms (end of solve 1's last op) to 13 ms
    assert bd["idle_gaps"][0] == ["between solves", pytest.approx(0.004)]
    assert {g[0] for g in bd["idle_gaps"]} == {"inside bench.solve",
                                               "between solves"}


def test_hlo_text_is_parsed_into_name_opcode_and_target():
    kernel = ('%_getrf_fast_core.161 = (f32[8,1024,2048]{2,1,0:T(8,128)S(1)}, '
              's32[1,1]{1,0:T(1,128)}) custom-call(s32[1]{0:T(128)} '
              '%constant.513), custom_call_target="tpu_custom_call"')
    name, stats = tr.parse_instruction(kernel)
    assert name == "_getrf_fast_core.161" and tr.is_kernel(stats)
    xla_own = ('%custom-call.136 = f32[8,128]{1,0} custom-call(f32[8,128] '
               '%x), custom_call_target="InvertDiagBlocksLowerTriangular"')
    assert not tr.is_kernel(tr.parse_instruction(xla_own)[1])
    name, stats = tr.parse_instruction(
        "%all-reduce-start.3 = f32[1024]{0:T(1024)} all-reduce-start("
        "f32[1024]{0} %p), replica_groups={{0,1}}")
    assert name == "all-reduce-start.3" and tr.is_collective(stats)
    name, stats = tr.parse_instruction(
        "%while.15 = (s32[]{:T(128)}, f32[16,1]{1,0:T(8,128)S(1)}) "
        "while((s32[], f32[16,1]) %tuple), condition=%c, body=%b")
    assert (name, stats["opcode"]) == ("while.15", "while")
    assert tr.parse_instruction("all-reduce.3")[1]["opcode"] == "all-reduce"


def test_a_trace_without_device_or_solves_is_refused():
    raw = hand_trace()
    raw["planes"] = raw["planes"][:1]
    with pytest.raises(ValueError):
        tr.reduce(raw)


@pytest.mark.parametrize("n", [1024, 16384])
def test_flop_formulas_against_hand_values(n):
    assert flops.potrf(n) == pytest.approx(n ** 3 / 3)
    assert flops.getrf(n) == pytest.approx(2 * n ** 3 / 3)
    assert flops.routine_flops("posv", n, 8) == pytest.approx(
        n ** 3 / 3 + 2 * n * n * 8)
    assert flops.routine_flops("gesv", n, 8) == pytest.approx(
        2 * n ** 3 / 3 + 2 * n * n * 8)


def test_gesv_16k_flops_is_the_figure_perf_md_uses():
    assert flops.routine_flops("gesv", 16384, 8) == pytest.approx(
        2.936e12, rel=1e-3)


# ------------------------------------------------- a trace from the chip

def sweep_union(intervals) -> float:
    """Length of a union by counting coverage at every endpoint:
    another algorithm than ``merge``."""
    points = sorted([(s, 1) for s, _ in intervals]
                    + [(e, -1) for _, e in intervals])
    covered, depth, last = 0.0, 0, None
    for t, step in points:
        if depth > 0:
            covered += t - last
        depth += step
        last = t
    return covered


def test_reduce_a_trace_recorded_on_the_chip():
    """The first 400 device ops of one traced ``slate.gesv`` at n=16384
    on a TPU v5 lite (PR 24), the solve's span and its program's span
    cut at the same instant."""
    with open(os.path.join(HERE, "recorded_gesv_16k_1x1.json"),
              encoding="utf-8") as f:
        red = tr.reduce(json.load(f))
    dev0 = red.first
    assert len(dev0.ops) == 400 and len(red.solves) == 1
    assert [m[0] for m in dev0.modules] == ["jit__getrf_fast_core"]
    spans = [(s, e) for _, s, e, _ in dev0.ops]
    assert tr.total(dev0.busy()) == pytest.approx(sweep_union(spans),
                                                  rel=1e-9)
    assert tr.total(dev0.busy()) == pytest.approx(0.033946399, rel=1e-6)
    kernels = [(s, e) for _, s, e, st in dev0.ops if tr.is_kernel(st)]
    assert len(kernels) == 26        # the Pallas panel's calls so far
    assert red.per_solve(dev0.where(tr.is_kernel)) == pytest.approx(
        sum(e - s for s, e in kernels))
    assert red.per_solve(dev0.where(tr.is_kernel)) == pytest.approx(
        0.016082501, rel=1e-6)
    assert tr.total(dev0.where(tr.is_collective)) == 0     # one chip
    # the two whiles only span other ops
    assert tr.total(dev0.leaf_busy()) <= tr.total(dev0.busy())
    # the program starts 0.5 ms BEFORE the host span that launched it:
    # two clocks, which is why nothing clips device events by host times
    assert dev0.modules[0][1] < red.solves[0][0]
    top = tr.breakdown(red)["device_ops"][0]
    assert top[0] == "jit__getrf_fast_core/fusion.53 (fusion)"
    assert top[1] == pytest.approx(0.003483371, rel=1e-6)


def test_reduce_a_four_chip_trace_recorded_on_the_chip():
    """The first 150 ops on each of the four devices of one traced
    ``slate.posv`` on ``Grid(2,2)`` (TPU v5 lite 2x2, PR 24): the start
    of the first chunk program, with its first two all-reduces."""
    with open(os.path.join(HERE, "recorded_posv_16k_2x2.json"),
              encoding="utf-8") as f:
        red = tr.reduce(json.load(f))
    assert sorted(red.devices) == [0, 1, 2, 3]
    assert [len(d.ops) for d in red.devices.values()] == [150] * 4
    dev0 = red.first
    assert len(dev0.modules) == 2       # a convert, then the chunk core
    assert dev0.modules[1][0] == "jit__potrf_chunk_core"
    collectives = [(s, e) for _, s, e, st in dev0.ops
                   if tr.is_collective(st)]
    assert len(collectives) == 2
    coll = dev0.where(tr.is_collective)
    assert tr.total(coll) == pytest.approx(0.00019213, rel=1e-5)
    assert tr.total(coll) == pytest.approx(sweep_union(collectives))
    # on this core's timeline the all-reduces overlap no other leaf op:
    # all of their time is exposed
    others = dev0.leaf_busy(keep=lambda st: not tr.is_collective(st))
    assert tr.total(tr.subtract(coll, others)) == pytest.approx(
        tr.total(coll))
    busy = [tr.total(d.busy()) for d in red.devices.values()]
    assert busy == pytest.approx([0.003292544, 0.00329197, 0.003291762,
                                  0.003291854], rel=1e-6)
    assert red.busy_s() == pytest.approx(sum(busy) / 4)
    assert 100 * (1 - red.busy_s() / red.window_s) == pytest.approx(
        29.2548, rel=1e-4)
