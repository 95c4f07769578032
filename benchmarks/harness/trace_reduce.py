"""From a profiler trace to the numbers the per-layer metrics read.

``load_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes
into a plain dict (``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, dur_ns, {stat: value}], ...]}]}]}``) with nothing but
``jax.profiler.ProfileData``; ``reduce`` works on that dict alone, so
the reduction is checked on a small recorded trace committed under
``benchmarks/tests/`` and no chip is needed to read how a number is
made.

What the chip's trace looks like (TPU v5 lite, jax 0.9.0; looked at by
hand in PR 24 with ``dump_trace.py``): one plane ``/device:TPU:<i>`` per
chip. Its line ``XLA Modules`` has one event per executed program
(``jit__getrf_fast_core(<fingerprint>)``); its line ``XLA Ops`` has one
event per executed HLO instruction, named by the instruction's whole
text (``%fusion.51 = f32[...] fusion(...), kind=kOutput, ...``), with a
``while`` spanning its body's ops on the same line — so busy time is a
union and a ranking takes self time. A Pallas/Mosaic kernel is an
instruction whose opcode is ``custom-call`` and whose
``custom_call_target`` is ``tpu_custom_call``. A third line, ``Async XLA
Ops``, holds DMAs in flight and is not read: busy means the core's own
timeline. ``jax.profiler.TraceAnnotation`` spans are on the ``python``
line of the ``/host:CPU`` plane. The device's clock is NOT the host's:
a program was seen to start 0.5 ms before the host span that launched
it. So nothing here clips device events by host times: the trace is on
for the annotated solves and nothing else, every device event in it
belongs to them, and a per-solve figure is the total over the number of
solves. Only the label of an idle gap (inside a solve or between two)
compares the two clocks, and is good to about a millisecond.
"""

from __future__ import annotations

import bisect
import re
import statistics
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION = "bench.solve"
OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
TARGET = re.compile(r'custom_call_target="([^"]+)"')
FINGERPRINT = re.compile(r"\(\d+\)$")
COLLECTIVES = ("all-gather", "all-reduce", "all-to-all",
               "collective-permute", "reduce-scatter",
               "collective-broadcast")
MOSAIC_TARGET = "tpu_custom_call"


def is_collective(stats: dict) -> bool:
    """all-gather, all-reduce, ... and their ``-start``/``-done``."""
    return str(stats.get("opcode", "")).startswith(COLLECTIVES)


def is_kernel(stats: dict) -> bool:
    """A Pallas/Mosaic kernel (not XLA's own custom calls)."""
    return stats.get("opcode") == "custom-call" \
        and stats.get("target") == MOSAIC_TARGET


def parse_instruction(text: str):
    """``%fusion.51 = f32[..] fusion(..), kind=..`` → (``fusion.51``,
    ``{"opcode": "fusion"}``); a custom call also gets its ``target``.
    A name that is not HLO text is kept, with its own first word as the
    opcode (a CPU rehearsal's ``all-reduce.3``)."""
    name, sep, rest = text.partition(" = ")
    name = name.lstrip("%")
    if not sep:
        return name, {"opcode": re.sub(r"[._]?\d+$", "", name)}
    stats = {}
    m = OPCODE.search(" " + rest)
    if m:
        stats["opcode"] = m.group(1)
    t = TARGET.search(rest)
    if t:
        stats["target"] = t.group(1)
    return name, stats


# ------------------------------------------------------------------ load

def load_xplane(path: str, rehearsal: bool = False) -> dict:
    """The trace at ``path`` as the plain dict ``reduce`` reads: device
    planes' ``XLA Ops`` (short name, opcode, target) and ``XLA Modules``
    (fingerprint dropped), and the host's annotated solves.

    ``rehearsal`` (a CPU run, never a result): there is no device plane,
    so the host threads' events that carry an ``hlo_op`` stat are put
    on a pretend ``/device:TPU:0`` and every executed module counts as
    a launch, to walk the same code."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_dev = DEVICE_PLANE.match(plane.name)
        if not (is_dev or plane.name == HOST_PLANE):
            continue
        lines = []
        for line in plane.lines:
            if is_dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = []
            for ev in line.events:
                start, dur = float(ev.start_ns), float(ev.duration_ns)
                if is_dev and line.name == OPS_LINE:
                    name, stats = parse_instruction(ev.name)
                    events.append([name, start, dur, stats])
                elif is_dev:
                    events.append([FINGERPRINT.sub("", ev.name),
                                   start, dur, {}])
                elif ev.name == ANNOTATION:
                    events.append([ev.name, start, dur, {}])
                elif rehearsal:
                    stats = dict(ev.stats)
                    if "hlo_op" in stats:
                        events.append([ev.name, start, dur, {
                            k: stats.get(k) for k in
                            ("hlo_op", "hlo_module", "run_id")}])
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    raw = {"planes": planes}
    if rehearsal and not any(DEVICE_PLANE.match(p["name"])
                             for p in planes):
        raw = _pretend_device(raw)
    return raw


def _pretend_device(raw: dict) -> dict:
    ops, mods, seen, keep = [], [], set(), []
    for plane in raw["planes"]:
        for line in plane["lines"]:
            for ev in line["events"]:
                if ev[0] == ANNOTATION:
                    keep.append(ev)
                    continue
                name, stats = parse_instruction(ev[0])
                ops.append([name, ev[1], ev[2], stats])
                run = (ev[3].get("hlo_module"), ev[3].get("run_id"))
                if run not in seen:
                    seen.add(run)
                    mods.append([str(run[0]), ev[1], ev[2], {}])
    return {"planes": [
        {"name": HOST_PLANE,
         "lines": [{"name": "python", "events": keep}]},
        {"name": "/device:TPU:0",
         "lines": [{"name": OPS_LINE, "events": ops},
                   {"name": MODULES_LINE, "events": mods}]}]}


# -------------------------------------------------------------- intervals

def merge(intervals):
    """Sorted, disjoint union of ``(start, end)`` pairs."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(merged) -> float:
    return sum(e - s for s, e in merged)


def clip(merged, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if min(e, hi) > max(s, lo)]


def subtract(merged, holes):
    """The part of ``merged`` not covered by ``holes`` (both merged)."""
    out = []
    for s, e in merged:
        cur = s
        for hs, he in holes:
            if he <= cur or hs >= e:
                continue
            if hs > cur:
                out.append((cur, hs))
            cur = max(cur, he)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events):
    """``{name: seconds}`` where a parent (``while``, ``call``) is
    charged only the part of its span no child covers. ``events`` are
    ``(name, start, end)`` of ONE line, on which events nest."""
    out: dict[str, float] = {}
    stack = []      # [name, end, self]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for name, s, e in sorted(events, key=lambda t: (t[1], -(t[2]))):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return out


# ---------------------------------------------------------------- reduce

@dataclass
class DeviceTrace:
    ops: list = field(default_factory=list)       # (name, start, end, stats)
    modules: list = field(default_factory=list)   # (name, start, end)

    def busy(self):
        return merge((s, e) for _, s, e, _ in self.ops)

    def leaf_busy(self, keep=None):
        """Union of the ops that span no other op (a ``while`` is not a
        leaf) and whose stats pass ``keep``."""
        ops = sorted(self.ops, key=lambda o: (o[1], -o[2]))
        leaves = []
        for i, (_, s, e, stats) in enumerate(ops):
            parent = i + 1 < len(ops) and ops[i + 1][1] < e \
                and ops[i + 1][2] <= e
            if not parent and (keep is None or keep(stats)):
                leaves.append((s, e))
        return merge(leaves)

    def where(self, keep):
        """Union of the ops whose stats pass ``keep``."""
        return merge((s, e) for _, s, e, stats in self.ops if keep(stats))


@dataclass
class Reduced:
    """Seconds throughout. ``solves`` are on the host's clock, the
    devices' events on the device's."""
    devices: dict            # device index -> DeviceTrace
    solves: list             # [(start, end)] of the annotated solves

    @property
    def first(self) -> DeviceTrace:
        return self.devices[min(self.devices)]

    @property
    def window_s(self) -> float:
        """Start of the first annotated solve to the end of the last."""
        return self.solves[-1][1] - self.solves[0][0]

    def busy_s(self) -> float:
        """Union of op intervals, mean over the devices."""
        return statistics.fmean(total(d.busy())
                                for d in self.devices.values())

    def per_solve(self, merged) -> float:
        """Seconds of ``merged`` for one solve: every event in the
        trace belongs to one of the solves, and they are alike."""
        return total(merged) / len(self.solves)


def reduce(raw: dict) -> Reduced:
    devices, solves = {}, []
    for plane in raw["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        for line in plane["lines"]:
            for name, start, dur, stats in line["events"]:
                s, e = start * 1e-9, (start + dur) * 1e-9
                if m:
                    dev = devices.setdefault(int(m.group(1)),
                                             DeviceTrace())
                    if line["name"] == OPS_LINE:
                        dev.ops.append((name, s, e, stats))
                    elif line["name"] == MODULES_LINE:
                        dev.modules.append((name, s, e))
                elif name == ANNOTATION:
                    solves.append((s, e))
    if not devices or not solves:
        raise ValueError(
            f"trace holds {len(devices)} device plane(s) and "
            f"{len(solves)} {ANNOTATION!r} span(s); nothing to reduce")
    solves.sort()
    return Reduced(devices=dict(sorted(devices.items())), solves=solves)


def breakdown(red: Reduced, top_ops: int = 10, top_gaps: int = 5) -> dict:
    """The device-0 ops with most self time, as ``program/instruction
    (opcode)`` summed over the traced solves, and the longest idle gaps
    on device 0 by where the host was (good to a millisecond: two
    clocks)."""
    dev0 = red.first
    modules = sorted(dev0.modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]

    def program_at(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        return modules[i][0] if i >= 0 and t <= modules[i][2] else "?"

    opcode = {}
    events = []
    for name, s, e, stats in dev0.ops:
        label = f"{program_at(s)}/{name}"
        opcode[label] = stats.get("opcode", "?")
        events.append((label, s, e))
    own = self_times(events)
    ops = sorted(own.items(), key=lambda kv: -kv[1])[:top_ops]
    busy = dev0.busy()
    lo = min(red.solves[0][0], busy[0][0])
    hi = max(red.solves[-1][1], busy[-1][1])
    gaps = subtract([(lo, hi)], busy)
    labelled = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top_gaps]:
        mid = 0.5 * (s + e)
        inside = any(a <= mid <= b for a, b in red.solves)
        labelled.append(["inside bench.solve" if inside
                         else "between solves", e - s])
    return {"device_ops": [[f"{n} ({opcode[n]})", t] for n, t in ops],
            "idle_gaps": labelled}
