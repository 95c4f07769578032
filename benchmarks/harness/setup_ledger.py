"""Set-up on the program's own clock: ``slate_tpu.obs.compile_ledger()``
cut at the window's start.

The program keeps, with no profiler session, one record a trace,
lowering and backend compile (``compile.trace`` / ``.lower`` /
``.backend``: ``start_ns``/``end_ns`` by ``time.perf_counter_ns``, the
``program``, the persistent cache's answer, and as ``parent`` / ``solve``
the span that was open on the compiling thread), one ``slate.import``,
and the root spans of the cold path (a process's first sixteen, and any
later one in which something compiled) with ``compiled``. The window
starts where the mix's last warm-up call ends: the end of the
``warm_up_calls``-th kept root named ``slate.<call>`` (the traffic's
``call``, else its ``routine``). That needs no clock of ``run.py``'s:
everything before that instant is set-up, everything after it the traced
solves, the window and the check.

A program without ``compile_ledger`` (a parent commit from before it)
gives ``None`` everywhere, and the metrics that read this are left out
of the line.
"""

from __future__ import annotations

from dataclasses import dataclass

from benchmarks.harness.trace_reduce import merge, subtract, total

COMPILE = "compile."
GENERATORS = ("slate.random_matrix", "slate.random_spd")
KINDS = {"compile.trace": "trace", "compile.lower": "lower",
         "compile.backend": "backend_compile"}


def seconds(record: dict) -> float:
    return (record["end_ns"] - record["start_ns"]) * 1e-9


def interval(record: dict) -> tuple:
    return (record["start_ns"] * 1e-9, record["end_ns"] * 1e-9)


@dataclass
class Setup:
    """The ledger with its records and roots split at ``cut_ns``, the
    end of the last warm-up call. ``calls`` are the warm-up calls'
    roots, ``roots`` every kept root that ended by the cut."""
    ledger: dict
    cut_ns: int
    calls: list
    roots: list
    before: list
    after: list

    def compiles(self, name: str) -> list:
        return [r for r in self.before if r["name"] == name]

    def generators(self) -> list:
        """The generators' roots before the first solver root."""
        first = self.calls[0]["start_ns"]
        return [r for r in self.roots if r["name"] in GENERATORS
                and r["end_ns"] <= first]


def read(run: dict):
    """The program's ledger: ``run["compile_ledger"]`` where a test put
    it, else ``slate_tpu.obs.compile_ledger()``; None when the program
    has no such function."""
    if "compile_ledger" in run:
        return run["compile_ledger"]
    from slate_tpu import obs
    ledger = getattr(obs, "compile_ledger", None)
    return ledger() if ledger else None


def cut(run: dict):
    """``Setup`` of ``run``, or None without a ledger. A ledger that
    does not hold the mix's warm-up calls is an error: the cut would be
    a guess."""
    ledger = read(run)
    if ledger is None:
        return None
    traffic = run["spec"]["traffic"]
    name = "slate." + traffic.get("call", traffic["routine"])
    warm_up_calls = max(1, traffic.get("warm_up_calls", 1))
    calls = sorted((r for r in ledger["roots"] if r["name"] == name),
                   key=lambda r: r["start_ns"])[:warm_up_calls]
    if len(calls) < warm_up_calls:
        raise ValueError(
            f"{len(calls)} kept root(s) named {name} for "
            f"{warm_up_calls} warm-up call(s): roots "
            f"{[r['name'] for r in ledger['roots']]}")
    cut_ns = calls[-1]["end_ns"]
    return Setup(
        ledger=ledger, cut_ns=cut_ns, calls=calls,
        roots=[r for r in ledger["roots"] if r["end_ns"] <= cut_ns],
        before=[r for r in ledger["records"] if r["end_ns"] <= cut_ns],
        after=[r for r in ledger["records"] if r["end_ns"] > cut_ns])


def compile_seconds_before(run: dict, name: str, **labels):
    """Seconds of the ``name`` records that ended before the window and
    carry ``labels``, or None."""
    setup = cut(run)
    if setup is None:
        return None
    return sum(seconds(r) for r in setup.compiles(name)
               if all(r["labels"].get(k) == v for k, v in labels.items()))


def by_phase(run: dict):
    """``setup_s`` split into disjoint phases that sum to it, in the
    order they are charged: the package's import, the generators, each
    warm-up call, any other root, compile records outside every root,
    and what is left (the TPU runtime's start, the benchmark's own
    code, the device finishing the operands). An instant under two
    headings counts for the first."""
    setup = cut(run)
    if setup is None:
        return None
    named = [("import", [r for r in setup.before
                         if r["name"] == "slate.import"]),
             ("operands", setup.generators())]
    named += [(f"warm_up_{i + 1}", [call])
              for i, call in enumerate(setup.calls)]
    mine = {r["id"] for _, records in named for r in records}
    named += [("other_roots", [r for r in setup.roots
                               if r["id"] not in mine]),
              ("compile_outside_roots",
               [r for r in setup.before if r["name"].startswith(COMPILE)
                and r["solve"] == 0])]
    phases, covered = {}, []
    for phase, records in named:
        own = subtract(merge(interval(r) for r in records), covered)
        phases[phase] = total(own)
        covered = merge(covered + own)
    phases["unattributed"] = run["setup_s"] - sum(phases.values())
    return phases


def unattributed_where(run: dict):
    """The unattributed part of ``setup_s`` by where it lies between the
    program's own extents: from the import's end to the first
    generator, from the last generator to the first warm-up call (the
    benchmark waits there for the device to finish the operands),
    between warm-up calls; compile records outside every root are taken
    out of each. What is left lies before the import (the interpreter,
    jax's import, the TPU runtime's start) or, a little, after the last
    warm-up call: the program's clock cannot part those two."""
    phases = by_phase(run)
    if phases is None:
        return None
    setup = cut(run)
    outside = merge(interval(r) for r in setup.before
                    if r["name"].startswith(COMPILE) and r["solve"] == 0)
    stops = [("import", r) for r in setup.before
             if r["name"] == "slate.import"][:1]
    stops += [("operands", r) for r in setup.generators()]
    stops += [(f"warm_up_{i + 1}", call)
              for i, call in enumerate(setup.calls)]
    where = {}
    for (a, left), (b, right) in zip(stops, stops[1:]):
        gap = subtract(merge([(left["end_ns"] * 1e-9,
                               right["start_ns"] * 1e-9)]), outside)
        key = f"{a}_to_{b}"
        where[key] = where.get(key, 0.0) + total(gap)
    where["before_import_or_after_warm_up"] = (
        phases["unattributed"] - sum(where.values()))
    return where


def by_program(setup: Setup, top: int = 12) -> list:
    """The ledger by program, whole process, largest first: seconds and
    counts by kind, the cache's answers, and which call paid (a kept
    root's name and its place among the kept roots; ``(outside)`` for
    records under no root, ``(root not kept)`` past the ledger's
    bound). Programs past ``top`` are summed as ``(others)``."""
    roots = setup.ledger["roots"]
    payer = {r["solve"]: f"{r['name']}[{i}]" for i, r in enumerate(roots)}
    paid: dict = {}
    for r in setup.ledger["records"]:
        if not r["name"].startswith(COMPILE):
            continue
        who = "(outside)" if r["solve"] == 0 else payer.get(
            r["solve"], "(root not kept)")
        calls = paid.setdefault(r["labels"]["program"], {})
        calls[who] = calls.get(who, 0.0) + seconds(r)
    rows = []
    for program, totals in setup.ledger["by_program"].items():
        row = {"program": program,
               "seconds": {k: v[0] for k, v in totals.items()
                           if k != "cache"},
               "counts": {k: v[1] for k, v in totals.items()
                          if k != "cache"},
               "cache": totals.get("cache", {}),
               "paid_by": paid.get(program, {})}
        row["total_s"] = sum(s for k, s in row["seconds"].items()
                             if k != "cache_retrieval")
        rows.append(row)
    rows.sort(key=lambda row: -row["total_s"])
    rest = rows[top:]
    out = rows[:top]
    if rest:
        out.append({"program": "(others)", "programs": len(rest),
                    "total_s": sum(row["total_s"] for row in rest)})
    return out
