"""The slaterace analysis engine: vector-clock happens-before with
FastTrack-style epochs per registered cell, lockset diagnostics, a
global lock-order graph, and lost-wakeup detection.

The engine is the sink ``slate_tpu.runtime.sync.arm`` installs: it
consumes :class:`SyncEvent` tuples online, under one internal lock
(raw ``threading`` is fine here — SL012 scopes to ``slate_tpu/``),
and accumulates :class:`RaceFinding` records with the exact
``file:line`` sites the events carried.

Event model (one vector clock per thread, ``tid → clock``):

* ``acquired``/``release`` — release stores the thread's clock into
  the lock and bumps the thread; acquire joins the lock's clock into
  the thread.  Same-lock critical sections are therefore totally
  ordered, which is exactly the happens-before a correct locking
  discipline induces.  Reentrant re-acquires (RLock depth > 1) are
  collapsed.  First acquires also extend the lock-order graph with an
  edge from every lock currently held; cycles in that graph at report
  time are acquisition-order inversions (potential deadlocks), even
  if the run never actually deadlocked.
* ``fork``/``thread_begin``/``thread_end``/``join`` — ``sync.Thread``
  lineage: the child starts from the parent's clock, the parent joins
  the child's final clock at ``join``.
* ``region_begin``/``region_end`` — native-pool bracketing
  (``dag.run_host``): threads first seen while a region is open seed
  from the region's entry clock and are joined back at exit.  A
  reused pool thread re-seeds lazily when it next speaks inside a
  newer region.
* ``event_set``/``event_wait``, ``notify``/``wait_end(ok)`` —
  signal edges.  A ``wait_end`` with ``ok=False`` on a condition that
  was *never* notified is reported as a lost wakeup.
* ``cell_read``/``cell_write`` — FastTrack: a cell keeps its last
  write epoch (tid@clock + site + lockset) and a read map; an access
  pair with at least one write that is not happens-before ordered is
  a data race, reported with both sites and the (dis)joint locksets.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field


def _join(dst: dict, src: dict) -> None:
    for t, c in src.items():
        if c > dst.get(t, 0):
            dst[t] = c


@dataclass(frozen=True)
class RaceFinding:
    kind: str                 # "data-race" | "lock-order" | "lost-wakeup"
    name: str                 # cell / lock-cycle / condition name
    message: str
    sites: tuple[str, ...]    # "path:line", most recent access last
    threads: tuple[int, ...] = ()

    def format(self) -> str:
        where = " <-> ".join(self.sites)
        return f"[{self.kind}] {self.name}: {self.message} @ {where}"

    def to_dict(self) -> dict:
        return {"kind": self.kind, "name": self.name,
                "message": self.message, "sites": list(self.sites),
                "threads": list(self.threads)}


@dataclass
class _Access:
    tid: int
    clock: int
    site: str
    lockset: frozenset


@dataclass
class _Cell:
    name: str
    write: _Access | None = None
    reads: dict = field(default_factory=dict)   # tid -> _Access


@dataclass
class _LockState:
    name: str
    vc: dict = field(default_factory=dict)
    site: str = ""        # most recent acquire site (for graph edges)


class Engine:
    """Online happens-before checker; install with ``sync.arm(engine)``
    and read :meth:`report` after the workload."""

    def __init__(self):
        self._mu = threading.Lock()
        self._vc: dict[int, dict] = {}          # tid -> vector clock
        self._held: dict[int, dict] = {}        # tid -> {lock_id: depth}
        self._locks: dict[int, _LockState] = {}
        self._cells: dict[int, _Cell] = {}
        self._conds: dict[int, dict] = {}       # cond id -> state
        self._events: dict[int, dict] = {}      # event id -> {vc, name}
        self._forks: dict[int, dict] = {}       # token -> parent vc copy
        self._ends: dict[int, dict] = {}        # token -> child final vc
        self._edges: dict[tuple, tuple] = {}    # (a,b) -> (names, sites)
        self._region: tuple[int, dict] | None = None   # (epoch, vc)
        self._region_no = 0
        self._pool_tids: dict[int, int] = {}    # tid -> last region epoch
        self._alias: dict[int, int] = {}        # OS ident -> logical tid
        self._next_tid = 0
        self._findings: list[RaceFinding] = []
        self._seen_races: set = set()

    # -- sink protocol ----------------------------------------------------

    def __call__(self, ev) -> None:
        with self._mu:
            self._handle(ev)

    # -- helpers ----------------------------------------------------------

    def _thread(self, tid: int) -> dict:
        vc = self._vc.get(tid)
        if vc is None:
            vc = {tid: 1}
            if self._region is not None:
                epoch, rvc = self._region
                _join(vc, rvc)
                self._pool_tids[tid] = epoch
            self._vc[tid] = vc
            self._held[tid] = {}
        elif self._region is not None and tid in self._pool_tids:
            epoch, rvc = self._region
            if self._pool_tids[tid] < epoch:
                _join(vc, rvc)
                self._pool_tids[tid] = epoch
        return vc

    def _lockset(self, tid: int) -> frozenset:
        return frozenset(self._held.get(tid, ()))

    @staticmethod
    def _fmt(ev) -> str:
        return f"{ev.path}:{ev.line}"

    def _hb(self, acc: _Access, vc: dict) -> bool:
        return acc.clock <= vc.get(acc.tid, 0)

    # -- dispatch ---------------------------------------------------------

    def _handle(self, ev) -> None:
        # the OS reuses a thread ident once its thread has exited: a
        # sync.Thread gets a fresh logical id at thread_begin (negative,
        # so it meets no ident), or two threads that never overlapped
        # in time would be ONE thread to the checker and their race
        # invisible
        raw = ev.tid
        if ev.kind == "thread_begin":
            self._next_tid -= 1
            self._alias[raw] = self._next_tid
        if raw in self._alias:
            ev = ev._replace(tid=self._alias[raw])
        fn = getattr(self, "_on_" + ev.kind, None)
        if fn is not None:
            fn(ev)
        if ev.kind == "thread_end":
            self._alias.pop(raw, None)

    # locks

    def _on_acquired(self, ev) -> None:
        vc = self._thread(ev.tid)
        held = self._held[ev.tid]
        if ev.obj in held:          # reentrant re-acquire
            held[ev.obj] += 1
            return
        st = self._locks.setdefault(ev.obj, _LockState(ev.name))
        st.name = ev.name
        site = self._fmt(ev)
        for other in held:
            o = self._locks.get(other)
            key = (other, ev.obj)
            if key not in self._edges:
                self._edges[key] = (
                    (o.name if o else "?", ev.name),
                    (o.site if o else "?", site), ev.tid)
        st.site = site
        held[ev.obj] = 1
        _join(vc, st.vc)

    def _on_release(self, ev) -> None:
        vc = self._thread(ev.tid)
        held = self._held[ev.tid]
        depth = held.get(ev.obj, 0)
        if depth > 1:
            held[ev.obj] = depth - 1
            return
        held.pop(ev.obj, None)
        st = self._locks.setdefault(ev.obj, _LockState(ev.name))
        st.vc = dict(vc)
        vc[ev.tid] = vc.get(ev.tid, 0) + 1

    # condition variables (wait = release + reacquire + signal edge)

    def _cond(self, ev) -> dict:
        return self._conds.setdefault(
            ev.obj, {"name": ev.name, "notify_vc": {}, "notifies": 0})

    def _on_wait_begin(self, ev) -> None:
        self._on_release(ev._replace(obj=ev.extra["lock"]))

    def _on_wait_end(self, ev) -> None:
        lock_ev = ev._replace(obj=ev.extra["lock"])
        self._on_acquired(lock_ev)
        cs = self._cond(ev)
        vc = self._thread(ev.tid)
        if ev.extra.get("ok"):
            _join(vc, cs["notify_vc"])
        elif cs["notifies"] == 0:
            self._findings.append(RaceFinding(
                kind="lost-wakeup", name=ev.name,
                message=("wait timed out and the condition was never "
                         "notified — no thread signals this sleeper"),
                sites=(self._fmt(ev),), threads=(ev.tid,)))

    def _on_notify(self, ev) -> None:
        cs = self._cond(ev)
        vc = self._thread(ev.tid)
        cs["notifies"] += 1
        _join(cs["notify_vc"], vc)
        vc[ev.tid] = vc.get(ev.tid, 0) + 1

    # events

    def _on_event_set(self, ev) -> None:
        vc = self._thread(ev.tid)
        es = self._events.setdefault(ev.obj, {"vc": {}, "name": ev.name})
        _join(es["vc"], vc)
        vc[ev.tid] = vc.get(ev.tid, 0) + 1

    def _on_event_wait(self, ev) -> None:
        vc = self._thread(ev.tid)
        if ev.extra.get("ok"):
            es = self._events.get(ev.obj)
            if es is not None:
                _join(vc, es["vc"])

    # thread lineage

    def _on_fork(self, ev) -> None:
        vc = self._thread(ev.tid)
        self._forks[ev.obj] = dict(vc)
        vc[ev.tid] = vc.get(ev.tid, 0) + 1

    def _on_thread_begin(self, ev) -> None:
        vc = {ev.tid: 1}
        parent = self._forks.get(ev.obj)
        if parent:
            _join(vc, parent)
        self._vc[ev.tid] = vc
        self._held.setdefault(ev.tid, {})

    def _on_thread_end(self, ev) -> None:
        self._ends[ev.obj] = dict(self._thread(ev.tid))

    def _on_join(self, ev) -> None:
        vc = self._thread(ev.tid)
        final = self._ends.get(ev.obj)
        if final:
            _join(vc, final)

    # native-pool regions

    def _on_region_begin(self, ev) -> None:
        vc = self._thread(ev.tid)
        self._region_no += 1
        self._region = (self._region_no, dict(vc))
        vc[ev.tid] = vc.get(ev.tid, 0) + 1

    def _on_region_end(self, ev) -> None:
        vc = self._thread(ev.tid)
        for tid in self._pool_tids:
            other = self._vc.get(tid)
            if other and tid != ev.tid:
                _join(vc, other)
        self._region = None

    # registered cells — FastTrack epochs

    def _race(self, cell: _Cell, prev: _Access, ev, writer_now: bool) -> None:
        site = self._fmt(ev)
        key = (id(cell), prev.site, site, writer_now)
        if key in self._seen_races:
            return
        self._seen_races.add(key)
        now_ls = self._lockset(ev.tid)
        common = prev.lockset & now_ls
        how = ("no lock is held in common"
               if not common else
               "locksets overlap but no happens-before edge orders them")
        a = "write" if prev is cell.write else "read"
        b = "write" if writer_now else "read"
        self._findings.append(RaceFinding(
            kind="data-race", name=cell.name,
            message=(f"{a}-{b} race on shared cell '{cell.name}': the "
                     f"accesses are concurrent and {how}"),
            sites=(prev.site, site), threads=(prev.tid, ev.tid)))

    def _on_cell_read(self, ev) -> None:
        vc = self._thread(ev.tid)
        cell = self._cells.setdefault(ev.obj, _Cell(ev.name))
        cell.name = ev.name
        w = cell.write
        if w is not None and w.tid != ev.tid and not self._hb(w, vc):
            self._race(cell, w, ev, writer_now=False)
        cell.reads[ev.tid] = _Access(ev.tid, vc.get(ev.tid, 0),
                                     self._fmt(ev), self._lockset(ev.tid))

    def _on_cell_write(self, ev) -> None:
        vc = self._thread(ev.tid)
        cell = self._cells.setdefault(ev.obj, _Cell(ev.name))
        cell.name = ev.name
        w = cell.write
        if w is not None and w.tid != ev.tid and not self._hb(w, vc):
            self._race(cell, w, ev, writer_now=True)
        for tid, acc in list(cell.reads.items()):
            if tid != ev.tid and not self._hb(acc, vc):
                self._race(cell, acc, ev, writer_now=True)
        cell.write = _Access(ev.tid, vc.get(ev.tid, 0), self._fmt(ev),
                             self._lockset(ev.tid))
        cell.reads.clear()

    # -- reporting --------------------------------------------------------

    def _lock_cycles(self) -> list[RaceFinding]:
        graph: dict[int, list[int]] = {}
        for (a, b) in self._edges:
            graph.setdefault(a, []).append(b)
            graph.setdefault(b, [])
        findings, reported = [], set()
        state: dict[int, int] = {}    # 0 unseen / 1 on stack / 2 done
        stack: list[int] = []

        def visit(n: int) -> None:
            state[n] = 1
            stack.append(n)
            for m in graph[n]:
                if state.get(m, 0) == 0:
                    visit(m)
                elif state.get(m) == 1:
                    cyc = tuple(stack[stack.index(m):])
                    key = frozenset(cyc)
                    if key in reported:
                        continue
                    reported.add(key)
                    names, sites, tids = [], [], []
                    ring = cyc + (cyc[0],)
                    for x, y in zip(ring, ring[1:]):
                        edge = self._edges.get((x, y))
                        if edge:
                            (na, nb), (sa, sb), tid = edge
                            names.append(f"{na}->{nb}")
                            sites.append(sb)
                            tids.append(tid)
                    findings.append(RaceFinding(
                        kind="lock-order",
                        name=" / ".join(names) or "lock cycle",
                        message=("acquisition-order inversion: these "
                                 "locks are taken in conflicting orders "
                                 "by different threads (potential "
                                 "deadlock)"),
                        sites=tuple(sites), threads=tuple(dict.fromkeys(tids))))
            stack.pop()
            state[n] = 2

        for n in graph:
            if state.get(n, 0) == 0:
                visit(n)
        return findings

    def report(self) -> list[RaceFinding]:
        """All findings: online data races + lost wakeups, plus the
        lock-order cycles computed over the whole run."""
        with self._mu:
            return list(self._findings) + self._lock_cycles()
