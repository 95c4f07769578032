"""Concurrency workloads the slaterace sweep drives.

Each workload is a small, deterministic exercise of one production
concurrency surface — the hosttask tile locks + native DAG pool, the
ckpt background saver, the serve scheduler's admission path, the
slateflow continuous-batching service (dispatch thread + WFQ state),
and the obs flight/metrics/correlation registries.  They are sized for CPU
(seconds, not minutes) but hit every sync primitive the real paths
use, so an armed run over them is a clean-tree certificate: zero
findings here means the happens-before engine saw every lock, fork,
join, wait, and registered cell access race-free under the chosen
schedule perturbation.

``SUITES`` maps suite name → callable; the CLI (``__main__``) runs
them under ``tools.slaterace.detector``.
"""

from __future__ import annotations

import os
import tempfile

import numpy as np


def _mk_grid():
    import slate_tpu as st
    import jax
    return st.Grid(1, 1, devices=jax.devices("cpu")[:1])


def wl_hosttask() -> None:
    """Tile-lock hosttask paths + the superstep DAG on the native
    pool (pool_region bracketing, st dict under its cell)."""
    import slate_tpu as st
    from slate_tpu.runtime.hosttask import (potrf_hosttask,
                                            potrf_superstep_dag,
                                            trsm_hosttask)
    from slate_tpu.types import Uplo
    grid = _mk_grid()
    rng = np.random.default_rng(7)
    n, nb = 64, 16
    g = rng.standard_normal((n, n))
    a = g @ g.T / n + 3 * np.eye(n)
    A = st.HermitianMatrix.from_dense(np.tril(a), nb=nb, grid=grid,
                                      uplo=Uplo.Lower)
    L, info = potrf_hosttask(A, lookahead=2, threads=4)
    assert int(info) == 0
    b = rng.standard_normal((n, 8))
    B = st.Matrix.from_dense(b, nb=nb, grid=grid)
    trsm_hosttask(L, B, lookahead=2, threads=4)
    A2 = st.HermitianMatrix.from_dense(np.tril(a), nb=nb, grid=grid,
                                       uplo=Uplo.Lower)
    _, info2 = potrf_superstep_dag(A2, threads=3)
    assert int(info2) == 0


def wl_ckpt() -> None:
    """Background saver: concurrent save_async from two sync.Threads
    into the SerialExecutor, then drain (the _PENDING cell)."""
    import slate_tpu as st
    from slate_tpu.robust import ckpt
    from slate_tpu.runtime import sync
    grid = _mk_grid()
    rng = np.random.default_rng(11)
    a = rng.standard_normal((64, 64))
    A = st.Matrix.from_dense(a, nb=16, grid=grid)
    with tempfile.TemporaryDirectory() as td:
        ckpt.set_ckpt_dir(os.path.join(td, "ckpt"))
        try:
            plans = [ckpt.plan("getrf", A) for _ in range(2)]

            def saver(p, base):
                for i in range(3):
                    p.save_async(base + i, data=np.full((4, 4), i * 1.0))

            ts = [sync.Thread(target=saver, args=(p, 10 * i),
                              name=f"race-ckpt-{i}")
                  for i, p in enumerate(plans)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            ckpt.drain()
        finally:
            ckpt.drain()
            ckpt.set_ckpt_dir(None)
            ckpt.reset_ckpt_dir()


def wl_serve() -> None:
    """Scheduler admission under concurrent submitters (the queue-map
    cell + depth check-then-act), then a deterministic drain."""
    from slate_tpu.runtime import sync
    from slate_tpu.serve import Scheduler, ShedError, SolveRequest
    rng = np.random.default_rng(13)

    def spd(n, seed):
        g = np.random.default_rng(seed).standard_normal((n, n))
        return g @ g.T / n + np.eye(n)

    s = Scheduler(table=(64,), nb=32, max_depth=8)

    def submitter(tid):
        for i in range(4):
            n = 8 + 2 * ((tid + i) % 3)
            try:
                s.submit(SolveRequest(a=spd(n, seed=tid * 10 + i),
                                      b=np.ones(n),
                                      tag=f"t{tid}.{i}"))
            except ShedError:
                pass

    ts = [sync.Thread(target=submitter, args=(i,),
                      name=f"race-serve-{i}") for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert s.depth() <= 16
    res = s.drain()
    assert all(r.shed or r.health is not None for r in res)
    del rng


def wl_flow() -> None:
    """slateflow continuous-batching service under concurrent
    submitters: WFQ admission (flow map + SCFQ clock under the state
    cell), the dispatch thread's condition hand-off, streaming
    delivery, and the condition-driven quiesce/stop lifecycle."""
    from slate_tpu.runtime import sync
    from slate_tpu.serve import ShedError, SolveRequest
    from slate_tpu.serve.flow import FlowScheduler

    def spd(n, seed):
        g = np.random.default_rng(seed).standard_normal((n, n))
        return g @ g.T / n + np.eye(n)

    s = FlowScheduler(table=(64,), nb=32, max_depth=8, slo_s=None)
    done = []
    done_mu = sync.Lock(name="race.flow.done")

    def on_done(res):
        with done_mu:
            done.append(res.rid)

    unsub = s.on_complete(on_done)
    try:
        def submitter(tid):
            for i in range(4):
                n = 8 + 2 * ((tid + i) % 3)
                try:
                    s.submit(SolveRequest(
                        a=spd(n, seed=tid * 10 + i), b=np.ones(n),
                        tag=f"f{tid}.{i}",
                        tenant=("acme" if tid % 2 else "globex")))
                except ShedError:
                    pass

        ts = [sync.Thread(target=submitter, args=(i,),
                          name=f"race-flow-{i}") for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert s.quiesce(120.0)
        with done_mu:
            resolved = len(done)
        assert resolved <= 16
    finally:
        unsub()
        s.stop()


def wl_flight() -> None:
    """obs registries under concurrent writers: metrics counters/
    histograms, flight ring + auto-dump gate, correlation inflight."""
    from slate_tpu.obs import correlation, flight, metrics
    from slate_tpu.runtime import sync
    was_metrics, was_flight = metrics.enabled(), flight.enabled()
    metrics.enable()
    flight.enable()
    try:
        def hammer(tid):
            for i in range(50):
                metrics.inc("race.test", routine="wl", t=str(tid))
                metrics.observe("race.hist", float(i), routine="wl")
                metrics.set_gauge("race.gauge", float(i), t=str(tid))
                flight.record("note", f"n{tid}", ts_s=float(i))
                rid = correlation.new_id("race")
                correlation.mark_inflight(rid)
                with correlation.bind(rid):
                    metrics.counter_value("race.test", routine="wl",
                                          t=str(tid))
                correlation.mark_done(rid)

        ts = [sync.Thread(target=hammer, args=(i,),
                          name=f"race-obs-{i}") for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert metrics.counter_total("race.test") == 200
    finally:
        # leave both as they were found: the flight ring is on by
        # default, and a process that runs this suite goes on using it
        metrics.reset()
        flight.reset()
        if not was_metrics:
            metrics.disable()
        if not was_flight:
            flight.disable()


SUITES = {
    "hosttask": wl_hosttask,
    "ckpt": wl_ckpt,
    "serve": wl_serve,
    "flow": wl_flow,
    "flight": wl_flight,
}
