"""The span record of PR 25: every span knows its parent and its solve,
capture follows ``jax.profiler`` (no switch), the host's spans land on
the profiler's host plane, and every trace, lowering and backend compile
is a span record under the call that paid for it, kept with no session
(``obs.compile_ledger()``: PR 39).

CPU only: what a chip run adds is in PERF.md section 6.
"""

import glob
import os
import time

import jax
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import obs
from slate_tpu.obs import correlation, flight, metrics, tracing
from tests.conftest import rand, spd


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Everything empty; the flight ring on (the default: switched on
    here in case an earlier test of this worker left it off), tracing
    and metrics as the session had them."""
    was_metrics = obs.metrics_enabled()
    was_flight = flight.enabled()
    flight.enable()
    obs.reset()
    yield
    if not was_metrics:
        obs.metrics_off()
    if not was_flight:
        flight.disable()
    obs.reset()


@pytest.fixture
def profiler(tmp_path):
    """A real ``jax.profiler`` session; ``stop()`` ends it and gives
    the path of the ``.xplane.pb``."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    state = {"on": True}

    def stop():
        if state["on"]:
            state["on"] = False
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(
            str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
        return path

    yield stop
    if state["on"]:
        jax.profiler.stop_trace()


def _posv_operands(grid, n=256, nb=64):
    A = st.HermitianMatrix.from_dense(spd(n, np.float32, seed=3), nb=nb,
                                      grid=grid, uplo=st.Uplo.Lower)
    B = st.Matrix.from_dense(rand(n, 4, np.float32, seed=4), nb=nb,
                             grid=grid)
    return A, B


def _by_op(counter):
    """A ``trsm`` counter by its ``op`` label."""
    return {dict(labels)["op"]: v for labels, v
            in metrics.counters_named(counter).items()}


def _moved_x():
    return _by_op("trsm.move_x")


def _read_tiles():
    return _by_op("trsm.read_tiles")


def _tree(spans):
    by_id = {s["id"]: s for s in spans}

    def path(s):
        names = [s["name"]]
        while s["parent"]:
            s = by_id[s["parent"]]
            names.append(s["name"])
        return "/".join(reversed(names))

    return [path(s) for s in sorted(spans, key=lambda s: s["start_ns"])]


# ------------------------------------------------------------ the tree

@pytest.mark.parametrize("grid_name", ["grid11", "grid22"])
def test_posv_span_tree_has_parents_and_one_solve_id(grid_name, request,
                                                     profiler):
    grid = request.getfixturevalue(grid_name)
    A, B = _posv_operands(grid)
    jax.block_until_ready(st.posv(A, B))         # compile outside
    obs.reset()
    obs.metrics_on()
    for _ in range(2):
        jax.block_until_ready(st.posv(A, B))
    profiler()
    spans = obs.captured_spans()
    roots = [s for s in spans if s["parent"] == 0]
    assert [r["name"] for r in roots] == ["slate.posv", "slate.posv"]
    assert roots[0]["solve"] != roots[1]["solve"]
    assert roots[0]["labels"]["grid"] == f"{grid.p}x{grid.q}"
    assert roots[0]["labels"]["routine"] == "posv"
    for root in roots:
        mine = [s for s in spans if s["solve"] == root["solve"]]
        paths = _tree(mine)
        assert paths[0] == "slate.posv"
        assert "slate.posv/potrf" in paths
        assert "slate.posv/potrf/potrf.chunk" in paths
        assert paths.count("slate.posv/potrs/trsm") == 2
        assert paths.count("slate.posv/potrs/trsm/trsm.launch") == 2
        # the second solve reads conj_transpose(L) where it lies:
        # nothing under trsm re-lays storage
        assert not [x for x in paths if "matrix.materialize" in x]
        trsms = [s for s in mine if s["name"] == "trsm"]
        assert [s["labels"]["op"] for s in trsms] == ["N", "C"]
        # B is one tile column: on a grid A stays and X moves over q
        assert [s["labels"]["form"] for s in trsms] == [
            "move_a" if grid.q == 1 else "move_x"] * 2
        # ... and on one device column nothing crosses q, so a step
        # reads column k tile by tile, past the diagonal tile only
        assert [s["labels"]["read"] for s in trsms] == [
            "tiles" if grid.q == 1 else "column"] * 2
        # children lie inside their parents, on one clock
        by_id = {s["id"]: s for s in mine}
        for s in mine:
            if s["parent"]:
                up = by_id[s["parent"]]
                assert up["start_ns"] <= s["start_ns"]
                assert s["end_ns"] <= up["end_ns"]
    chunks = [s for s in spans if s["name"] == "potrf.chunk"
              and s["solve"] == roots[0]["solve"]]
    assert len(chunks) == (1 if grid.size == 1 else 2)
    assert metrics.counter_total("trsm.in_place") == len(roots)
    assert metrics.counter_total("matrix.relayout_bytes") == 0
    once_each = {"N": len(roots), "C": len(roots)}
    assert _moved_x() == ({} if grid.q == 1 else once_each)
    assert _read_tiles() == (once_each if grid.q == 1 else {})


@pytest.mark.parametrize("grid_name,nrhs,w,narrow,form,read", [
    ("grid11", 8, 128, 2, "move_a", "tiles"),
    ("grid11", 256, 256, 0, "move_a", "tiles"),
    ("grid11", 512, 512, 0, "move_a", "tiles"),
    ("grid22", 8, 128, 2, "move_x", "column"),
    ("grid22", 256, 256, 0, "move_x", "column"),
    # two tile columns: X spread over q
    ("grid22", 512, 256, 0, "move_a", "column"),
])
def test_trsm_span_says_the_width_it_carried(request, profiler, grid_name,
                                             nrhs, w, narrow, form, read):
    """8 right-hand sides in a 256-wide tile ride both solves of a posv
    at 128 columns; a B of whole tiles is carried as it is stored. On a
    grid a B of one tile column stays put while X moves (``form``,
    ``trsm.move_x`` once a solve and op); on one chip, or with a wider
    B, never. Where no operand crosses q (one device column) a step
    reads the tiles of column k past the diagonal where they are
    stored (``read``, ``trsm.read_tiles`` once a solve and op)."""
    grid = request.getfixturevalue(grid_name)
    n, nb = 512, 256
    A = st.HermitianMatrix.from_dense(spd(n, np.float32, seed=5), nb=nb,
                                      grid=grid, uplo=st.Uplo.Lower)
    B = st.Matrix.from_dense(rand(n, nrhs, np.float32, seed=6), nb=nb,
                             grid=grid)
    jax.block_until_ready(st.posv(A, B))         # compile outside
    obs.reset()
    obs.metrics_on()
    jax.block_until_ready(st.posv(A, B))
    profiler()
    trsms = [s["labels"] for s in obs.captured_spans()
             if s["name"] == "trsm"]
    assert [(t["op"], t["nrhs"], t["w"], t["form"], t["read"])
            for t in trsms] == [("N", nrhs, w, form, read),
                                ("C", nrhs, w, form, read)]
    assert metrics.counter_total("trsm.narrow") == narrow
    assert metrics.counter_total("trsm.in_place") == 1
    assert _moved_x() == ({"N": 1, "C": 1} if form == "move_x" else {})
    assert _read_tiles() == ({"N": 1, "C": 1} if read == "tiles" else {})


def test_capture_follows_the_profiler(grid11, profiler):
    A, B = _posv_operands(grid11)
    with obs.span("inside"):
        pass
    profiler()                                   # session over
    assert [s["name"] for s in obs.captured_spans()] == ["inside"]
    jax.block_until_ready(st.posv(A, B))
    with obs.span("after"):
        pass
    # profiler off: nothing more is kept, the flight ring goes on
    assert [s["name"] for s in obs.captured_spans()] == ["inside"]
    names = [e["name"] for e in flight.events()]
    assert "slate.posv" in names and "after" in names


def test_captured_inside_a_session_and_on_the_host_plane(grid11, profiler):
    A, B = _posv_operands(grid11)
    with jax.profiler.TraceAnnotation("bench.solve"):
        jax.block_until_ready(st.posv(A, B))
    assert obs.captured_spans()
    st.transpose(B).materialize()                # a re-layout of its own
    path = profiler()
    spans = obs.captured_spans()
    (root,) = [s for s in spans if s["name"] == "slate.posv"]
    found, bench = {}, None
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "bench.solve":
                    bench = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name.startswith(tracing.ANNOTATION_PREFIX):
                    found.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.duration_ns, dict(ev.stats)))
    assert bench is not None
    for name in ("slate.posv", "slate.potrf", "slate.potrs", "slate.trsm",
                 "slate.matrix.materialize"):
        assert name in found, sorted(found)
    (start, dur, stats), = found["slate.posv"]
    assert stats["solve"] == root["solve"] and stats["parent"] == 0
    assert stats["id"] == root["id"]
    assert bench[0] <= start and start + dur <= bench[1]
    # the annotation is the span: it opens just before and closes just
    # after (milliseconds of slack for a loaded test machine)
    assert 0 <= dur - (root["end_ns"] - root["start_ns"]) < 5e6
    for start, dur, stats in found["slate.trsm"]:
        assert stats["solve"] == root["solve"] and stats["parent"] > 0


def test_cap_drops_whole_solves(monkeypatch):
    monkeypatch.setattr(tracing, "_profiling", lambda: True)
    monkeypatch.setattr(tracing, "CAPTURE_CAP", 10)
    issued = []
    for _ in range(4):
        with obs.span("slate.fake") as root:
            issued.append(root.solve)
            for _ in range(3):
                with obs.span("child"):
                    pass
    spans = obs.captured_spans()
    # 4 spans a solve: two solves fit under the cap of 10, the third
    # pushes the oldest out whole
    assert sorted({s["solve"] for s in spans}) == issued[-2:]
    for solve in issued[-2:]:
        assert len([s for s in spans if s["solve"] == solve]) == 4
    obs.reset()
    assert obs.captured_spans() == []


def test_a_bound_request_id_is_the_solve_id(monkeypatch):
    monkeypatch.setattr(tracing, "_profiling", lambda: True)
    with correlation.bind("r-77"):
        with obs.span("slate.fake"):
            with obs.span("child"):
                obs.instant("compile", kind="lower")
    spans = obs.captured_spans()
    assert {s["solve"] for s in spans} == {"r-77"}
    (mark,) = [s for s in spans if s["name"] == "compile"]
    (child,) = [s for s in spans if s["name"] == "child"]
    assert mark["parent"] == child["id"]
    assert mark["start_ns"] == mark["end_ns"]


def test_threads_grow_their_own_trees(monkeypatch):
    from slate_tpu.runtime import sync
    monkeypatch.setattr(tracing, "_profiling", lambda: True)

    def work():
        with obs.span("slate.worker"):
            with obs.span("child"):
                pass

    with obs.span("slate.main"):
        t = sync.Thread(target=work)
        t.start()
        t.join()
    spans = obs.captured_spans()
    roots = {s["name"]: s for s in spans if s["parent"] == 0}
    assert set(roots) == {"slate.main", "slate.worker"}
    (child,) = [s for s in spans if s["name"] == "child"]
    assert child["parent"] == roots["slate.worker"]["id"]
    assert child["solve"] == roots["slate.worker"]["solve"]


# ---------------------------------------------------------- host syncs

def test_host_sync_once_for_gesv_fast_path_never_for_posv(grid11,
                                                          monkeypatch):
    monkeypatch.setenv("SLATE_LU_FAST", "1")
    monkeypatch.setattr(tracing, "_profiling", lambda: True)
    obs.metrics_on()
    n, nb = 384, 128
    A = st.Matrix.from_dense(rand(n, n, np.float32, seed=9), nb=nb,
                             grid=grid11)
    B = st.Matrix.from_dense(rand(n, 2, np.float32, seed=10), nb=nb,
                             grid=grid11)
    X, LU, piv, info = st.gesv(A, B)
    assert int(info) == 0
    assert metrics.counter_total("host.sync") == 1
    spans = obs.captured_spans()
    syncs = [s for s in spans if s["labels"].get("sync") == 1]
    assert [s["name"] for s in syncs] == ["gesv.order_to_ipiv"]
    paths = _tree(spans)
    assert "slate.gesv/getrf.chunk" in paths
    assert "slate.gesv/getrs/getrs.apply_pivots" in paths
    assert "slate.gesv/gesv.order_to_ipiv" in paths
    # getrs: unit-lower L then upper U, both NoTrans, tile by tile
    trsms = [s["labels"] for s in spans if s["name"] == "trsm"]
    assert [(t["op"], t["form"], t["read"]) for t in trsms] == [
        ("N", "move_a", "tiles")] * 2
    assert _read_tiles() == {"N": 2}
    x = np.asarray(X.to_dense())
    a, b = np.asarray(A.to_dense()), np.asarray(B.to_dense())
    assert np.linalg.norm(a @ x - b) < 1e-3 * np.linalg.norm(b)

    obs.reset()
    Ah, Bh = _posv_operands(grid11)
    jax.block_until_ready(st.posv(Ah, Bh))
    assert metrics.counter_total("host.sync") == 0
    assert not [s for s in obs.captured_spans()
                if s["labels"].get("sync") == 1]
    assert metrics.counter_total("matrix.relayout_bytes") == 0
    assert metrics.counter_total("trsm.in_place") == 1


def test_materialize_without_an_op_opens_no_span(grid11, monkeypatch):
    monkeypatch.setattr(tracing, "_profiling", lambda: True)
    A = st.Matrix.from_dense(rand(128, 128, np.float32), nb=64,
                             grid=grid11)
    assert A.materialize() is A
    assert obs.captured_spans() == []
    At = st.transpose(A).materialize()
    assert np.array_equal(np.asarray(At.to_dense()),
                          np.asarray(A.to_dense()).T)
    (mat,) = [s for s in obs.captured_spans() if s["parent"] == 0]
    assert mat["name"] == "matrix.materialize"
    assert mat["labels"]["bytes"] == 128 * 128 * 4
    leaves = [s["name"] for s in obs.captured_spans()
              if s["parent"] == mat["id"]]
    assert leaves == ["materialize.to_tiles", "materialize.transpose",
                      "materialize.device_put"]


def test_redistribute_span_holds_its_materialize(grid11, grid22,
                                                 monkeypatch):
    monkeypatch.setattr(tracing, "_profiling", lambda: True)
    A = st.Matrix.from_dense(rand(256, 256, np.float32), nb=64,
                             grid=grid22)
    R = st.transpose(A).redistribute(grid11)
    assert np.array_equal(np.asarray(R.to_dense()),
                          np.asarray(A.to_dense()).T)
    paths = _tree(obs.captured_spans())
    assert paths[0] == "matrix.redistribute"
    assert "matrix.redistribute/matrix.materialize" in paths


# ------------------------------------------------------ compile records

def _compile_records(program=None):
    return [r for r in obs.compile_ledger()["records"]
            if r["name"].startswith("compile.")
            and program in (None, r["labels"]["program"])]


def _seconds_by_kind(by_program):
    out = {}
    for totals in by_program.values():
        for kind, total in totals.items():
            if kind != "cache":
                seconds, count = out.get(kind, (0.0, 0))
                out[kind] = (seconds + total[0], count + total[1])
    return out


def test_compile_seconds_grow_on_a_first_call_only():
    import jax.numpy as jnp

    @jax.jit
    def probe(x):
        return jnp.tril(x @ x.T + 3.0).sum()

    before = obs.compile_seconds()
    probe(jnp.ones((33, 17))).block_until_ready()
    first = obs.compile_seconds()
    for kind in ("trace", "lower", "backend_compile"):
        assert first["counts"][kind] > before["counts"].get(kind, 0)
        assert first["seconds"][kind] > before["seconds"].get(kind, 0.0)
    assert any(name == "probe" for name, _ in first["top"])
    # one record a kind for the program, with a start and an end, and
    # nothing of it in the flight ring
    mine = _compile_records("probe")
    assert sorted(r["name"] for r in mine) == [
        "compile.backend", "compile.lower", "compile.trace"]
    assert all(r["end_ns"] > r["start_ns"] for r in mine)
    assert all(r["parent"] == 0 and r["solve"] == 0 for r in mine)
    assert not [e for e in flight.events()
                if e["name"].startswith("compile")]
    size = len(obs.compile_ledger()["records"])
    probe(jnp.ones((33, 17))).block_until_ready()
    assert obs.compile_seconds() == first        # nothing on a second
    assert len(obs.compile_ledger()["records"]) == size


def test_nested_traces_count_once():
    import jax.numpy as jnp

    @jax.jit
    def outer(x):
        for _ in range(20):
            x = jnp.tril(jnp.where(x > 0, x, -x)) + 1.0   # inner jits
        return x

    t0 = time.perf_counter()
    outer(jnp.ones((9, 9))).block_until_ready()
    wall = time.perf_counter() - t0
    seen = obs.compile_seconds()
    # the inner functions' traces are inside outer's: summed they
    # would pass the wall
    assert seen["seconds"]["trace"] <= wall
    assert seen["counts"]["trace"] < 20
    # ... and are a count on the outermost trace's record
    (trace,) = [r for r in _compile_records("outer")
                if r["name"] == "compile.trace"]
    assert trace["labels"]["inner_traces"] >= 20
    assert not [r for r in _compile_records()
                if r["name"] == "compile.trace"
                and r["start_ns"] > trace["start_ns"]
                and r["end_ns"] < trace["end_ns"]]


def test_a_compile_inside_a_captured_solve_is_a_span(monkeypatch):
    import jax.numpy as jnp
    monkeypatch.setattr(tracing, "_profiling", lambda: True)

    @jax.jit
    def probe(x):
        return x * 5.0 - 1.0

    x = jnp.ones((3, 5))
    with obs.span("slate.fake"):
        with obs.span("child"):
            probe(x).block_until_ready()
    spans = obs.captured_spans()
    (child,) = [s for s in spans if s["name"] == "child"]
    mine = [s for s in spans if s["name"].startswith("compile.")
            and s["labels"]["program"] == "probe"]
    assert {s["name"] for s in mine} == {"compile.trace", "compile.lower",
                                         "compile.backend"}
    for s in mine:       # a span with a duration, where the instant was
        assert s["end_ns"] > s["start_ns"]
        assert s["parent"] == child["id"] and s["solve"] == child["solve"]
    assert not [s for s in spans if s["name"] == "compile"]
    # the same records are the ledger's, with no session needed
    assert {s["id"] for s in mine} <= {r["id"] for r in _compile_records()}


@pytest.mark.parametrize("depth", [1, 3])
def test_a_compile_record_lies_inside_the_span_that_paid(depth):
    """No profiler session: the record's parent is the innermost open
    span, on the spans' clock (``time.time`` through the one anchor)."""
    import jax.numpy as jnp

    @jax.jit
    def probe(x):
        return jnp.cos(x).sum() * float(depth)

    opened = []
    with obs.span("slate.fake") as up:
        opened.append(up)
        for level in range(1, depth):
            up = obs.span(f"level{level}")
            up.__enter__()
            opened.append(up)
        time.sleep(0.002)
        t0 = time.perf_counter_ns()
        probe(jnp.ones((7, 3))).block_until_ready()
        t1 = time.perf_counter_ns()
        time.sleep(0.002)
        for up in reversed(opened[1:]):
            up.__exit__(None, None, None)
    mine = _compile_records("probe")
    assert len(mine) == 3
    for r in mine:
        assert r["parent"] == opened[-1].id
        assert r["solve"] == opened[0].solve
        assert t0 - 1e6 <= r["start_ns"] <= r["end_ns"] <= t1 + 1e6
    (root,) = obs.compile_ledger()["roots"]
    assert root["name"] == "slate.fake" and root["compiled"] is True
    assert root["start_ns"] <= mine[0]["start_ns"] + 1e6
    assert mine[-1]["end_ns"] <= root["end_ns"] + 1e6


def test_the_cold_path_keeps_its_roots():
    """The first sixteen roots whatever they did, then only a root in
    which something compiled; ten thousand warm calls add nothing."""
    import jax.numpy as jnp
    for i in range(tracing.COLD_ROOTS + 4):
        with obs.span("slate.fake", i=i):
            with obs.span("child"):
                pass
    roots = obs.compile_ledger()["roots"]
    assert [r["labels"]["i"] for r in roots] == list(
        range(tracing.COLD_ROOTS))
    assert not any(r["compiled"] for r in roots)
    assert all(r["parent"] == 0 and r["end_ns"] >= r["start_ns"]
               for r in roots)

    @jax.jit
    def probe(x):
        return jnp.sin(x) + 2.0

    with obs.span("slate.late", n=5) as late:
        with obs.span("child"):
            probe(jnp.ones((5,))).block_until_ready()
    with obs.span("slate.late", n=5):
        probe(jnp.ones((5,))).block_until_ready()    # warm: not kept
    ledger = obs.compile_ledger()
    assert len(ledger["roots"]) == tracing.COLD_ROOTS + 1
    kept = ledger["roots"][-1]
    assert kept["name"] == "slate.late" and kept["compiled"] is True
    assert kept["id"] == late.id and kept["solve"] == late.solve
    assert kept["labels"] == {"n": 5}
    assert {r["solve"] for r in _compile_records("probe")} == {late.solve}
    sizes = (len(ledger["roots"]), len(ledger["records"]))
    for _ in range(10_000):
        with obs.span("slate.warm"):
            pass
    ledger = obs.compile_ledger()
    assert (len(ledger["roots"]), len(ledger["records"])) == sizes
    assert ledger["listener_s"] > 0.0 and ledger["dropped"] == 0


def test_the_persistent_caches_answer_is_on_the_record(tmp_path):
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = [getattr(jax.config, name) for name in names]

    @jax.jit
    def probe(x):
        return jnp.tanh(x @ x.T).sum() - 7.0

    x = jnp.ones((19, 5))
    try:
        probe(x).block_until_ready()             # no cache placed: off
        for name, value in zip(names, (str(tmp_path), 0.0, -1)):
            jax.config.update(name, value)
        cc.reset_cache()
        for _ in range(2):
            jax.clear_caches()
            probe(x).block_until_ready()
    finally:
        for name, value in zip(names, was):
            jax.config.update(name, value)
        cc.reset_cache()
    backends = [r for r in _compile_records("probe")
                if r["name"] == "compile.backend"]
    assert [r["labels"]["cache"] for r in backends] == ["off", "miss",
                                                        "hit"]
    assert 0.0 < backends[2]["labels"]["retrieval_s"] <= (
        backends[2]["end_ns"] - backends[2]["start_ns"]) * 1e-9
    assert "retrieval_s" not in backends[1]["labels"]
    totals = obs.compile_ledger()["by_program"]["probe"]
    assert totals["cache"] == {"off": 1, "miss": 1, "hit": 1}
    assert totals["cache_retrieval"] == [
        backends[2]["labels"]["retrieval_s"], 1]
    assert obs.compile_seconds()["counts"]["cache_retrieval"] >= 1


@pytest.mark.parametrize("cap", [4, 65_536])
def test_compile_seconds_is_the_ledgers_sum_whatever_the_bound(
        monkeypatch, cap):
    import jax.numpy as jnp
    monkeypatch.setattr(tracing, "CAPTURE_CAP", cap)
    for k in range(1, 5):
        jax.jit(lambda x, k=k: jnp.exp(x) * k)(jnp.ones((k,)))
    ledger = obs.compile_ledger()
    made = sum(count for _, count in _seconds_by_kind(
        ledger["by_program"]).values())
    assert made >= 12
    compiles = [r for r in ledger["records"]
                if r["name"].startswith("compile.")]
    assert len(compiles) == min(cap, made)
    assert ledger["dropped"] == made - len(compiles)
    seen = obs.compile_seconds()
    by_kind = _seconds_by_kind(ledger["by_program"])
    assert set(by_kind) == set(seen["seconds"]) == set(seen["counts"])
    for kind, (seconds, count) in by_kind.items():
        assert seen["counts"][kind] == count
        assert seen["seconds"][kind] == pytest.approx(seconds)
    if not ledger["dropped"]:
        # kind by kind the kept records are the totals
        kinds = {"compile.trace": "trace", "compile.lower": "lower",
                 "compile.backend": "backend_compile"}
        for name, kind in kinds.items():
            mine = [r for r in compiles if r["name"] == name]
            assert len(mine) == seen["counts"][kind]
            assert sum(r["end_ns"] - r["start_ns"]
                       for r in mine) * 1e-9 == pytest.approx(
                           seen["seconds"][kind])


@pytest.mark.parametrize("make,labels", [
    (lambda g: st.random_matrix(96, 40, 32, g, np.float32, seed=1),
     {"m": 96, "n": 40, "nb": 32, "grid": "1x1", "dtype": "float32"}),
    (lambda g: st.random_spd(96, nb=32, grid=g, dtype=np.float32, seed=1),
     {"m": 96, "n": 96, "nb": 32, "grid": "1x1", "dtype": "float32"}),
], ids=["random_matrix", "random_spd"])
def test_a_generator_opens_a_root_with_its_labels(grid11, make, labels):
    M = make(grid11)
    name = ("slate.random_spd" if isinstance(M, st.HermitianMatrix)
            else "slate.random_matrix")
    ledger = obs.compile_ledger()
    (root,) = ledger["roots"]
    assert root["name"] == name and root["labels"] == labels
    assert root["parent"] == 0 and root["end_ns"] > root["start_ns"]
    # whatever it compiled is its own, and a generator inside a
    # generator is a child, not a second root
    for r in _compile_records():
        assert r["solve"] == root["solve"]
    flown = [e["name"] for e in flight.events()]
    assert name in flown
    assert ("slate.random_matrix" in flown) or name == "slate.random_spd"


def test_the_import_is_a_record_of_the_ledger():
    t0 = time.perf_counter_ns()
    tracing.import_record(t0 - 5_000_000, jax_preloaded=True)
    (rec,) = [r for r in obs.compile_ledger()["records"]
              if r["name"] == "slate.import"]
    assert rec["labels"] == {"jax_preloaded": True}
    assert rec["parent"] == 0 and rec["solve"] == 0
    assert 5_000_000 <= rec["end_ns"] - rec["start_ns"] < 1_000_000_000


# ------------------------------------------------------------ one clock

def test_flight_ring_and_chrome_export_share_one_clock():
    obs.trace_on()
    try:
        wall_before = time.time()
        with obs.span("a"):
            time.sleep(0.01)
        time.sleep(0.02)
        with obs.span("b"):
            pass
        obs.instant("c")
        wall_after = time.time()
        chrome = {e["name"]: e for e in tracing.events()}
        ring = {e["name"]: e for e in flight.events()}
        for x, y in (("a", "b"), ("b", "c"), ("a", "c")):
            d_chrome = (chrome[y]["ts"] - chrome[x]["ts"]) * 1e-6
            d_ring = ring[y]["t"] - ring[x]["t"]
            assert abs(d_chrome - d_ring) < 5e-6
        # the ring's start + its duration is the Chrome event's end
        assert abs(ring["a"]["dur_s"] * 1e6 - chrome["a"]["dur"]) < 1e-3
        # and the ring is on the wall clock, through the one anchor
        assert wall_before - 0.05 <= ring["a"]["t"] <= wall_after + 0.05
    finally:
        obs.trace_off()


# --------------------------------------------------------- named scopes

def test_named_scopes_mark_the_phases_of_the_cells_programs(grid22):
    from slate_tpu.linalg import potrf as potrf_mod
    from slate_tpu.ops import blas
    import jax.numpy as jnp
    A, B = _posv_operands(grid22)
    text = jax.jit(potrf_mod._potrf_chunk_core,
                   static_argnames=("k0", "klen", "win_hi", "tier")).lower(
        A, jnp.zeros((), jnp.int32), k0=0, klen=2).as_text(debug_info=True)
    for scope in ("panel", "panel_bcast", "trailing"):
        assert f'"{scope}/' in text, scope
    L = st.TriangularMatrix(data=A.data, m=A.m, n=A.n, nb=A.nb,
                            grid=A.grid, uplo=st.Uplo.Lower,
                            diag=st.Diag.NonUnit)
    for trans in (False, True):
        text = jax.jit(blas._trsm_left_jit._fn,
                       static_argnames=("lower", "unit", "trans")).lower(
            jnp.float32(1.0), L, B, lower=True, unit=False,
            trans=trans).as_text(debug_info=True)
        for scope in ("diag_solve", "update"):
            assert f'"{scope}/' in text, (scope, trans)
