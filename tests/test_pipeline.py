"""slatepipe tests: double-buffered ring-SUMMA and software-pipelined
factorization loops (Option.PipelineDepth).

The double-buffered systolic ring issues the ppermute shift of block
k+1 before the local dot of block k consumes its buffer; shift and dot
touch disjoint values, so the schedule change must be BITWISE invisible
— asserted here on 1x8 / 2x4 / 4x2 meshes, f32/f64, and all three
TrailingPrecision tiers, including an odd tile count that exercises the
lcm-padding edge.  The pipelined potrf/getrf loops reorder whole-panel
work but keep per-element operation order, so factors match the
sequential path and getrf pivots are bit-identical.
"""

import math

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu.types import Option, MethodGemm
from slate_tpu.internal.precision import TIERS
from tests.conftest import rand, spd

GRIDS = [(1, 8), (2, 4), (4, 2)]


def _grid(p, q):
    return st.Grid(p, q)


def _nt_chunked(p, q):
    """The fewest block columns the drivers run as chunked super-steps
    on a p x q mesh (``kt >= 2 lcm(p, q)``): two chunks of lcm(p, q)
    columns, a first and a last, each with its own prologue, body and
    epilogue (``dag.chunk_plan`` clamps the depth to ``klen - 1``, so a
    chunk of four columns holds a depth of 3). A chunk is a program of
    its own, so compile time goes with their number; the inner chunks
    of a longer matrix are in the depth-3 cases below, at nt = 16."""
    return 2 * math.lcm(p, q)


# ---------------------------------------------------------------------------
# double-buffered ring-SUMMA == single-buffered, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p,q", GRIDS)
@pytest.mark.parametrize("dt", [np.float32, np.float64])
def test_ring_double_buffer_bitwise(p, q, dt):
    g = _grid(p, q)
    nb, nt = 8, 8
    n = nt * nb - 3                       # ragged last tile
    a = np.asarray(rand(n, n, dt, seed=p * 10 + q))
    b = np.asarray(rand(n, n, dt, seed=p * 10 + q + 1))
    c0 = np.asarray(rand(n, n, dt, seed=p * 10 + q + 2))

    def run(depth):
        A = st.Matrix.from_dense(a, nb=nb, grid=g)
        B = st.Matrix.from_dense(b, nb=nb, grid=g)
        C = st.Matrix.from_dense(c0, nb=nb, grid=g)
        C = st.gemm(1.0, A, B, 0.5, C,
                    opts={Option.MethodGemm: MethodGemm.Ring,
                          Option.PipelineDepth: depth})
        return np.asarray(C.to_dense())

    db, sb = run(1), run(0)
    np.testing.assert_array_equal(db, sb)
    ref = a.astype(np.float64) @ b.astype(np.float64) + 0.5 * c0
    tol = 1e-3 if dt == np.float32 else 1e-11
    np.testing.assert_allclose(db, ref, rtol=tol, atol=tol)


@pytest.mark.parametrize("tier", list(TIERS))
def test_ring_double_buffer_bitwise_tiers(grid24, tier):
    n, nb = 61, 8                         # nt=8, ragged edge
    a = np.asarray(rand(n, n, np.float32, seed=31))
    b = np.asarray(rand(n, n, np.float32, seed=32))

    def run(depth):
        A = st.Matrix.from_dense(a, nb=nb, grid=grid24)
        B = st.Matrix.from_dense(b, nb=nb, grid=grid24)
        C = st.Matrix.zeros(n, n, nb=nb, grid=grid24, dtype=np.float32)
        C = st.gemm(1.0, A, B, 0.0, C,
                    opts={Option.MethodGemm: MethodGemm.Ring,
                          Option.TrailingPrecision: tier,
                          Option.PipelineDepth: depth})
        return np.asarray(C.to_dense())

    np.testing.assert_array_equal(run(1), run(0))


def test_ring_double_buffer_odd_tile_count(grid24):
    # odd nt: the generalized Cannon schedule pads to lcm(p, q) steps;
    # the double-buffered shift order must survive the padded steps
    n, nb = 7 * 8, 8                      # nt=7, odd
    a = np.asarray(rand(n, n, np.float64, seed=41))
    b = np.asarray(rand(n, n, np.float64, seed=42))

    def run(depth):
        A = st.Matrix.from_dense(a, nb=nb, grid=grid24)
        B = st.Matrix.from_dense(b, nb=nb, grid=grid24)
        C = st.Matrix.zeros(n, n, nb=nb, grid=grid24, dtype=np.float64)
        C = st.gemm(1.0, A, B, 0.0, C,
                    opts={Option.MethodGemm: MethodGemm.Ring,
                          Option.PipelineDepth: depth})
        return np.asarray(C.to_dense())

    db = run(1)
    np.testing.assert_array_equal(db, run(0))
    np.testing.assert_allclose(db, a @ b, rtol=1e-11, atol=1e-11)


def test_gemm_a_reduce_scatter_epilogue(grid24):
    # stationary-A algorithm: replicated B, local partials over the
    # k ≡ (mesh column) classes, reduce-scatter epilogue landing each
    # chip exactly its block-cyclic C columns
    n, nb = 61, 8
    a = np.asarray(rand(n, n, np.float64, seed=51))
    b = np.asarray(rand(n, n, np.float64, seed=52))
    c0 = np.asarray(rand(n, n, np.float64, seed=53))
    A = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    B = st.Matrix.from_dense(b, nb=nb, grid=grid24)
    C = st.Matrix.from_dense(c0, nb=nb, grid=grid24)
    C = st.gemm(2.0, A, B, -1.0, C,
                opts={Option.MethodGemm: MethodGemm.GemmA})
    np.testing.assert_allclose(np.asarray(C.to_dense()),
                               2.0 * (a @ b) - c0,
                               rtol=1e-11, atol=1e-11)


# ---------------------------------------------------------------------------
# pipelined factorizations == sequential (pivots bit-identical)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p,q", GRIDS)
def test_potrf_pipelined_matches_sequential(p, q):
    g = _grid(p, q)
    n, nb = _nt_chunked(p, q) * 8, 8      # nt = 2·lcm ⇒ chunked
    a = spd(n, np.float64, seed=p * 100 + q)
    A1 = st.HermitianMatrix.from_dense(a, nb=nb, grid=g)
    Lp, ip = st.potrf(A1, opts={Option.PipelineDepth: 1})
    A2 = st.HermitianMatrix.from_dense(a, nb=nb, grid=g)
    Ls, is_ = st.potrf(A2, opts={Option.PipelineDepth: 0})
    assert int(ip) == int(is_) == 0
    lp = np.tril(np.asarray(Lp.to_dense()))
    ls = np.tril(np.asarray(Ls.to_dense()))
    np.testing.assert_allclose(lp, ls, rtol=1e-13, atol=1e-13)
    np.testing.assert_allclose(lp @ lp.T, a, rtol=1e-10, atol=1e-10)


@pytest.mark.parametrize("p,q", GRIDS)
def test_getrf_pipelined_matches_sequential_pivots_bitwise(p, q):
    g = _grid(p, q)
    n, nb = _nt_chunked(p, q) * 8, 8
    a = np.asarray(rand(n, n, np.float64, seed=p * 100 + q + 7))
    A1 = st.Matrix.from_dense(a, nb=nb, grid=g)
    LUp, pivp, ip = st.getrf(A1, opts={Option.PipelineDepth: 1})
    A2 = st.Matrix.from_dense(a, nb=nb, grid=g)
    LUs, pivs, is_ = st.getrf(A2, opts={Option.PipelineDepth: 0})
    assert int(ip) == int(is_) == 0
    # the pipelined loop must see bit-identical panel values at every
    # pivot comparison — pivots are exactly equal, not just close
    np.testing.assert_array_equal(np.asarray(pivp), np.asarray(pivs))
    np.testing.assert_allclose(np.asarray(LUp.to_dense()),
                               np.asarray(LUs.to_dense()),
                               rtol=1e-13, atol=1e-13)


def test_potrf_pipelined_one_program_path(grid24):
    # nt < 2·lcm(p,q) routes through the single-program jit; the
    # static depth arg must still select the pipelined body there
    n, nb = 48, 8                         # nt=6 < 8
    a = spd(n, np.float64, seed=71)
    A1 = st.HermitianMatrix.from_dense(a, nb=nb, grid=grid24)
    Lp, ip = st.potrf(A1, opts={Option.PipelineDepth: 1})
    A2 = st.HermitianMatrix.from_dense(a, nb=nb, grid=grid24)
    Ls, is_ = st.potrf(A2, opts={Option.PipelineDepth: 0})
    assert int(ip) == int(is_) == 0
    np.testing.assert_allclose(np.asarray(Lp.to_dense()),
                               np.asarray(Ls.to_dense()),
                               rtol=1e-13, atol=1e-13)


@pytest.mark.parametrize("tier", list(TIERS))
def test_potrf_pipelined_matches_sequential_tiers(grid24, tier):
    # every TrailingPrecision tier flows through the pipelined loop's
    # trailing einsum with the same dot kwargs as the sequential one
    n, nb = _nt_chunked(2, 4) * 8, 8
    a = spd(n, np.float32, seed=81).astype(np.float32)
    A1 = st.HermitianMatrix.from_dense(a, nb=nb, grid=grid24)
    Lp, ip = st.potrf(A1, opts={Option.TrailingPrecision: tier,
                                Option.PipelineDepth: 1})
    A2 = st.HermitianMatrix.from_dense(a, nb=nb, grid=grid24)
    Ls, is_ = st.potrf(A2, opts={Option.TrailingPrecision: tier,
                                 Option.PipelineDepth: 0})
    assert int(ip) == int(is_) == 0
    np.testing.assert_allclose(np.asarray(Lp.to_dense()),
                               np.asarray(Ls.to_dense()),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# depth-k schedules (runtime/dag.py chunk plans) == sequential, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth", [2, 3])
def test_potrf_depth_k_bitwise(grid24, depth):
    # the plan-driven ring (dag.chunk_plan) reorders scheduling only:
    # every depth reproduces the sequential factors EXACTLY. Depth 3
    # at nt=16 (four chunks: a first, two inner, a last), depth 2 at
    # the fewest chunked columns
    n, nb = (16 if depth == 3 else _nt_chunked(2, 4)) * 8, 8
    a = spd(n, np.float64, seed=60 + depth)
    A0 = st.HermitianMatrix.from_dense(a, nb=nb, grid=grid24)
    Ls, is_ = st.potrf(A0, opts={Option.PipelineDepth: 0})
    A1 = st.HermitianMatrix.from_dense(a, nb=nb, grid=grid24)
    Lk, ik = st.potrf(A1, opts={Option.PipelineDepth: depth})
    assert int(is_) == int(ik) == 0
    np.testing.assert_array_equal(np.tril(np.asarray(Lk.to_dense())),
                                  np.tril(np.asarray(Ls.to_dense())))


@pytest.mark.parametrize("depth", [2, 3])
def test_getrf_depth_k_bitwise_pivots(grid24, depth):
    # LU at depth k: the exclusion-window swaps and column advances
    # must reproduce the sequential elimination bit-for-bit — factors
    # AND the pivot vector (depth 3 at nt=16: four chunks, inner ones
    # included; depth 2 at the fewest chunked columns)
    n, nb = (16 if depth == 3 else _nt_chunked(2, 4)) * 8, 8
    a = np.asarray(rand(n, n, np.float64, seed=160 + depth))
    A0 = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    LUs, pivs, is_ = st.getrf(A0, opts={Option.PipelineDepth: 0})
    A1 = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    LUk, pivk, ik = st.getrf(A1, opts={Option.PipelineDepth: depth})
    assert int(is_) == int(ik) == 0
    np.testing.assert_array_equal(np.asarray(pivk), np.asarray(pivs))
    np.testing.assert_array_equal(np.asarray(LUk.to_dense()),
                                  np.asarray(LUs.to_dense()))


@pytest.mark.parametrize("p,q", [(2, 4), (4, 2)])
def test_getrf_depth2_bitwise_meshes(p, q):
    n, nb = _nt_chunked(p, q) * 8, 8
    g = _grid(p, q)
    a = np.asarray(rand(n, n, np.float64, seed=p * 100 + q + 60))
    A0 = st.Matrix.from_dense(a, nb=nb, grid=g)
    LUs, pivs, is_ = st.getrf(A0, opts={Option.PipelineDepth: 0})
    A1 = st.Matrix.from_dense(a, nb=nb, grid=g)
    LUk, pivk, ik = st.getrf(A1, opts={Option.PipelineDepth: 2})
    assert int(is_) == int(ik) == 0
    np.testing.assert_array_equal(np.asarray(pivk), np.asarray(pivs))
    np.testing.assert_array_equal(np.asarray(LUk.to_dense()),
                                  np.asarray(LUs.to_dense()))


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("m,n", [(96, 96), (128, 64)])
def test_geqrf_depth_k_bitwise(grid24, depth, m, n):
    # QR through the runtime schedule: the per-column compact-WY
    # advance slices bitwise-identically out of the sequential
    # trailing applies, for square and tall shapes
    nb = 16
    a = np.asarray(rand(m, n, np.float64, seed=70 + depth))
    A0 = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    QRs, Ts = st.geqrf(A0, opts={Option.PipelineDepth: 0})
    A1 = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    QRk, Tk = st.geqrf(A1, opts={Option.PipelineDepth: depth})
    np.testing.assert_array_equal(np.asarray(QRk.to_dense()),
                                  np.asarray(QRs.to_dense()))
    np.testing.assert_array_equal(np.asarray(Tk), np.asarray(Ts))


# ---------------------------------------------------------------------------
# executable-cache key: pipelined and sequential never share
# ---------------------------------------------------------------------------

def test_pipeline_depth_is_a_cache_key_component(grid24, tmp_path,
                                                 monkeypatch):
    from slate_tpu.cache import jitcache, store as slc
    from slate_tpu.obs import metrics
    was_enabled = metrics.enabled()
    metrics.enable()
    metrics.reset()
    slc.set_cache_dir(tmp_path / "exec")
    try:
        n, nb = 48, 8                     # one-program path (nt=6)
        a = spd(n, np.float64, seed=91)
        for depth in (2, 1, 0):
            A = st.HermitianMatrix.from_dense(a, nb=nb, grid=grid24)
            st.potrf(A, opts={Option.PipelineDepth: depth})
        # same routine, same shapes — only the static depth differs,
        # and every depth must produce its own executable
        assert metrics.counter_value("cache.miss", routine="potrf") == 3
        # a re-run at an already-compiled depth is a hit, not a miss
        A = st.HermitianMatrix.from_dense(a, nb=nb, grid=grid24)
        st.potrf(A, opts={Option.PipelineDepth: 2})
        assert metrics.counter_value("cache.miss", routine="potrf") == 3
    finally:
        slc.reset_cache_dir()
        jitcache.clear_in_process()
        metrics.reset()
        if not was_enabled:
            metrics.disable()


# ---------------------------------------------------------------------------
# two-axis link attribution (ICI vs DCN)
# ---------------------------------------------------------------------------

def test_link_bytes_follow_axis_roles(monkeypatch):
    import slate_tpu.obs as obs
    from slate_tpu.obs import metrics
    from slate_tpu import grid as grid_mod
    obs.metrics_on()
    monkeypatch.setenv("SLATE_TPU_DCN_GBS", "2.0")
    try:
        # declare the q axis host-crossing, as dcn_grid does for a
        # hybrid mesh: bytes moved on q must bill as DCN, p stays ICI
        grid_mod.set_axis_roles(q="dcn")
        x = np.zeros((64, 64), np.float32)
        with obs.link_window("pipe-unit"):
            obs.comm_event("allgather", "p", x, axis_size=4, tiled=True)
            obs.comm_event("allgather", "q", x, axis_size=2, tiled=True)
        assert obs.counter_value("comm.link_bytes", kind="allgather",
                                 axis="p", link="ici") > 0
        assert obs.counter_value("comm.link_bytes", kind="allgather",
                                 axis="q", link="dcn") > 0
        rows = {(g["labels"]["axis"], g["labels"]["link"]): g["value"]
                for g in metrics.snapshot()["gauges"]
                if g["name"] == "comm.link_occupancy"
                and g["labels"].get("where") == "pipe-unit"}
        assert ("p", "ici") in rows and ("q", "dcn") in rows
        # same wall window, q moved fewer bytes but against a 2 GB/s
        # DCN link vs the default ICI figure — occupancy rows must be
        # computed against their own link's bandwidth
        assert rows[("q", "dcn")] > 0
    finally:
        grid_mod.set_axis_roles(p="ici", q="ici")


def test_grid_block_cyclic_map(grid24):
    g = grid24
    # 2D block-cyclic: tile (i, j) lives on device (i%p, j%q) at local
    # slot (i//p, j//q) — and the round trip reproduces (i, j)
    for (i, j) in [(0, 0), (1, 3), (5, 2), (7, 7)]:
        r, c = g.tile_owner(i, j)
        si, sj = g.tile_slot(i, j)
        assert (r, c) == (i % g.p, j % g.q)
        assert g.global_tile(r, c, si, sj) == (i, j)
        assert g.tile_device(i, j) is g.mesh.devices[r, c]
    assert g.axis_role("p") in ("ici", "dcn")
    assert g.link_gbs("p") > 0


def test_matrix_tile_accessor(grid24):
    n, nb = 32, 8
    a = np.arange(n * n, dtype=np.float64).reshape(n, n)
    A = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    got = np.asarray(A.tile(1, 2))
    np.testing.assert_array_equal(got, a[8:16, 16:24])
