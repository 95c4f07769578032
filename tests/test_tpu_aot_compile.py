"""The main path's kernels and programs, compiled for the real chip.

Nothing runs: the TPU compiler installed in the sandbox compiles for a
DESCRIBED ``v5e:2x2`` (no chip attached), which refuses what interpret
mode cannot see — misaligned slices, too much VMEM, a kernel that
cannot be partitioned, a program that does not fit HBM.  Real widths
(h=16384, nb=1024); the n=16384 whole-factorization programs take
minutes and are compiled by hand, not here.

The topology is described inside a module-scoped fixture and nowhere at
import time (one process at a time may load libtpu; every xdist worker
imports every test file).  JAX's persistent compilation cache is off
around these compiles: an entry written without a chip cannot be read
back and only produces warnings.  So is x64, which tests/conftest.py
turns on for the CPU references: the chip runs with jax's default, and
Mosaic has no 64-bit integers.
"""

import math
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import slate_tpu as slate
from tests.conftest import all_reduce_shapes

H, W, NB = 16384, 128, 1024
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = (jax.config.jax_enable_compilation_cache,
            jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev[0])
    jax.config.update("jax_enable_x64", prev[1])
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tpu_grid22(topo):
    return slate.Grid(2, 2, devices=list(topo.devices))


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _loop_bodies(hlo: str) -> str:
    """The text of the computations that a ``while`` of ``hlo`` runs
    as its body."""
    bodies = set(re.findall(r"\bbody=(%?[\w.\-]+)", hlo))
    return "\n".join(c for c in hlo.split("\n\n")
                     if c.lstrip().split(" ", 1)[0] in bodies)


def _widest_product(text: str) -> int:
    """Elements of the largest f32 result of a ``dot`` or ``convolution``
    in a compiled text."""
    products = re.findall(
        r"= f32\[([\d,]+)\]\S* (?:convolution|dot)\(", text)
    assert products
    return max(math.prod(map(int, dims.split(","))) for dims in products)


# -- the production LU panel kernels (internal/panel_plu.py) ---------------

@pytest.mark.parametrize("fold", [True, False], ids=["fold", "nofold"])
def test_plu_subpanel_compiles(one_chip, fold):
    from slate_tpu.internal import panel_plu
    sub = jax.ShapeDtypeStruct((H, W), F32, sharding=one_chip)
    act = jax.ShapeDtypeStruct((H,), F32, sharding=one_chip)
    c = _compile(partial(panel_plu.plu_subpanel, fold=fold), sub, act)
    assert _kernels(c) >= 3      # transpose in, factor, transpose out


def test_fold_unfold_panel_compile(one_chip):
    from slate_tpu.internal import panel_plu
    flat = jax.ShapeDtypeStruct((H, NB), F32, sharding=one_chip)
    folded = jax.ShapeDtypeStruct((8, NB, H // 8), F32, sharding=one_chip)
    assert _kernels(_compile(panel_plu.fold_panel, flat)) == 1
    assert _kernels(_compile(panel_plu.unfold_panel, folded)) == 1


def test_plu_call_folded_block_compiles(one_chip):
    from slate_tpu.internal import panel_plu
    pcf = jax.ShapeDtypeStruct((8, NB, H // 8), F32, sharding=one_chip)
    act = jax.ShapeDtypeStruct((8, H // 8), F32, sharding=one_chip)
    sidx = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    assert _kernels(_compile(panel_plu.plu_call_folded_block,
                             pcf, act, sidx)) == 1


def test_getrf_fast_group_program_compiles_with_the_kernel(topo, one_chip):
    """The smaller twin (n=8192, two panels) of the program slate.gesv
    runs at n=16384 on one chip: layout-pinned, donated, Pallas
    panels."""
    from slate_tpu.linalg import getrf
    n = 8192
    a = jax.ShapeDtypeStruct((n, n), F32, sharding=one_chip)
    content = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    info = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    c = getrf._fast_group_program(topo.devices[0]).lower(
        a, content, info, 0, 2, NB, False, True, None).compile()
    assert _kernels(c) > 0


# -- the Householder panel geqrf turns on by default on the chip -----------

def test_qr_panel_compiles(one_chip):
    from slate_tpu.internal import panel_qr
    pan = jax.ShapeDtypeStruct((H, NB), F32, sharding=one_chip)
    assert _kernels(_compile(panel_qr.qr_panel_blocked, pan)) >= NB // W


def test_gels_cell_programs_compile_with_the_panel_kernel(topo):
    """The two programs of ``slate.gels(MethodGels.Geqrf)`` at the
    benchmark cell's shape (m=16384, n=1024, nb=256: PR 44): the
    exact-shape QR with its panels in the Pallas kernel at the tallest
    height it takes, and ``unmqr`` on the 8 right-hand sides."""
    from slate_tpu.linalg import geqrf
    m, n, nb, nrhs = H, 1024, 256, 8
    g = slate.Grid(1, 1, devices=[topo.devices[0]])

    def tiled(rows, cols):
        data = jax.ShapeDtypeStruct(
            (1, 1, rows // nb, -(-cols // nb), nb, nb), F32,
            sharding=g.sharding())
        return slate.Matrix(data=data, m=rows, n=cols, nb=nb, grid=g)

    A, B = tiled(m, n), tiled(m, nrhs)
    assert geqrf._panel_form(A, "tpu") == "pallas"
    c = geqrf._geqrf_fast_jit.lower(A, panel_mode="tpu",
                                    tier="bf16_6x").compile()
    assert _kernels(c) == (n // nb) * (nb // W)     # a call a subpanel
    assert c.memory_analysis().temp_size_in_bytes < 16 * 2 ** 20
    T = jax.ShapeDtypeStruct((n // nb, nb, nb), F32)
    assert _kernels(geqrf._unmqr_jit.lower(A, T, B, False).compile()) == 0


# -- one 2x2 super-step chunk of each factorization -------------------------

def _tiles(grid, n=H, nb=NB):
    t = n // nb
    return jax.ShapeDtypeStruct(
        (grid.p, grid.q, t // grid.p, t // grid.q, nb, nb), F32,
        sharding=grid.sharding())


def _assert_sharded_with_collectives(compiled, whole_bytes):
    text = compiled.as_text()
    assert "all-gather" in text or "all-reduce" in text
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert abs(per_device - whole_bytes // 4) < 2 ** 20, per_device


def test_potrf_chunk_2x2_compiles_sharded(tpu_grid22):
    from slate_tpu.linalg import potrf
    data = _tiles(tpu_grid22)
    A = slate.HermitianMatrix(data=data, m=H, n=H, nb=NB, grid=tpu_grid22)
    info0 = jax.ShapeDtypeStruct((), jnp.int32)
    c = potrf._potrf_chunk_jit.lower(A, info0, 0, 2,
                                     tier="bf16_6x").compile()
    _assert_sharded_with_collectives(c, H * H * 4)


def _getrf_chunk(grid, k0):
    """The chunk program of block columns [k0, k0 + 2), compiled."""
    from slate_tpu.linalg import getrf
    A = slate.Matrix(data=_tiles(grid), m=H, n=H, nb=NB, grid=grid)
    piv0 = jax.ShapeDtypeStruct((H // NB, NB), jnp.int32)
    info0 = jax.ShapeDtypeStruct((), jnp.int32)
    return getrf._getrf_chunk_jit.lower(A, piv0, info0, k0, 2,
                                        tier="bf16_6x").compile()


def _all_gathers(text) -> int:
    return text.count(" all-gather(") + text.count(" all-gather-start(")


def _all_gather_shapes(text) -> set:
    """(dtype, dims) of every all-gather's result in an optimized HLO
    text (an async one appears once in each fusion it is split over)."""
    import re
    return {(dt, tuple(int(d) for d in dims.split(",")))
            for dt, dims in re.findall(
                r"= (\w+)\[([\d,]+)\]\S* all-gather(?:-start)?\(", text)}


def test_getrf_chunk_2x2_compiles_sharded(tpu_grid22):
    c = _getrf_chunk(tpu_grid22, 0)
    _assert_sharded_with_collectives(c, H * H * 4)
    # the first chunk's temp decides the cell's peak_hbm_gib: 396 MiB
    # compiled here (2026-09-28, jax 0.9.0) with the panel factored where
    # its rows are stored; 860 while the [M, nb] panel was gathered
    temp_mib = c.memory_analysis().temp_size_in_bytes / 2 ** 20
    assert temp_mib < 450, temp_mib


def test_getrf_last_chunk_2x2_collectives_and_temp(tpu_grid22):
    """The last of the eight chunk programs ``gesv_16k_2x2`` runs
    (k0 = 14, two block columns). What crosses chips in a step, and
    nothing else: column k's local slots over q (mtl*nb*nb elements);
    over p the tournament's p*nb winner rows and their row ids (the
    16,384-row panel is over the cap of one ``lu``, so it is factored
    where its rows are stored and never gathered), the diagonal tile,
    the row swaps' candidate rows (2*nb rows of the local stack: twice
    column k's bytes, the largest thing that moves), the U block-row
    of the window. No all-to-all. The next change to the panel or the
    swaps has these numbers to beat."""
    import math
    mtl = ntl = H // NB // 2
    c = _getrf_chunk(tpu_grid22, H // NB - 2)
    _assert_sharded_with_collectives(c, H * H * 4)
    text = c.as_text()
    assert "all-to-all" not in text and "collective-permute" not in text
    reduced = sorted(math.prod(dims) for _, dims in all_reduce_shapes(text))
    assert reduced == [NB * NB,                 # U(k, last tile column)
                       NB * NB,                 # the diagonal tile over p
                       mtl * NB * NB,           # column k over q
                       2 * NB * ntl * NB], reduced      # swapped rows
    assert _all_gather_shapes(text) == {("f32", (2 * NB, NB)),
                                        ("s32", (2 * NB,))}
    assert f"f32[2,{mtl},{NB},{NB}]" not in text    # no [M, nb] panel
    # 362 MiB compiled here (2026-09-28, jax 0.9.0; 330 with the
    # gathered panel, whose first chunk held 860): the swaps' rows
    # (2 x 64 MiB of candidates, 256 MiB of replacements) lead it
    temp_mib = c.memory_analysis().temp_size_in_bytes / 2 ** 20
    assert temp_mib < 400, temp_mib


def _while_trips(text):
    """Trip counts of the compiled text's ``while`` loops, each read
    off the constant its condition compares the counter with."""
    trips = []
    for cond in re.findall(r" while\(.*?condition=%([\w.\-]+)", text):
        body = text[text.index(f"\n%{cond} ("):]
        body = body[:body.index("\n}")]
        assert "direction=LT" in body, body
        trips += [int(n) for n in re.findall(r"constant\((\d+)\)", body)]
    return sorted(trips)


def test_apply_piv_2x2_one_rhs_compiles(tpu_grid22):
    """``getrs``'s pivots on the cell's B, [16384, 1] in one tile
    column a device column: the stored 2 x 64 MiB (one real column) are
    gathered to every device, the 16 panels' swaps replayed at once in
    one ``while`` of 1,024 trips and composed in one of 16
    (``_sim_perm``), and the rows taken: one all-gather, nothing else
    crosses."""
    from slate_tpu.linalg import getrf
    b = jax.ShapeDtypeStruct((2, 2, H // NB // 2, 1, NB, NB), F32,
                             sharding=tpu_grid22.sharding())
    B = slate.Matrix(data=b, m=H, n=1, nb=NB, grid=tpu_grid22)
    piv = jax.ShapeDtypeStruct((H // NB, NB), jnp.int32)
    c = getrf._apply_piv_jit.lower(B, piv, forward=True).compile()
    text = c.as_text()
    assert "all-reduce" not in text and "all-to-all" not in text
    assert _all_gathers(text) == 1
    assert _while_trips(text) == [H // NB, NB]
    mem = c.memory_analysis()
    assert abs(mem.argument_size_in_bytes
               - b.size * 4 // tpu_grid22.size) < 2 ** 20
    # 64 MiB compiled here: the gathered B; a reader of one column
    # would hold 64 KiB
    assert mem.temp_size_in_bytes / 2 ** 20 < 80


# -- potrs' second solve: trsm on conj_transpose(L) where it lies ----------

def _trsm_h_by_8(topo, tpu_grid22, shape, trans, lower=True, unit=False):
    """The cells' solve, L (H x H) against 8 right-hand sides in one
    1024-wide tile column a device column, compiled; the grid it is
    for; and the bytes of its two stored operands."""
    from slate_tpu.ops import blas
    grid = (tpu_grid22 if shape == "2x2"
            else slate.Grid(1, 1, devices=[topo.devices[0]]))
    L = slate.TriangularMatrix(
        data=_tiles(grid), m=H, n=H, nb=NB, grid=grid,
        uplo=slate.Uplo.Lower if lower else slate.Uplo.Upper)
    b = jax.ShapeDtypeStruct(
        (grid.p, grid.q, H // NB // grid.p, 1, NB, NB), F32,
        sharding=grid.sharding())
    B = slate.Matrix(data=b, m=H, n=8, nb=NB, grid=grid)
    c = blas._trsm_left_jit.lower(jax.ShapeDtypeStruct((), F32), L, B,
                                  lower, unit, trans=trans).compile()
    return c, grid, (L.data.size + b.size) * 4


@pytest.mark.parametrize("shape", ["1x1", "2x2"])
def test_trsm_left_op_in_place_compiles_with_no_relayout(topo, tpu_grid22,
                                                         shape):
    c, grid, stored = _trsm_h_by_8(topo, tpu_grid22, shape, trans=True)
    text = c.as_text()
    assert "all-gather" not in text and "all-to-all" not in text
    mem = c.memory_analysis()
    assert abs(mem.argument_size_in_bytes
               - stored // grid.size) < 2 ** 20
    # no copy of the factor: the left-looking step needs tiles only (a
    # column of them as one value cost the one chip a re-laid copy of
    # all of L, 1 GiB of temporaries, until PR 46)
    assert mem.temp_size_in_bytes < 2 ** 24, mem.temp_size_in_bytes
    if shape == "2x2":
        assert "all-reduce" in text


@pytest.mark.parametrize("lower,unit,trans", [
    (True, False, False), (True, False, True),      # potrs: L, then L^H
    (True, True, False), (False, False, False),     # getrs: unit L, then U
    (False, False, True)],
    ids=["N", "C", "unit_lower_N", "upper_N", "upper_C"])
def test_trsm_left_one_chip_reads_tiles_past_the_diagonal(topo, tpu_grid22,
                                                          lower, unit, trans):
    """The one-chip cells' solves (``potrs``, ``getrs``, every M^-1 of
    ``gesv_mixed_gmres``): a step takes the tiles of column k one at a
    time from where each is stored, from the diagonal on. The program
    holds no re-laid copy of A (the parent's 1 GiB of temporaries), and
    what a step multiplies is one [nb, nb] by [nb, w] product in a loop
    whose trips end at the diagonal, not the column whole with its
    masked half."""
    c, grid, stored = _trsm_h_by_8(topo, tpu_grid22, "1x1", trans, lower,
                                   unit)
    mtl = H // NB
    mem = c.memory_analysis()
    assert abs(mem.argument_size_in_bytes - stored) < 2 ** 20
    assert mem.temp_size_in_bytes < 2 ** 24, mem.temp_size_in_bytes
    text = c.as_text()
    assert f"f32[{mtl},{mtl},{NB},{NB}]" in text        # A, the argument
    assert not re.findall(
        rf"= f32\[{mtl},{mtl},{NB},{NB}\]\S* (?:copy|fusion)\(", text)
    assert _widest_product(text) == NB * W
    # a step's flops as XLA counts them (an inner trip once): under the
    # unmasked half of the column, where the parent's were the column's
    assert c.cost_analysis()["flops"] <= 2 * (mtl // 2) * NB * NB * W


def _assert_a_stays(c, mtl):
    """The 2x2 program for a B of one tile column: A stays where it is
    stored, in the order it is stored in. No all-reduce carries the
    local slots of a tile column of it, nothing gathers it, and the
    program holds no copy of it."""
    import math
    text = c.as_text()
    assert "all-gather" not in text and "all-to-all" not in text
    sent = [math.prod(dims) for _, dims in all_reduce_shapes(text)]
    assert sent and max(sent) <= mtl * NB * W, sent
    assert c.memory_analysis().temp_size_in_bytes < 2 ** 24


@pytest.mark.parametrize("trans", [False, True], ids=["N", "C"])
@pytest.mark.parametrize("shape", ["1x1", "2x2"])
def test_trsm_left_8_rhs_multiplies_128_lanes(topo, tpu_grid22, shape,
                                              trans):
    """X rides the loop 128 columns wide, so no product of the program
    is [.., 1024, 1024] by [.., 1024, 1024]."""
    import math
    import re
    c, grid, _ = _trsm_h_by_8(topo, tpu_grid22, shape, trans)
    mtl = H // NB // grid.p
    text = c.as_text()
    # the widest is column k of A by X(k,:), [mtl, 1024, 128] (one tile
    # of it by its rows of X, [1024, 128], where A is read tile by
    # tile); the padded tile's was 8x that
    widest = _widest_product(text)
    assert widest in (mtl * NB * W, NB * W), widest
    # a step's flops: that product and the diagonal block's solve (the
    # padded tile cost 8x: 35.6e9 on one chip, 18.5e9 on the 2x2)
    assert c.cost_analysis()["flops"] < 1.2 * 2 * mtl * NB * NB * W
    if shape == "2x2":
        _assert_a_stays(c, mtl)


@pytest.mark.parametrize("trans", [False, True], ids=["N", "C"])
@pytest.mark.parametrize("lower,unit", [(True, True), (False, False),
                                        (False, True)],
                         ids=["unit_lower", "upper", "unit_upper"])
def test_trsm_left_moves_x_for_getrs_too(topo, tpu_grid22, lower, unit,
                                         trans):
    """``getrs`` on a grid takes the same form with L unit-lower and U
    upper, and their op'd twins (first cell to measure it:
    ``gesv_16k_2x2``, PERF 7)."""
    c, grid, _ = _trsm_h_by_8(topo, tpu_grid22, "2x2", trans, lower, unit)
    _assert_a_stays(c, H // NB // grid.p)


# -- the refining solvers' products with A: gemm at the width B holds --------

@pytest.mark.parametrize("shape", ["1x1", "2x2"])
def test_gemm_one_column_multiplies_128_lanes(topo, tpu_grid22, shape):
    """``mixed._residual`` / ``matvec`` at the cell's size: A (H x H)
    times one column stored as a 1024-wide tile column. B and C ride
    the product 128 columns wide, A is read where it lies (no copy of
    it among the temporaries), and on the grid what crosses p is a
    ``[nb, w]`` block-row of B beside column k of A over q."""
    import math
    from slate_tpu.ops import blas
    grid = (tpu_grid22 if shape == "2x2"
            else slate.Grid(1, 1, devices=[topo.devices[0]]))
    mtl = H // NB // grid.p
    col = jax.ShapeDtypeStruct((grid.p, grid.q, mtl, 1, NB, NB), F32,
                               sharding=grid.sharding())
    A = slate.Matrix(data=_tiles(grid), m=H, n=H, nb=NB, grid=grid)
    B = slate.Matrix(data=col, m=H, n=1, nb=NB, grid=grid)
    C = slate.Matrix(data=col, m=H, n=1, nb=NB, grid=grid)
    s = jax.ShapeDtypeStruct((), F32)
    c = blas._gemm_jit.lower(s, A, B, s, C, tier="bf16_6x").compile()
    text = c.as_text()
    assert _widest_product(text) <= mtl * NB * W
    # one chip: the whole product, 2 H H w; the grid: a step's, whose
    # loop cost_analysis counts once (the 1024-wide tile cost 8x)
    assert c.cost_analysis()["flops"] < 1.2 * 2 * H * H * W / grid.size
    mem = c.memory_analysis()
    assert abs(mem.argument_size_in_bytes
               - (A.data.size + 2 * col.size) * 4 // grid.size) < 2 ** 20
    # a copy of the local A would be 2^30 / chips bytes
    assert mem.temp_size_in_bytes < 2 ** 24, mem.temp_size_in_bytes
    assert "all-gather" not in text and "all-to-all" not in text
    sent = sorted(math.prod(dims) for _, dims in all_reduce_shapes(text))
    assert sent == ([] if shape == "1x1" else [NB * W, mtl * NB * NB]), sent


# -- one served executable ---------------------------------------------------

def test_served_posv_bucket_compiles(one_chip):
    from slate_tpu.cache import buckets
    from slate_tpu.serve import batched
    bucket, rung = 1024, 4
    a = jax.ShapeDtypeStruct((rung, bucket, bucket), F32,
                             sharding=one_chip)
    b = jax.ShapeDtypeStruct((rung, bucket, 8), F32, sharding=one_chip)
    c = batched._posv_jit.lower(a, b, nb=buckets.default_nb(bucket),
                                tier="bf16_6x").compile()
    assert c.memory_analysis().argument_size_in_bytes >= a.size * 4


# -- heev with vectors: the tridiagonal stage's merges and the blocked
#    back-transform at the benchmark cell's size (PR 41) ------------------

N_EIG, BAND_EIG = 8192, 128


def _shape(one_chip, *dims, dtype=F32):
    return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)


@pytest.mark.parametrize("m, k", [(1, N_EIG), (2, N_EIG // 2), (128, 64)],
                         ids=["top", "second", "bottom"])
def test_stedc_level_compiles(one_chip, m, k):
    """The three programs of a level of the cell's tree, the top merge
    (m = 1, k = n = 8192) as the 128 bottom ones: everything k x k of
    the secular solves fuses (no temporary of a G's size), and no level
    holds more than the top merge does: its G, its blocks of Z and
    their product, n·k entries each."""
    from slate_tpu.linalg import stedc
    n, i32 = N_EIG, jnp.int32
    assert m * k == n
    poles = _shape(one_chip, m, 3, k)
    each, wide = _shape(one_chip, m, dtype=i32), _shape(one_chip, m, k)
    rows = stedc._zrows_jit._jit.lower(
        _shape(one_chip, n, n), each, each, k=k).compile()
    solve = stedc._secular_jit._jit.lower(
        poles, _shape(one_chip, m), each, iters=35).compile()
    assert solve.memory_analysis().temp_size_in_bytes < 2 ** 24
    merge = stedc._merge_jit._jit.lower(
        _shape(one_chip, n, n), each, poles,
        _shape(one_chip, m, k, dtype=i32), wide, wide,
        _shape(one_chip, m, 3, k, dtype=i32),
        _shape(one_chip, 2, n, dtype=i32), _shape(one_chip, 2, n),
        _shape(one_chip, dtype=i32)).compile()
    assert merge.memory_analysis().temp_size_in_bytes <= 3 * 4 * n * n
    # a rotation turns two rows of G where they lie: a loop that copied
    # G each step was 1.02 s of a 0.10 s stage on the chip (PR 43)
    copied = re.findall(r"= f32\[([\d,]+)\]\S* copy\(",
                        _loop_bodies(merge.as_text()))
    assert all(math.prod(map(int, dims.split(","))) < n * k
               for dims in copied), copied
    assert _kernels(rows) == _kernels(solve) == _kernels(merge) == 0


def test_blocked_unmtr_hb2st_compiles(one_chip):
    """The blocked back-transform on the VMEM chaser's pack at n=8192,
    band 128: its windows of Z start on multiples of the band."""
    from slate_tpu.linalg import bulge
    S, T = N_EIG - 1, N_EIG // BAND_EIG
    back = bulge._apply_bulge_jit._jit.lower(
        _shape(one_chip, S, T, BAND_EIG), _shape(one_chip, S, T),
        _shape(one_chip, N_EIG, N_EIG), band=BAND_EIG, forward=False,
        conj_tau=True).compile()
    # the padded copy of Z and one more of its size, no window's worth
    # a sweep any more
    assert back.memory_analysis().temp_size_in_bytes < 4 * 4 * N_EIG ** 2
    assert "dot" in back.as_text() or "convolution" in back.as_text()


@pytest.mark.parametrize("rows", [128, 256])
def test_single_pass_shears_compile(one_chip, rows):
    """Both single-pass forms of the VMEM chaser's shears on the
    band-128 frame ([128, 256]) and, past one lane tile a vector, at
    band 256: the lane gather (one source vreg a gather, so two gathers
    and a select at 256) and the strided rotate. A form Mosaic refuses
    would demote the whole chase to the XLA wave in silence."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from slate_tpu.internal import band_wave_vmem as bwv
    W = 2 * rows
    assert bwv.shear_form(rows, W, rows - 1) == "single_pass"

    def kern(v_ref, q_ref, s_ref, z_ref):
        s_ref[...] = bwv._shear_rowvec(v_ref[...], rows - 1, rows, W)
        z_ref[...] = bwv._antishear_sum(q_ref[...], rows, W)

    def both(v, q):
        vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
        return pl.pallas_call(
            kern, in_specs=[vmem, vmem], out_specs=[vmem, vmem],
            out_shape=(jax.ShapeDtypeStruct((rows, W), F32),
                       jax.ShapeDtypeStruct((1, W), F32)))(v, q)

    c = _compile(both, _shape(one_chip, 1, W), _shape(one_chip, rows, W))
    assert _kernels(c) == 1


def test_hb2st_vmem_chaser_compiles(one_chip):
    """The whole chaser at band 128 (the frame layout the cell runs,
    single-pass shears in every task body) at a small n: the body is
    the cell's, n only sets the grid."""
    from slate_tpu.internal import band_wave_vmem as bwv
    n = 1024
    assert bwv.vmem_applies(n, BAND_EIG, F32)
    c = bwv._hb2st_vmem_jit.lower(_shape(one_chip, BAND_EIG + 1, n),
                                  band=BAND_EIG, n=n).compile()
    assert _kernels(c) == 1
