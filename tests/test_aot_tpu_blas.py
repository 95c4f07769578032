"""The ``trsm(Side.Left)`` and ``gemm`` forms of the cells' solves,
compiled for the real chip at the cells' size: what a step multiplies,
what crosses chips, and that no program holds a copy of A. (LU and
Cholesky: tests/test_aot_tpu_compile.py; QR and the eigensolver:
tests/test_aot_tpu_qr_eig.py.)

Nothing runs: the TPU compiler installed in the sandbox compiles for a
DESCRIBED ``v5e:2x2`` (no chip attached), which refuses what interpret
mode cannot see — misaligned slices, too much VMEM, a kernel that
cannot be partitioned, a program that does not fit HBM. A kernel is
compiled at its true block; the program around it at the fewest steps
the assertion needs, and at the cell's size where the assertion is about
that size (temporaries, bytes a device). The fixtures (``topo``,
``one_chip``, ``tpu_grid22``) are in tests/conftest.py.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest

import slate_tpu as slate
from tests.conftest import (AOT_H as H, AOT_NB as NB, AOT_W as W,
                            all_reduce_shapes, aot_tiles, widest_product)

F32 = jnp.float32


# -- potrs' second solve: trsm on conj_transpose(L) where it lies ----------


def _trsm_h_by_8(topo, tpu_grid22, shape, trans, lower=True, unit=False):
    """The cells' solve, L (H x H) against 8 right-hand sides in one
    1024-wide tile column a device column, compiled; the grid it is
    for; and the bytes of its two stored operands."""
    from slate_tpu.ops import blas
    grid = (tpu_grid22 if shape == "2x2"
            else slate.Grid(1, 1, devices=[topo.devices[0]]))
    L = slate.TriangularMatrix(
        data=aot_tiles(grid), m=H, n=H, nb=NB, grid=grid,
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
    assert widest_product(text) == NB * W
    # a step's flops as XLA counts them (an inner trip once): under the
    # unmasked half of the column, where the parent's were the column's
    assert c.cost_analysis()["flops"] <= 2 * (mtl // 2) * NB * NB * W


def _assert_a_stays(c, mtl):
    """The 2x2 program for a B of one tile column: A stays where it is
    stored, in the order it is stored in. No all-reduce carries the
    local slots of a tile column of it, nothing gathers it, and the
    program holds no copy of it."""
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
    c, grid, _ = _trsm_h_by_8(topo, tpu_grid22, shape, trans)
    mtl = H // NB // grid.p
    text = c.as_text()
    # the widest is column k of A by X(k,:), [mtl, 1024, 128] (one tile
    # of it by its rows of X, [1024, 128], where A is read tile by
    # tile); the padded tile's was 8x that
    widest = widest_product(text)
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
    from slate_tpu.ops import blas
    grid = (tpu_grid22 if shape == "2x2"
            else slate.Grid(1, 1, devices=[topo.devices[0]]))
    mtl = H // NB // grid.p
    col = jax.ShapeDtypeStruct((grid.p, grid.q, mtl, 1, NB, NB), F32,
                               sharding=grid.sharding())
    A = slate.Matrix(data=aot_tiles(grid), m=H, n=H, nb=NB, grid=grid)
    B = slate.Matrix(data=col, m=H, n=1, nb=NB, grid=grid)
    C = slate.Matrix(data=col, m=H, n=1, nb=NB, grid=grid)
    s = jax.ShapeDtypeStruct((), F32)
    c = blas._gemm_jit.lower(s, A, B, s, C, tier="bf16_6x").compile()
    text = c.as_text()
    assert widest_product(text) <= mtl * NB * W
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
