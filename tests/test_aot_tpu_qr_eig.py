"""The QR and eigensolver programs of the main path, compiled for the
real chip: the Householder panel kernel, the two programs of the
``gels`` cell, a level of ``stedc``'s tree, the blocked back-transform
and the VMEM bulge chaser with its single-pass shears. (LU and Cholesky:
tests/test_aot_tpu_compile.py; BLAS: tests/test_aot_tpu_blas.py.)

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
from tests.conftest import (AOT_H as H, AOT_W as W, aot_compile,
                            aot_kernels)

F32 = jnp.float32


def _loop_bodies(hlo: str) -> str:
    """The text of the computations that a ``while`` of ``hlo`` runs
    as its body."""
    bodies = set(re.findall(r"\bbody=(%?[\w.\-]+)", hlo))
    return "\n".join(c for c in hlo.split("\n\n")
                     if c.lstrip().split(" ", 1)[0] in bodies)


# -- the Householder panel geqrf turns on by default on the chip -----------


def test_qr_panel_compiles(one_chip):
    """The panel kernel at its true block, a [16384, 128] subpanel, in
    the panel the ``gels`` cell factors first ([16384, 256], nb = 256:
    a kernel call a subpanel, ``nb // W`` of them; no cell has a wider
    panel)."""
    from slate_tpu.internal import panel_qr
    nb = 256
    pan = jax.ShapeDtypeStruct((H, nb), F32, sharding=one_chip)
    assert aot_kernels(aot_compile(panel_qr.qr_panel_blocked, pan)) >= nb // W


@pytest.mark.parametrize("program", ["geqrf", "unmqr"])
def test_gels_cell_programs_compile_with_the_panel_kernel(topo, program):
    """The two programs of ``slate.gels(MethodGels.Geqrf)`` at the
    benchmark cell's shape (m=16384, n=1024, nb=256: PR 44): the
    exact-shape QR with its panels in the Pallas kernel at the tallest
    height it takes, and ``unmqr`` on the 8 right-hand sides. At the
    cell's size, a case a program: the QR's temporaries are the cell's
    (2 MiB at four panels; 18 at two, where XLA fuses otherwise), and
    its eight kernels, each at a height of its own, are 40 s of
    Mosaic."""
    from slate_tpu.linalg import geqrf
    m, n, nb, nrhs = H, 1024, 256, 8
    g = slate.Grid(1, 1, devices=[topo.devices[0]])

    def tiled(rows, cols):
        data = jax.ShapeDtypeStruct(
            (1, 1, rows // nb, -(-cols // nb), nb, nb), F32,
            sharding=g.sharding())
        return slate.Matrix(data=data, m=rows, n=cols, nb=nb, grid=g)

    A, B = tiled(m, n), tiled(m, nrhs)
    if program == "unmqr":
        T = jax.ShapeDtypeStruct((n // nb, nb, nb), F32)
        assert aot_kernels(
            geqrf._unmqr_jit.lower(A, T, B, False).compile()) == 0
        return
    assert geqrf._panel_form(A, "tpu") == "pallas"
    c = geqrf._geqrf_fast_jit.lower(A, panel_mode="tpu",
                                    tier="bf16_6x").compile()
    assert aot_kernels(c) == (n // nb) * (nb // W)  # a call a subpanel
    assert c.memory_analysis().temp_size_in_bytes < 16 * 2 ** 20


def test_ge2tb_exact_body_compiles_a_loop_a_stage(topo):
    """The one-chip ``_ge2tb_jit`` (PR 49) at a reduced exact shape,
    [3072, 2048] at nb = 128: 16 tile columns, so two stages (loops of
    8 and 7 steps) and the last QR panel, 31 panels in all. The module
    keeps the name the benchmark's ``svd_band_reduce_s`` reads; its
    panels are XLA's geqrf, so it holds no Mosaic call; and no loop
    body copies or selects an array of its window's size (the SPMD
    body copies and selects all of A under a mask, every step)."""
    from slate_tpu.linalg import ge2tb as g2
    m, n, nb = 3072, 2048, 128
    g = slate.Grid(1, 1, devices=[topo.devices[0]])
    data = jax.ShapeDtypeStruct((1, 1, m // nb, n // nb, nb, nb), F32,
                                sharding=g.sharding())
    A = slate.Matrix(data=data, m=m, n=n, nb=nb, grid=g)
    assert g2._program(A) == "exact"
    low = g2._ge2tb_jit.lower(A, "bf16_6x")
    assert "module @jit__ge2tb_jit" in low.as_text()
    c = low.compile()
    assert aot_kernels(c) == 0
    assert -(-(n // nb - 1) // g2.STAGE) == 2
    bodies = _loop_bodies(c.as_text())
    smallest_window = (m - g2.STAGE * nb) * (n - g2.STAGE * nb)
    moved = re.findall(r"= \w+\[([\d,]+)\]\S* (?:copy|select)\(", bodies)
    assert all(math.prod(map(int, dims.split(","))) < smallest_window
               for dims in moved), moved


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
    assert aot_kernels(rows) == aot_kernels(solve) == aot_kernels(merge) == 0


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
def test_single_pass_shearsaot_compile(one_chip, rows):
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

    c = aot_compile(both, _shape(one_chip, 1, W), _shape(one_chip, rows, W))
    assert aot_kernels(c) == 1


def test_hb2st_vmem_chaser_compiles(one_chip):
    """The whole chaser at band 128 (the frame layout the cell runs,
    single-pass shears in every task body) at a small n: the body is
    the cell's, n only sets the grid."""
    from slate_tpu.internal import band_wave_vmem as bwv
    n = 1024
    assert bwv.vmem_applies(n, BAND_EIG, F32)
    c = bwv._hb2st_vmem_jit.lower(_shape(one_chip, BAND_EIG + 1, n),
                                  band=BAND_EIG, n=n).compile()
    assert aot_kernels(c) == 1


def test_tb2bd_vmem_chaser_compiles(one_chip):
    """The bidiagonal twin at band 128 (the frame layout the
    ``gesvd_12288x8192_vec_1x1`` cell runs, PR 48) at a small n: the
    body is the cell's, n only sets the grid (at n=8192 the same
    compile is two minutes). And the program that cuts U_B and V_B out
    of the Golub-Kahan Z at the cell's order, 2n = 16384: its
    temporaries are no more than Z itself (the reversed positive half
    and its split; a strided form that holds a quarter of that was 70
    times slower on the chip, PR 48)."""
    from slate_tpu.internal import band_wave_vmem_bd as bd
    from slate_tpu.linalg import bulge
    n = 1024
    assert bd.vmem_applies_bd(n, BAND_EIG, F32)
    assert bd.vmem_applies_bd(N_EIG, BAND_EIG, F32)
    c = bd._tb2bd_vmem_jit.lower(_shape(one_chip, BAND_EIG + 1, n),
                                 band=BAND_EIG, n=n).compile()
    assert aot_kernels(c) == 1
    halves = bulge._gk_halves_jit._jit.lower(
        _shape(one_chip, 2 * N_EIG, 2 * N_EIG), n=N_EIG).compile()
    assert halves.memory_analysis().temp_size_in_bytes \
        <= 1.01 * 4 * (2 * N_EIG) ** 2

