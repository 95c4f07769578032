"""The LU and Cholesky programs of the main path, compiled for the real
chip: the Pallas LU panel kernels, the one-chip fast-path group, a 2x2
super-step chunk of each factorization, ``getrs``'s pivots, one served
executable. (QR and the eigensolver: tests/test_aot_tpu_qr_eig.py; the
``trsm`` and ``gemm`` forms: tests/test_aot_tpu_blas.py.)

Nothing runs: the TPU compiler installed in the sandbox compiles for a
DESCRIBED ``v5e:2x2`` (no chip attached), which refuses what interpret
mode cannot see — misaligned slices, too much VMEM, a kernel that
cannot be partitioned, a program that does not fit HBM. A kernel is
compiled at its true block; the program around it at the fewest steps
the assertion needs, and at the cell's size where the assertion is about
that size (temporaries, bytes a device). The fixtures (``topo``,
``one_chip``, ``tpu_grid22``) are in tests/conftest.py.
Real widths (h=16384, nb=1024); the n=16384 whole-factorization programs
take minutes and are compiled by hand, not here.
"""

import math
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest

import slate_tpu as slate
from tests.conftest import (AOT_H as H, AOT_NB as NB, AOT_W as W,
                            all_reduce_shapes, aot_compile, aot_kernels,
                            aot_tiles)

F32 = jnp.float32


# -- the production LU panel kernels (internal/panel_plu.py) ---------------


@pytest.mark.parametrize("fold", [True, False], ids=["fold", "nofold"])
def test_plu_subpanel_compiles(one_chip, fold):
    from slate_tpu.internal import panel_plu
    sub = jax.ShapeDtypeStruct((H, W), F32, sharding=one_chip)
    act = jax.ShapeDtypeStruct((H,), F32, sharding=one_chip)
    c = aot_compile(partial(panel_plu.plu_subpanel, fold=fold), sub, act)
    assert aot_kernels(c) >= 3      # transpose in, factor, transpose out


def test_fold_unfold_panelaot_compile(one_chip):
    from slate_tpu.internal import panel_plu
    flat = jax.ShapeDtypeStruct((H, NB), F32, sharding=one_chip)
    folded = jax.ShapeDtypeStruct((8, NB, H // 8), F32, sharding=one_chip)
    assert aot_kernels(aot_compile(panel_plu.fold_panel, flat)) == 1
    assert aot_kernels(aot_compile(panel_plu.unfold_panel, folded)) == 1


def test_plu_call_folded_block_compiles(one_chip):
    from slate_tpu.internal import panel_plu
    pcf = jax.ShapeDtypeStruct((8, NB, H // 8), F32, sharding=one_chip)
    act = jax.ShapeDtypeStruct((8, H // 8), F32, sharding=one_chip)
    sidx = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    assert aot_kernels(aot_compile(panel_plu.plu_call_folded_block,
                             pcf, act, sidx)) == 1


def test_getrf_fast_group_program_compiles_with_the_kernel(topo, one_chip):
    """A small twin of the program slate.gesv runs at n=16384 on one
    chip: layout-pinned, donated, Pallas panels. One group of two panels
    (the in-group trailing update) of four subpanels each (the
    intra-panel solve) on an 8,192-row window: twelve kernels, each at
    its own height, which is what the compile costs. The kernels at the
    cell's height are compiled alone above."""
    from slate_tpu.linalg import getrf
    n, nb = 8192, 512
    a = jax.ShapeDtypeStruct((n, n), F32, sharding=one_chip)
    content = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    info = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    c = getrf._fast_group_program(topo.devices[0]).lower(
        a, content, info, 0, 2, nb, False, True, None).compile()
    assert aot_kernels(c) > 0


# -- one 2x2 super-step chunk of each factorization -------------------------


def _assert_sharded_with_collectives(compiled, whole_bytes):
    text = compiled.as_text()
    assert "all-gather" in text or "all-reduce" in text
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert abs(per_device - whole_bytes // 4) < 2 ** 20, per_device


def test_potrf_chunk_2x2_compiles_sharded(tpu_grid22):
    from slate_tpu.linalg import potrf
    data = aot_tiles(tpu_grid22)
    A = slate.HermitianMatrix(data=data, m=H, n=H, nb=NB, grid=tpu_grid22)
    info0 = jax.ShapeDtypeStruct((), jnp.int32)
    c = potrf._potrf_chunk_jit.lower(A, info0, 0, 2,
                                     tier="bf16_6x").compile()
    _assert_sharded_with_collectives(c, H * H * 4)


# -- the one-chip Cholesky on the stored tiles ------------------------------


def _entry_relayouts(text, elements):
    """``(name, op, operand)`` of every ``copy``, ``transpose`` or
    ``reshape`` of the entry computation whose result has at least
    ``elements`` elements."""
    entry = text[text.index("ENTRY"):]
    found = re.findall(
        r"^\s*(?:ROOT )?(\S+) = \w+\[([\d,]+)\]\S* "
        r"(copy|transpose|reshape)\((\S+?)[,)]", entry, re.M)
    return [(name, op, operand) for name, dims, op, operand in found
            if math.prod(map(int, dims.split(","))) >= elements]


@pytest.mark.parametrize("donate,dtype,n,nb", [
    (True, F32, 4096, NB), (False, F32, 4096, NB),
    (False, jnp.complex64, 2048, 512)], ids=["donated", "kept", "complex"])
def test_potrf_one_chip_factors_the_tiles_where_they_are(topo, donate,
                                                         dtype, n, nb):
    """``_potrf_core`` on one chip, a twin of ``posv_16k_1x1``'s program
    at the cell's nb (four block columns: every kind of trailing window,
    and the tail of the loop where XLA's layout assignment, unpinned,
    turns the whole array column-major and back). The parent brought the
    tiles to a dense [n, n] array and back: five matrix-sized copies and
    a matrix of temporaries at any n (PERF.md section 6, PR 50). The
    tile body moves the matrix once, and only where the caller keeps A:
    an in-place loop on an input that is not donated starts from a copy
    of it. A complex matrix has to compile too: the pin that holds the
    layout is refused on one by the TPU compiler, so it goes unpinned."""
    from slate_tpu.linalg import potrf
    grid = slate.Grid(1, 1, devices=[topo.devices[0]])
    t = n // nb
    data = jax.ShapeDtypeStruct((1, 1, t, t, nb, nb), dtype,
                                sharding=grid.sharding())
    A = slate.HermitianMatrix(data=data, m=n, n=n, nb=nb, grid=grid)
    jit = potrf._potrf_jit_overwrite if donate else potrf._potrf_jit
    c = jit.lower(A, "bf16_6x", depth=0).compile()
    assert aot_kernels(c) == 0
    if dtype != F32:
        return
    moved = _entry_relayouts(c.as_text(), n * n)
    if donate:
        assert moved == []
    else:
        assert [(op, operand[:3]) for _, op, operand in moved] == [
            ("copy", "%A_")], moved
    assert c.memory_analysis().temp_size_in_bytes < n * n * 4 // 4


# -- the one-chip LU between the stored tiles and its dense array ------------


def _entry_fusions(text, elements):
    """``(name, op_name)`` of every fusion of the entry computation
    whose result has at least ``elements`` elements."""
    entry = text[text.index("ENTRY"):]
    found = re.findall(
        r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]+)\]\S* fusion\((.*)$", entry,
        re.M)
    return [(name, "".join(re.findall(r'op_name="([^"]*)"', rest)))
            for name, dims, rest in found
            if math.prod(map(int, dims.split(","))) >= elements]


@pytest.mark.parametrize("n,donate,temp_mib", [
    (4096, False, 106.0),
    pytest.param(8192, False, 360.0, marks=pytest.mark.slow),
    pytest.param(4096, True, 106.0, marks=pytest.mark.slow)],
    ids=["one_group", "two_groups", "one_group_donated"])
def test_getrf_one_chip_carries_the_matrix_once_each_way(topo, n, donate,
                                                         temp_mib):
    """``_getrf_fast_core`` at the cell's nb, A kept, as ``slate.gesv``
    calls it on one chip. The parent made four matrix-sized passes
    between the stored tiles and the dense working array (the
    transposition [kt, nb, kt, nb] and a second copy to the (8, 128)
    tiling of [n, n], each way: ``copy.62``, ``copy.148``,
    ``reshape.16`` and ``copy_bitcast_fusion`` at n = 4096) and, from
    two groups up, a fifth that ``jnp.take``'s fill mode spent selecting
    between the gathered rows and NaN (``broadcast_select_fusion``,
    PERF.md section 6, PR 53); each later group's gather was fed a
    ``slice`` of the window copied out first. Now: one ``copy`` in, one
    fusion out, the first group's gather feeding the array it becomes,
    and the parent's temporaries at n = 4096 (105.97 MiB). At n = 8192
    ``temp_size_in_bytes`` reads 356.1 MiB where the parent's read
    309.4 (its buffer assignment: 966 MiB in all against 943; at the
    cell's n = 16384 3.55 GiB against the parent's 3.68): the working
    array now lives in the temporaries and the first dense array in the
    output buffer, where the parent's odd number of copies had them the
    other way round. Donating A changes none of it (the dense array
    is another shape: nothing to factor in place). The two-group case
    takes 100-250 s to compile: by hand, like the donated twin."""
    from slate_tpu.linalg import getrf
    grid = slate.Grid(1, 1, devices=[topo.devices[0]])
    t = n // NB
    data = jax.ShapeDtypeStruct((1, 1, t, t, NB, NB), F32,
                                sharding=grid.sharding())
    A = slate.Matrix(data=data, m=n, n=n, nb=NB, grid=grid)
    jit = (getrf._getrf_fast_jit_overwrite if donate
           else getrf._getrf_fast_jit)
    c = jit.lower(A, interpret=False, want_ipiv=False, fold=True,
                  tier="bf16_6x").compile()
    text = c.as_text()
    assert aot_kernels(c) > 0
    moved = _entry_relayouts(text, n * n)
    assert [op for _, op, _ in moved] == ["copy"], moved
    fusions = _entry_fusions(text, n * n)
    carried = [name for name, op_name in fusions
               if "pivot_gather" not in op_name and "panel" not in op_name
               and "trailing" not in op_name]
    assert carried == ["copy_bitcast_fusion"], fusions
    assert not [f for f in fusions if f[0].startswith("broadcast_select")
                or f[1].endswith("jit(_take)/select_n")], fusions
    # a later group's gather indexes the whole array: no window
    # ``a[done:]`` (every column of the rows below) copied out first
    sliced = re.findall(r"= \w+\[(\d+),(\d+)\]\S* slice\(",
                        text[text.index("ENTRY"):])
    assert not [d for d in sliced if int(d[1]) == n], sliced
    assert c.memory_analysis().temp_size_in_bytes <= temp_mib * 2 ** 20


def _getrf_chunk(grid, k0):
    """The chunk program of block columns [k0, k0 + 2), compiled."""
    from slate_tpu.linalg import getrf
    A = slate.Matrix(data=aot_tiles(grid), m=H, n=H, nb=NB, grid=grid)
    piv0 = jax.ShapeDtypeStruct((H // NB, NB), jnp.int32)
    info0 = jax.ShapeDtypeStruct((), jnp.int32)
    return getrf._getrf_chunk_jit.lower(A, piv0, info0, k0, 2,
                                        tier="bf16_6x").compile()


def _all_gathers(text) -> int:
    return text.count(" all-gather(") + text.count(" all-gather-start(")


def _all_gather_shapes(text) -> set:
    """(dtype, dims) of every all-gather's result in an optimized HLO
    text (an async one appears once in each fusion it is split over)."""
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
