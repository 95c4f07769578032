"""The one-chip LU fast path (``getrf._getrf_fast_core``: Pallas panel
kernels pivoting by index, compaction groups, the folded layout, the
dense donated entry) through the public API on the CPU, kernels in
interpret mode. An interpreted kernel call costs with the height of its
window and a panel makes ``nb / 128`` of them, so each test runs the
smallest (n, nb) that reaches the (kt, group, fold) branch it names and
asserts that it did. The tiled and grid LU: tests/test_getrf.py."""

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu.types import Op
from tests.conftest import rand
from tests.test_getrf import lu_parts, perm_from_piv


def _count_folds(monkeypatch):
    """``[n]``: how many times ``panel_plu.fold_panel`` is called from
    here on (at trace time: once a panel that takes the folded layout)."""
    from slate_tpu.internal import panel_plu
    calls, orig = [0], panel_plu.fold_panel

    def counting(*args, **kw):
        calls[0] += 1
        return orig(*args, **kw)

    monkeypatch.setattr(panel_plu, "fold_panel", counting)
    return calls


def test_getrf_fast_path(grid24, monkeypatch):
    """The no-row-movement fast LU (Pallas panel kernel, pivoting by
    index — internal/panel_plu.py) through the public API on CPU via
    interpret mode. Reference parity target: internal_getrf.cc panel +
    swap semantics, LAPACK ipiv convention."""
    import jax
    monkeypatch.setenv("SLATE_LU_FAST", "1")
    from slate_tpu import Grid
    g1 = Grid(1, 1, devices=jax.devices()[:1])
    n, nb = 384, 128
    a = rand(n, n, seed=9).astype(np.float32)
    a[0, 0] = 0.0                      # force a nontrivial pivot
    A = st.Matrix.from_dense(a, nb=nb, grid=g1)
    LU, piv, info = st.getrf(A)
    assert int(info) == 0
    lu = np.asarray(LU.to_dense())
    l, u = lu_parts(lu)
    perm = perm_from_piv(piv, n)
    err = np.linalg.norm(a[perm] - l @ u) / (n * np.linalg.norm(a))
    assert err < 1e-5
    assert np.abs(l).max() <= 1.0 + 1e-5   # partial-pivoting bound
    # solve through getrs with the returned LAPACK-style pivots
    b = rand(n, 2, seed=10).astype(np.float32)
    B = st.Matrix.from_dense(b, nb=nb, grid=g1)
    X = st.getrs(LU, piv, B)
    x = np.asarray(X.to_dense())
    r = np.linalg.norm(a @ x - b) / (np.linalg.norm(a) * np.linalg.norm(x))
    assert r < 1e-4


def test_getrf_fast_path_nb256_multigroup(grid24, monkeypatch):
    """Fast-path coverage at nb=256 (sb=2: the intra-panel ubuf /
    triangular-solve branch runs) and kt=6 (two compaction groups: the
    cross-group permutation of a[done:, :done] runs) — the auto-on TPU
    configuration's structure at test scale (ADVICE r3)."""
    import jax
    monkeypatch.setenv("SLATE_LU_FAST", "1")
    from slate_tpu import Grid
    g1 = Grid(1, 1, devices=jax.devices()[:1])
    n, nb = 1536, 256
    a = rand(n, n, seed=21).astype(np.float32)
    A = st.Matrix.from_dense(a, nb=nb, grid=g1)
    LU, piv, info = st.getrf(A)
    assert int(info) == 0
    lu = np.asarray(LU.to_dense())
    l, u = lu_parts(lu)
    perm = perm_from_piv(piv, n)
    err = np.linalg.norm(a[perm] - l @ u) / (n * np.linalg.norm(a))
    assert err < 1e-5
    assert np.abs(l).max() <= 1.0 + 1e-5


def test_plu_subpanel_folded_twin(monkeypatch):
    """The folded-layout panel kernel ([8, W, h/8] storage, round-4
    sweep rework) matches the flat [W, h] kernel: same pivots, same
    active mask, same info; values agree to last-ULP association
    differences (the strip-end contraction sums 8 folded segments
    instead of one flat axis — a summation-order change only)."""
    from slate_tpu.internal import panel_plu as pp
    rng = np.random.default_rng(5)
    for h, kill in [(1024, 0), (2048, 3)]:
        sub = np.asarray(rng.standard_normal((h, pp.W)), np.float32)
        act = np.ones(h, np.float32)
        act[:kill] = 0.0               # some rows already eliminated
        monkeypatch.setenv("SLATE_LU_FOLD", "0")
        o1, p1, a1, i1 = pp.plu_subpanel(
            np.asarray(sub), np.asarray(act), interpret=True)
        monkeypatch.setenv("SLATE_LU_FOLD", "1")
        o2, p2, a2, i2 = pp.plu_subpanel(
            np.asarray(sub), np.asarray(act), interpret=True)
        assert np.array_equal(np.asarray(p1), np.asarray(p2))
        assert np.array_equal(np.asarray(a1), np.asarray(a2))
        # cancellation in the 16 compounded strip updates amplifies
        # the reorder noise on ~0.2% of (small) entries; both kernels
        # measure identical 8.7e-9 backward error vs L·U reconstruction
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=0, atol=1e-4)
        assert int(i1) == int(i2)


def test_getrf_fast_path_folded_group(grid24, monkeypatch):
    """The full fast path with the folded kernel active (h a multiple
    of 1024) and the round-4 group-blocked trailing: per-panel updates
    stay inside the compaction group; the cross-group trailing is one
    exact-height gemm after a blocked forward substitution builds the
    U block rows."""
    import jax
    monkeypatch.setenv("SLATE_LU_FAST", "1")
    monkeypatch.setenv("SLATE_LU_FOLD", "1")
    from slate_tpu.linalg import getrf as getrf_mod
    monkeypatch.setattr(getrf_mod, "_FAST_GROUP", 1)
    folds = _count_folds(monkeypatch)
    from slate_tpu import Grid
    g1 = Grid(1, 1, devices=jax.devices()[:1])
    # kt=2, group=1: the first group's window is 1024 rows (folded),
    # then the Ug leg; the second's 512 are not (four subpanel kernels
    # a panel: an interpreted kernel costs with its window's height)
    n, nb = 1024, 512
    a = rand(n, n, seed=33).astype(np.float32)
    A = st.Matrix.from_dense(a, nb=nb, grid=g1)
    LU, piv, info = st.getrf(A)
    assert folds == [1]                # group 0's panel, not group 1's
    assert int(info) == 0
    lu = np.asarray(LU.to_dense())
    l, u = lu_parts(lu)
    perm = perm_from_piv(piv, n)
    err = np.linalg.norm(a[perm] - l @ u) / (n * np.linalg.norm(a))
    assert err < 1e-5
    assert np.abs(l).max() <= 1.0 + 1e-5


def test_getrf_fast_path_folded_multipanel_group(grid24, monkeypatch):
    """Folded panels inside a MULTI-panel compaction group (gsz >= 2,
    default _FAST_GROUP): the ordg/upend interplay and the p < kk
    blocked-substitution leg run with the folded kernel active —
    round 4 only covered the folded branch with _FAST_GROUP
    monkeypatched to 1 (ADVICE r4)."""
    import jax
    monkeypatch.setenv("SLATE_LU_FAST", "1")
    monkeypatch.setenv("SLATE_LU_FOLD", "1")
    from slate_tpu.linalg import getrf as getrf_mod
    assert getrf_mod._FAST_GROUP >= 4     # default grouping, no patch
    folds = _count_folds(monkeypatch)
    from slate_tpu import Grid
    g1 = Grid(1, 1, devices=jax.devices()[:1])
    # kt=4 → one group, gsz=4; hw = 1024 is folded; two subpanels a
    # panel, so the intra-panel solve runs too (eight kernels in all)
    n, nb = 1024, 256
    a = rand(n, n, seed=35).astype(np.float32)
    A = st.Matrix.from_dense(a, nb=nb, grid=g1)
    LU, piv, info = st.getrf(A)
    assert folds == [4]                # every panel of the one group
    assert int(info) == 0
    lu = np.asarray(LU.to_dense())
    l, u = lu_parts(lu)
    perm = perm_from_piv(piv, n)
    err = np.linalg.norm(a[perm] - l @ u) / (n * np.linalg.norm(a))
    assert err < 1e-5
    assert np.abs(l).max() <= 1.0 + 1e-5


def test_fast_path_compaction_chunked(grid24, monkeypatch):
    """The column-chunked in-place compaction (the n >
    _COMPACT_TAKE_MAX_N leg that admits the 45k-64k class) produces
    the same factorization as the one-shot full-window take: force it
    at test scale by dropping the threshold and shrinking the chunk
    so multiple chunks run."""
    import jax
    monkeypatch.setenv("SLATE_LU_FAST", "1")
    from slate_tpu.linalg import getrf as getrf_mod
    from slate_tpu import Grid
    g1 = Grid(1, 1, devices=jax.devices()[:1])
    n, nb = 512, 128
    a = rand(n, n, seed=36).astype(np.float32)
    A = st.Matrix.from_dense(a, nb=nb, grid=g1)
    LU0, piv0, info0 = st.getrf(A)          # take leg (n <= threshold)
    # the constants are baked at trace time: drop the jit caches so
    # the patched values actually retrace (and again after, so traces
    # with patched constants cannot leak into other tests)
    from slate_tpu.cache import clear_in_process
    getrf_mod._getrf_fast_jit.clear_cache()
    clear_in_process("getrf")
    monkeypatch.setattr(getrf_mod, "_COMPACT_TAKE_MAX_N", 0)
    monkeypatch.setattr(getrf_mod, "_COMPACT_CB", 128)
    try:
        LU1, piv1, info1 = st.getrf(A)      # chunked leg, 4 chunks
    finally:
        getrf_mod._getrf_fast_jit.clear_cache()
        clear_in_process("getrf")
    assert np.array_equal(np.asarray(piv0), np.asarray(piv1))
    np.testing.assert_allclose(np.asarray(LU0.to_dense()),
                               np.asarray(LU1.to_dense()),
                               rtol=0, atol=1e-6)
    assert int(info0) == int(info1) == 0


def test_gesv_fast_pivot_order(grid24, monkeypatch):
    """gesv through the fast path: the solve consumes the elimination
    order directly (PivotOrder — one gather, no swap simulation) and
    the returned LAPACK ipiv comes from the host chain conversion
    (runtime.order_to_ipiv), matching the device simulation exactly."""
    import jax
    monkeypatch.setenv("SLATE_LU_FAST", "1")
    from slate_tpu import Grid
    from slate_tpu.linalg.getrf import (_getrf_fast_jit, PivotOrder,
                                        pivot_order_to_ipiv)
    g1 = Grid(1, 1, devices=jax.devices()[:1])
    n, nb = 384, 128
    a = rand(n, n, seed=22).astype(np.float32)
    A = st.Matrix.from_dense(a, nb=nb, grid=g1)
    _, piv_dev, _ = _getrf_fast_jit(A, interpret=True, want_ipiv=True)
    _, order, _ = _getrf_fast_jit(A, interpret=True, want_ipiv=False)
    assert np.array_equal(np.asarray(pivot_order_to_ipiv(order)),
                          np.asarray(piv_dev))
    b = rand(n, 3, seed=23).astype(np.float32)
    B = st.Matrix.from_dense(b, nb=nb, grid=g1)
    X, LU, piv, info = st.gesv(A, B)
    assert int(info) == 0
    assert np.array_equal(np.asarray(piv), np.asarray(piv_dev))
    x = np.asarray(X.to_dense())
    r = np.linalg.norm(a @ x - b) / (np.linalg.norm(a) * np.linalg.norm(x))
    assert r < 1e-4
    # transposed solve applies the inverse permutation (scatter side)
    Xt = st.getrs(LU, PivotOrder(order), B, Op.Trans)
    xt = np.asarray(Xt.to_dense())
    rt_ = np.linalg.norm(a.T @ xt - b) / (np.linalg.norm(a)
                                          * np.linalg.norm(xt))
    assert rt_ < 1e-4


def test_plu_panel_tournament(monkeypatch):
    """The CALU tournament branch of plu_panel (panel taller than
    H_MAX), exercised at small n by shrinking H_MAX (ADVICE r3: the
    production branch for 16k < n <= 32k panels was untested).
    Checks the factorization invariants the driver relies on:
    pivot rows carry the LU of the winner rows (L11·U11 = A[piv]) and
    every still-active row holds multipliers out[r]·U11 = A[r]."""
    from slate_tpu.internal import panel_plu
    monkeypatch.setattr(panel_plu, "H_MAX", 256)
    import jax.numpy as jnp
    # h/H_MAX = 2 chunks -> 256 winner rows = one final-round subpanel
    h, w = 512, 128
    a = rand(h, w, seed=24).astype(np.float32)
    sub = jnp.asarray(a)
    act = jnp.ones(h, jnp.float32)
    out, piv, act_new, info = panel_plu.plu_panel(sub, act,
                                                  interpret=True)
    out = np.asarray(out)
    piv = np.asarray(piv)
    act_new = np.asarray(act_new)
    assert int(info) == 0
    assert len(np.unique(piv)) == w            # w distinct pivot rows
    assert np.array_equal(np.where(act_new == 0)[0], np.sort(piv))
    lu_rows = out[piv]                         # [w, w] LU in elim order
    l11 = np.tril(lu_rows, -1) + np.eye(w, dtype=np.float32)
    u11 = np.triu(lu_rows)
    err = (np.linalg.norm(a[piv] - l11 @ u11)
           / (w * np.linalg.norm(a[piv])))
    assert err < 1e-5
    active = act_new > 0
    rec = out[active] @ u11                    # L·U11 = original rows
    err2 = (np.linalg.norm(a[active] - rec)
            / (w * np.linalg.norm(a[active])))
    assert err2 < 1e-5


def test_plu_panel_tournament_zero_pivot(monkeypatch):
    """CALU singular-panel semantics (ADVICE r3): a column that is
    entirely zero among the candidates must produce ZERO multipliers
    in the active rows (matching the in-VMEM kernel and LAPACK), with
    info counting the zero pivot."""
    from slate_tpu.internal import panel_plu
    monkeypatch.setattr(panel_plu, "H_MAX", 256)
    import jax.numpy as jnp
    h, w = 512, 128
    a = rand(h, w, seed=25).astype(np.float32)
    a[:, 5] = 0.0                              # exactly singular column
    sub = jnp.asarray(a)
    out, piv, act_new, info = panel_plu.plu_panel(
        sub, jnp.ones(h, jnp.float32), interpret=True)
    assert int(info) >= 1
    out = np.asarray(out)
    active = np.asarray(act_new) > 0
    # the multiplier column of the zero pivot is zero in active rows
    lu_rows = out[np.asarray(piv)]
    zcol = np.where(np.diag(np.triu(lu_rows)) == 0.0)[0]
    assert zcol.size >= 1
    assert np.all(out[active][:, zcol] == 0.0)


def _interpreted_groups(monkeypatch):
    """``getrf_dense_inplace``'s per-group program with its kernels in
    interpret mode (the real one pins TPU layouts), one jit a group."""
    import jax
    from slate_tpu.linalg import getrf as G
    group = jax.jit(G._getrf_fast_group_core,
                    static_argnums=(3, 4, 5, 6, 7, 8))
    monkeypatch.setattr(
        G, "_getrf_fast_group_jit",
        lambda a, c, i, g0, gsz, nb, interpret, fold=True, tier=None:
        group(a, c, i, g0, gsz, nb, True, fold, tier))


def test_getrf_dense_inplace(grid24, monkeypatch):
    """Dense donated LU entry (the 45k-class path, VERDICT r3 #3) —
    same pivots/factor as the tiled fast path, no tile conversion."""
    import jax.numpy as jnp
    _interpreted_groups(monkeypatch)
    n, nb = 768, 128
    a = rand(n, n, seed=51).astype(np.float32)
    lu, piv, info = st.getrf_dense_inplace(jnp.asarray(a), nb=nb)
    assert int(info) == 0
    lu = np.asarray(lu)
    l, u = lu_parts(lu)
    perm = perm_from_piv(piv, n)
    err = np.linalg.norm(a[perm] - l @ u) / (n * np.linalg.norm(a))
    assert err < 1e-5
    assert np.abs(l).max() <= 1.0 + 1e-5


def _parent_spelling(A):
    """PR 53's parent, spelled out around the same group body: the dense
    working array by ``tiles_to_dense`` (one transposition of the whole
    tile array, which the TPU compiler makes in two copies), the groups,
    the tiles back by ``dense_to_tiles``. Returns (LU tiles, elimination
    order [kt, nb], info)."""
    import jax.numpy as jnp
    from slate_tpu.linalg import getrf as G
    from slate_tpu.matrix import dense_to_tiles, tiles_to_dense
    n, nb = A.n, A.nb
    kt = n // nb
    a = tiles_to_dense(A.data[0, 0], n, n)
    content = jnp.arange(n, dtype=jnp.int32)
    info = jnp.zeros((), jnp.int32)
    order = []
    for g0 in range(0, kt, G._FAST_GROUP):
        gsz = min(G._FAST_GROUP, kt - g0)
        a, content, o_g, info = G._getrf_fast_group_core(
            a, content, info, g0, gsz, nb, True)
        order.append(o_g)
    return (dense_to_tiles(a, nb, kt, kt),
            jnp.concatenate(order).reshape(kt, nb), info)


def _one_chip_matrix(n, nb, seed):
    import jax
    from slate_tpu import Grid
    g1 = Grid(1, 1, devices=jax.devices()[:1])
    a = rand(n, n, seed=seed).astype(np.float32)
    a[0, 0] = 0.0                      # the first pivot is not row 0
    return a, st.Matrix.from_dense(a, nb=nb, grid=g1)


@pytest.mark.parametrize("n,nb", [(256, 128), (640, 128)],
                         ids=["two_panels", "two_groups_ragged"])
def test_fast_core_container_passes_keep_every_bit(n, nb):
    """``_getrf_fast_core`` brings the stored tiles to its dense working
    array and back with the 8 rows of a sublane group as an axis of
    their own (one copy each way on the chip where the plain
    transposition was two: tests/test_aot_tpu_compile.py counts them).
    Only the spelling moved: the factor, the elimination order and info
    are the parent's bit for bit, at n = 2 nb (one group) and at kt = 5
    (a group of four panels and a ragged one of one: the second group's
    window gather and write-back run)."""
    import jax
    from slate_tpu.linalg.getrf import _getrf_fast_jit
    _, A = _one_chip_matrix(n, nb, seed=53)
    lu, order, info = _getrf_fast_jit(A, interpret=True, want_ipiv=False)
    lu0, order0, info0 = jax.jit(_parent_spelling)(A)
    assert lu.shape == (1, 1, n // nb, n // nb, nb, nb)
    assert np.array_equal(np.asarray(lu[0, 0]), np.asarray(lu0))
    assert np.array_equal(np.asarray(order), np.asarray(order0))
    assert sorted(np.asarray(order).ravel()) == list(range(n))
    assert int(info) == int(info0) == 0


def test_getrf_dense_inplace_matches_the_tiled_entry(monkeypatch):
    """The donated dense entry shares the group body and has no tiles to
    convert: at kt = 5 its factor is the tiled entry's element for
    element, its LAPACK pivots the tiled entry's order converted."""
    import jax.numpy as jnp
    from slate_tpu.linalg import getrf as G
    from slate_tpu.matrix import tiles_to_dense
    _interpreted_groups(monkeypatch)
    n, nb = 640, 128
    a, A = _one_chip_matrix(n, nb, seed=54)
    lu, order, info = G._getrf_fast_jit(A, interpret=True, want_ipiv=False)
    lud, pivd, infod = st.getrf_dense_inplace(jnp.asarray(a), nb=nb)
    assert np.array_equal(np.asarray(lud),
                          np.asarray(tiles_to_dense(lu[0, 0], n, n)))
    assert np.array_equal(np.asarray(pivd),
                          np.asarray(G.pivot_order_to_ipiv(order)))
    assert int(info) == int(infod) == 0

