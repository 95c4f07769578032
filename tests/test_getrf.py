"""LU tier-2 tests (reference test/test_getrf.cc / test_gesv.cc:
‖PA − LU‖ backward error + solve residuals, pivoted and unpivoted).
The one-chip fast path (Pallas panels in interpret mode) is in
tests/test_getrf_fast.py."""

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu.types import Op
from tests.conftest import rand


def lu_parts(lu):
    l = np.tril(lu, -1) + np.eye(lu.shape[0])
    u = np.triu(lu)
    return l, u


def perm_from_piv(piv, m):
    """Apply LAPACK-style sequential swaps to identity. Pivot entries
    for zero-padded columns (j >= m) are identity self-swaps in the
    padded row space; simulate there and crop."""
    piv = np.asarray(piv).reshape(-1)
    size = max(m, int(piv.max()) + 1, piv.size)
    perm = np.arange(size)
    for j, pv in enumerate(piv):
        perm[[j, pv]] = perm[[pv, j]]
    return perm[:m]


@pytest.mark.parametrize("n,nb", [(32, 8), (29, 8), (24, 4)])
def test_getrf_backward_error(grid24, n, nb):
    a = rand(n, n, seed=1)
    A = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    LU, piv, info = st.getrf(A)
    assert int(info) == 0
    lu = np.asarray(LU.to_dense())
    l, u = lu_parts(lu)
    perm = perm_from_piv(piv, n)
    pa = a[perm]
    err = np.linalg.norm(pa - l @ u) / (n * np.linalg.norm(a))
    assert err < 1e-13


def test_getrf_pivoting_matches_lapack_growth(grid24):
    # a matrix that needs pivoting: zero diagonal block
    n = 16
    a = rand(n, n, seed=2)
    a[0, 0] = 0.0
    A = st.Matrix.from_dense(a, nb=8, grid=grid24)
    LU, piv, info = st.getrf(A)
    assert int(info) == 0
    lu = np.asarray(LU.to_dense())
    l, u = lu_parts(lu)
    perm = perm_from_piv(piv, n)
    err = np.linalg.norm(a[perm] - l @ u) / np.linalg.norm(a)
    assert err < 1e-13
    assert np.abs(l).max() <= 1.0 + 1e-12  # partial pivoting bound


@pytest.mark.parametrize("dt", [np.float64, np.complex128])
def test_gesv(grid24, dt):
    n, nrhs = 24, 3
    a = rand(n, n, dt, 3)
    b = rand(n, nrhs, dt, 4)
    A = st.Matrix.from_dense(a, nb=8, grid=grid24)
    B = st.Matrix.from_dense(b, nb=8, grid=grid24)
    X, LU, piv, info = st.gesv(A, B)
    assert int(info) == 0
    res = np.linalg.norm(a @ np.asarray(X.to_dense()) - b) \
        / np.linalg.norm(b)
    assert res < 1e-11


@pytest.mark.parametrize("trans", [Op.Trans, Op.ConjTrans])
def test_getrs_trans(grid24, trans):
    n = 16
    dt = np.complex128 if trans == Op.ConjTrans else np.float64
    a = rand(n, n, dt, 5)
    b = rand(n, 2, dt, 6)
    A = st.Matrix.from_dense(a, nb=8, grid=grid24)
    B = st.Matrix.from_dense(b, nb=8, grid=grid24)
    LU, piv, info = st.getrf(A)
    X = st.getrs(LU, piv, B, trans)
    at = a.T if trans == Op.Trans else np.conj(a.T)
    res = np.linalg.norm(at @ np.asarray(X.to_dense()) - b) \
        / np.linalg.norm(b)
    assert res < 1e-10


def test_getrf_nopiv(grid24):
    n = 24
    a = rand(n, n, seed=7) + n * np.eye(n)   # diagonally dominant
    A = st.Matrix.from_dense(a, nb=8, grid=grid24)
    LU, info = st.getrf_nopiv(A)
    assert int(info) == 0
    lu = np.asarray(LU.to_dense())
    l, u = lu_parts(lu)
    err = np.linalg.norm(a - l @ u) / (n * np.linalg.norm(a))
    assert err < 1e-13


def test_getri(grid24):
    n = 16
    a = rand(n, n, seed=8) + n * np.eye(n)
    A = st.Matrix.from_dense(a, nb=8, grid=grid24)
    LU, piv, info = st.getrf(A)
    Ainv = st.getri(LU, piv)
    np.testing.assert_allclose(np.asarray(Ainv.to_dense()),
                               np.linalg.inv(a), rtol=1e-9, atol=1e-9)


def test_trtri(grid24):
    n = 16
    a = rand(n, n, seed=9) + n * np.eye(n)
    from slate_tpu.types import Uplo
    A = st.TriangularMatrix.from_dense(a, nb=8, grid=grid24,
                                       uplo=Uplo.Lower)
    Ainv = st.trtri(A)
    got = np.tril(np.asarray(Ainv.to_dense()))
    np.testing.assert_allclose(got, np.linalg.inv(np.tril(a)),
                               rtol=1e-10, atol=1e-10)


def test_gbsv(grid24):
    n, kl, ku = 24, 2, 3
    a = rand(n, n, seed=10)
    band = np.zeros_like(a)
    for i in range(n):
        for j in range(n):
            if -kl <= j - i <= ku:
                band[i, j] = a[i, j]
    band += n * np.eye(n)
    b = rand(n, 2, seed=11)
    Ab = st.BandMatrix.from_dense(band, nb=8, grid=grid24, kl=kl, ku=ku)
    Bm = st.Matrix.from_dense(b, nb=8, grid=grid24)
    X, LU, piv, info = st.gbsv(Ab, Bm)
    assert int(info) == 0
    res = np.linalg.norm(band @ np.asarray(X.to_dense()) - b) \
        / np.linalg.norm(b)
    assert res < 1e-11


def test_gecondest(grid24):
    n = 16
    a = rand(n, n, seed=12) + n * np.eye(n)
    A = st.Matrix.from_dense(a, nb=8, grid=grid24)
    LU, piv, info = st.getrf(A)
    anorm = float(st.norm(st.Norm.One, A))
    rcond = st.gecondest(st.Norm.One, LU, piv, anorm)
    true_rcond = 1.0 / (np.linalg.norm(a, 1)
                        * np.linalg.norm(np.linalg.inv(a), 1))
    # estimator is within a modest factor of the truth
    assert true_rcond / 10 < rcond < true_rcond * 10


def test_hesv(grid24):
    n = 20
    a = rand(n, n, seed=13)
    a = (a + a.T) / 2           # symmetric indefinite
    b = rand(n, 2, seed=14)
    A = st.HermitianMatrix.from_dense(a, nb=8, grid=grid24)
    B = st.Matrix.from_dense(b, nb=8, grid=grid24)
    X, factors, info = st.hesv(A, B)
    assert int(info) == 0
    res = np.linalg.norm(a @ np.asarray(X.to_dense()) - b) \
        / np.linalg.norm(b)
    assert res < 1e-10


def test_getrf_wide_and_tall(grid24):
    """Rectangular LU (regression: padded diagonal rows in wide
    matrices must self-pivot, not report spurious singularity)."""
    m, n, nb = 20, 44, 8
    a = rand(m, n, seed=20)
    A = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    LU, piv, info = st.getrf(A)
    assert int(info) == 0
    lu = np.asarray(LU.to_dense())
    l = np.tril(lu[:, :m], -1) + np.eye(m)
    u = np.triu(lu)[:m]
    perm = perm_from_piv(piv, m)
    err = np.linalg.norm(a[perm] - l @ u) / np.linalg.norm(a)
    assert err < 1e-12

    mt, nt2 = 44, 20
    at = rand(mt, nt2, seed=21)
    At = st.Matrix.from_dense(at, nb=nb, grid=grid24)
    LUt, pivt, infot = st.getrf(At)
    assert int(infot) == 0
    lut = np.asarray(LUt.to_dense())
    lt = np.tril(lut, -1)[:, :nt2] + np.eye(mt, nt2)
    ut = np.triu(lut[:nt2])
    permt = perm_from_piv(pivt, mt)
    err = np.linalg.norm(at[permt] - lt @ ut) / np.linalg.norm(at)
    assert err < 1e-12


def test_panel_lu_tournament():
    """Chunked CALU tournament path (tall-panel fallback): backward
    error P·A = L·U on the active window, growth bound, and rows
    outside the window untouched."""
    import jax.numpy as jnp
    from slate_tpu.internal.tile_kernels import panel_lu_factor
    rng = np.random.default_rng(7)
    M, nb, m, start = 96, 8, 90, 16
    panel = jnp.asarray(rng.standard_normal((M, nb)))
    ref = np.asarray(panel)
    for max_rows in (24, 40):   # forces 1-2 tournament rounds
        out, piv, info = panel_lu_factor(panel, start, m,
                                         max_rows=max_rows)
        assert int(info) == 0
        out = np.asarray(out)
        np.testing.assert_array_equal(out[:start], ref[:start])
        np.testing.assert_array_equal(out[m:], ref[m:])
        perm = np.arange(M)
        for j, pv in enumerate(np.asarray(piv)):
            perm[[start + j, pv]] = perm[[pv, start + j]]
        pa = ref[perm][start:m]
        lw = out[start:m]            # output rows are post-swap
        L = np.tril(lw, -1)
        L[:nb] += np.eye(nb)
        U = np.triu(lw[:nb])
        err = np.linalg.norm(pa - L @ U) / np.linalg.norm(pa)
        assert err < 1e-12, (max_rows, err)
        # CALU growth: |L| can exceed 1 for tournament losers, but
        # stays modest (bounded by 2^rounds in theory)
        assert np.abs(L).max() < 8.0


def _stored_rows_panel(grid, panel, start, m, nb, cap):
    """``getrf._panel_stored_rows`` on ``panel`` [M, nb] laid out
    block-cyclically over the grid's p (tile t on mesh row t % p, the
    same on every mesh column, as the chunk core hands it over after
    column k has crossed q), the padded diagonal fixed as the core
    fixes it. Returns the factored panel in global row order, the
    replicated diagonal block, the swap list and info."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from slate_tpu.grid import AXIS_P
    from slate_tpu.internal import masks
    from slate_tpu.linalg import getrf
    p = grid.p
    M = panel.shape[0]
    mtl, k = M // nb // p, start // nb
    tiles = jnp.asarray(panel).reshape(M // nb, nb, nb)
    tiles = tiles.at[k].set(masks.tile_diag_pad_identity(tiles[k], k, m, nb))
    local = tiles.reshape(mtl, p, nb, nb).transpose(1, 0, 2, 3)

    def body(x):
        gi = masks.local_tile_rows(mtl, p)
        t_local = gi[:, None] * nb + jnp.arange(nb)[None, :]
        newcol, lu_top, piv, info = getrf._panel_stored_rows(
            x[0], jnp.int32(k), t_local, m, p, cap)
        return newcol[None], lu_top, piv, info

    newcol, lu_top, piv, info = jax.jit(jax.shard_map(
        body, mesh=grid.mesh, in_specs=P(AXIS_P),
        out_specs=(P(AXIS_P), P(), P(), P()), check_vma=False))(local)
    out = np.asarray(newcol).transpose(1, 0, 2, 3).reshape(M, nb)
    fixed = np.asarray(tiles).reshape(M, nb)
    return out, fixed, np.asarray(lu_top), np.asarray(piv), int(info)


def _assert_swap_list(piv, start, hi):
    """LAPACK's contract of a step's swap list: row start+j is swapped
    with a row at or below it, inside the active window."""
    at = start + np.arange(piv.size)
    assert (piv >= at).all() and (piv < hi).all(), piv


@pytest.fixture(scope="module")
def grids():
    import jax
    d = jax.devices()
    return {"2x2": st.Grid(2, 2, devices=d[:4]),
            "4x2": st.Grid(4, 2, devices=d[:8]),
            "1x4": st.Grid(1, 4, devices=d[:4])}


# on the 2x2 a device stores 48 of the 96 rows. cap 48: one lu each;
# 24: two local chunks and a second local round. On a 4x2 (24 rows a
# device) cap 16: two local chunks, and the 4 x 8 gathered winners are
# over the cap too, so the rounds after the gather run as well. On a
# 1x4 every device stores all 96 rows and nothing crosses p
@pytest.mark.parametrize("shape,cap", [("2x2", 48), ("2x2", 24),
                                       ("4x2", 16), ("1x4", 48)])
@pytest.mark.parametrize("start,m", [(0, 96), (16, 90), (88, 90), (88, 96)],
                         ids=["first", "ragged", "ragged-last", "last"])
def test_panel_stored_rows_contract(grids, shape, cap, start, m):
    """The tournament run where the rows are stored, against
    ``_panel_lu_tournament``'s contract: P*panel = L*U on the active
    window, a LAPACK swap list, rows outside the window untouched, the
    replicated diagonal block = the top of the factor. ``last``: one
    mesh row stores the active rows, the others send sentinels only."""
    M, nb = 96, 8
    rng = np.random.default_rng(11 + start)
    panel = rng.standard_normal((M, nb))
    panel[m:] = 0.0
    out, ref, lu_top, piv, info = _stored_rows_panel(
        grids[shape], panel, start, m, nb, cap)
    hi = max(m, start + nb)
    assert info == 0
    _assert_swap_list(piv, start, hi)
    np.testing.assert_array_equal(out[:start], ref[:start])
    np.testing.assert_array_equal(out[hi:], ref[hi:])
    np.testing.assert_array_equal(out[start:start + nb], lu_top)
    perm = np.arange(M)
    for j, pv in enumerate(piv):
        perm[[start + j, pv]] = perm[[pv, start + j]]
    pa = ref[perm][start:hi]
    lw = out[start:hi]
    L = np.tril(lw, -1)
    L[:nb] += np.eye(nb)
    err = np.linalg.norm(pa - L @ np.triu(lw[:nb])) / np.linalg.norm(pa)
    assert err < 1e-12, err
    assert np.abs(L).max() < 8.0            # CALU's growth stays modest


@pytest.mark.parametrize("cap", [48, 24])
def test_panel_stored_rows_singular_column(grid22, cap):
    """A zero column counts into info and leaves a finite factor with
    P*panel = L*U; an all-zero window counts nb zero pivots and moves
    nothing it should not."""
    M, nb, start, m = 96, 8, 16, 96
    rng = np.random.default_rng(5)
    panel = rng.standard_normal((M, nb))
    panel[:, 3] = 0.0
    out, ref, _, piv, info = _stored_rows_panel(grid22, panel, start, m,
                                                nb, cap)
    assert info == 1 and np.isfinite(out).all()
    _assert_swap_list(piv, start, m)
    perm = np.arange(M)
    for j, pv in enumerate(piv):
        perm[[start + j, pv]] = perm[[pv, start + j]]
    lw = out[start:]
    L = np.tril(lw, -1)
    L[:nb] += np.eye(nb)
    assert np.abs(ref[perm][start:] - L @ np.triu(lw[:nb])).max() < 1e-12
    panel[start:] = 0.0
    out, ref, _, piv, info = _stored_rows_panel(grid22, panel, start, m,
                                                nb, cap)
    assert info == nb
    _assert_swap_list(piv, start, m)
    np.testing.assert_array_equal(out[:start], ref[:start])
    assert (out[start:] == 0).all()


@pytest.mark.parametrize("M,cap,depth,form", [
    (16384, 10240, 0, "stored"),        # the chip's 2x2 cell
    (10240, 10240, 0, "gathered"),      # at the cap: one lu of it
    (16384, None, 0, "gathered"),       # no cap off the TPU
    (16384, 10240, 1, "gathered"),      # the pipelined core's ring
])
def test_panel_form_is_read_from_the_shape(M, cap, depth, form):
    from slate_tpu.linalg import getrf
    assert getrf._panel_form(M, cap, depth) == form


def test_panel_max_rows_is_the_platforms():
    """The cap of one ``lax.linalg.lu`` panel: a TPU's scoped vmem, no
    other platform's (what ``_panel_form`` is handed as ``cap``)."""
    from slate_tpu.internal import tile_kernels
    from slate_tpu.linalg import getrf
    assert getrf._panel_max_rows("tpu") == tile_kernels.LU_PANEL_MAX_ROWS
    assert getrf._panel_max_rows("tpu") == 10240
    assert getrf._panel_max_rows("cpu") is None
    assert getrf._panel_max_rows("gpu") is None


def _rhs_stub(p, q, mtl, ntl, nb, n, itemsize=4):
    """What ``_apply_pivots_kind`` reads of a B: no array behind it."""
    from types import SimpleNamespace as NS
    return NS(grid=NS(size=p * q, p=p, q=q), nb=nb, n=n,
              data=NS(shape=(p, q, mtl, ntl, nb, nb),
                      dtype=NS(itemsize=itemsize)))


@pytest.mark.parametrize("B,order,kind", [
    # an elimination order is one gather, whatever B is
    (_rhs_stub(2, 2, 8, 8, 1024, 16384), True, "order_gather"),
    (_rhs_stub(1, 1, 16, 1, 1024, 8), False, "swap_sim"),   # one chip
    # the 2x2 cells' B: one and eight columns in one tile column
    (_rhs_stub(2, 2, 8, 1, 1024, 1), False, "swap_sim"),
    (_rhs_stub(2, 2, 8, 1, 1024, 8), False, "swap_sim"),
    (_rhs_stub(2, 2, 8, 2, 1024, 4096), False, "swap_sim"),  # n = 4 nb
    # getri's scale: over four tile columns, 1 GiB replicated
    (_rhs_stub(2, 2, 8, 8, 1024, 16384), False, "dist"),
    # wide in tiles but 1 MiB replicated
    (_rhs_stub(2, 2, 8, 8, 32, 512), False, "swap_sim"),
    # 512 tile rows: that many psum rounds lose to one gather of 32 MiB
    (_rhs_stub(2, 2, 256, 8, 32, 512), False, "swap_sim"),
    # ... unless the replicated B is 2 GiB
    (_rhs_stub(2, 2, 256, 32, 128, 8192), False, "dist"),
    # float64 doubles the bytes: 32 MiB is no longer under the floor,
    # 16 tile rows are no latency to guard against
    (_rhs_stub(2, 2, 8, 8, 128, 2048, itemsize=8), False, "dist"),
    (_rhs_stub(2, 2, 8, 8, 128, 2048), False, "swap_sim"),
])
def test_apply_pivots_kind_is_read_off_the_shape(B, order, kind):
    from slate_tpu.linalg import getrf
    piv = getrf.PivotOrder(None) if order else object()
    assert getrf._apply_pivots_kind(B, piv) == kind


# sha256 of _getrf_chunk_jit's lowered StableHLO text under the cap
# (the gathered panel) on the CPU 2x2 at nb = 32, as the commit before
# the stored form (8d50beb) lowers it: (n, k0, window) -> digest;
# jax 0.9.0, x64 on as tests/conftest.py sets it
_GATHERED_CHUNK_TEXT = {
    (512, 0, None): "42f58bf7225b8662fcfa404ab3d980e0"
                    "a532c1c58998a1d75bfac20079e14b84",
    (500, 14, None): "02dc781a385c1db18cdcb30f95a38e6e"
                     "efa18220c7855bbc45959c6fc36c7d12",
    (512, 4, (6, 4)): "1206e6a9136cf62d24273fbdb1955963"
                      "7eda6142b29f369ba73c94b8bd7d113f",
}


@pytest.mark.parametrize("n,k0,window", list(_GATHERED_CHUNK_TEXT),
                         ids=["first", "ragged-last", "windowed"])
def test_chunk_core_under_the_cap_lowers_to_the_text_it_had(grid22, n, k0,
                                                            window):
    """Under the row cap the shape chooses the gathered panel, and the
    chunk program is the one it was: the same StableHLO text."""
    import hashlib
    import jax
    import jax.numpy as jnp
    from slate_tpu.linalg import getrf
    if jax.__version__ != "0.9.0":
        pytest.skip("the digests were taken with jax 0.9.0")
    nb = 32
    A = st.random_matrix(n, n, nb, grid22, jnp.float32, seed=1)
    kw = {} if window is None else {"win_hi": window[0],
                                    "swap_min": window[1]}
    low = getrf._getrf_chunk_jit.lower(
        A, jnp.zeros((A.mt, nb), jnp.int32), jnp.zeros((), jnp.int32),
        k0, 2, tier="bf16_6x", **kw)
    assert "all_gather" in low.as_text()
    assert (hashlib.sha256(low.as_text().encode()).hexdigest()
            == _GATHERED_CHUNK_TEXT[n, k0, window])


def test_getrf_chunked_spmd_path(grid24):
    # kt=12 >= 2*lcm(2,4): exercises the chunked super-step programs,
    # with a matrix that genuinely pivots
    n, nb = 90, 8
    a = rand(n, n, seed=18)
    a[np.arange(n), np.arange(n)] *= 1e-8
    b = rand(n, 3, seed=19)
    A = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    B = st.Matrix.from_dense(b, nb=nb, grid=grid24)
    X, LU, piv, info = st.gesv(A, B)
    assert int(info) == 0
    x = np.asarray(X.to_dense())
    xref = np.linalg.solve(a, b)
    assert np.abs(x - xref).max() / np.abs(xref).max() < 1e-8


def test_getri_with_real_pivoting(grid24):
    n, nb = 40, 8
    a = rand(n, n, seed=21)
    a[np.arange(n), np.arange(n)] *= 1e-8   # force row interchanges
    A = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    LU, piv, info = st.getrf(A)
    assert int(info) == 0
    Ainv = st.getri(LU, piv)
    got = np.asarray(Ainv.to_dense())
    np.testing.assert_allclose(got @ a, np.eye(n), rtol=1e-7, atol=1e-7)


def test_apply_pivots_distributed_matches_dense(grid24):
    """Multi-chip pivot application (masked-psum pass, no replicated
    dense array) is bit-identical to the single-device dense path
    (reference internal_swap.cc semantics)."""
    import jax.numpy as jnp
    from slate_tpu.linalg.getrf import _apply_piv_jit, _apply_piv_dist
    rng = np.random.default_rng(17)
    m, n, nb, kt = 130, 70, 16, 4
    a = rng.standard_normal((m, n))
    B = st.Matrix.from_dense(a, nb=nb, grid=grid24)
    piv = np.zeros((kt, nb), np.int32)
    for k in range(kt):
        for j in range(nb):
            lo = k * nb + j
            piv[k, j] = rng.integers(lo, m) if lo < m else lo
    piv = jnp.asarray(piv)
    for fwd in (True, False):
        ref = np.asarray(_apply_piv_jit(B, piv, fwd).to_dense())
        got = np.asarray(_apply_piv_dist(B, piv, fwd).to_dense())
        assert np.array_equal(ref, got)


def _serial_perm(piv, Mrows, forward):
    """The swap list replayed one swap after another, in numpy: the
    permutation ``_sim_perm`` must give entry for entry."""
    piv = np.asarray(piv).reshape(-1)
    perm = np.arange(Mrows)
    for t in (range(piv.size) if forward else reversed(range(piv.size))):
        perm[[t, piv[t]]] = perm[[piv[t], t]]
    return perm


def _swap_list(kind, kt, nb, Mrows, rng):
    own = np.arange(kt * nb).reshape(kt, nb)
    if kind == "lapack":            # getrf's: piv[t] >= t
        return rng.integers(own, Mrows)
    if kind == "arbitrary":         # Aasen's (hetrs): rows above t too
        return rng.integers(0, Mrows, (kt, nb))
    if kind == "self":
        return own
    if kind == "repeated":          # a few rows named again and again
        return rng.integers(0, min(3, Mrows), (kt, nb))
    assert kind == "padded"         # a ragged order: the tail swaps itself
    real = max(1, kt * nb - max(1, nb // 3))
    return np.where(own < real, rng.integers(np.minimum(own, real - 1),
                                             real), own)


@pytest.mark.parametrize("forward", [True, False], ids=["fwd", "bwd"])
@pytest.mark.parametrize("kind", ["lapack", "arbitrary", "self", "repeated",
                                  "padded"])
@pytest.mark.parametrize("kt,nb,Mrows", [
    (4, 8, 32), (1, 16, 16), (6, 1, 6), (3, 8, 40), (5, 1, 9), (7, 16, 130)],
    ids=["4x8", "kt1", "nb1", "ragged", "nb1-ragged", "7x16-ragged"])
def test_sim_perm_is_the_serial_replay(kt, nb, Mrows, kind, forward):
    """``_sim_perm`` (all panels replayed at once, then composed) gives
    bit for bit the permutation of the swap-by-swap replay, for any swap
    list, both directions."""
    import jax.numpy as jnp
    from slate_tpu.linalg.getrf import _sim_perm
    rng = np.random.default_rng(kt * 1000 + nb * 10 + forward)
    piv = _swap_list(kind, kt, nb, Mrows, rng).astype(np.int32)
    got = np.asarray(_sim_perm(jnp.asarray(piv), Mrows, forward))
    assert got.dtype == np.int32
    assert np.array_equal(got, _serial_perm(piv, Mrows, forward))


@pytest.mark.parametrize("cell,p,q,kt,nb", [
    ("gesv_10000_nb384_1x1", 1, 1, 27, 384), ("gesv_16k_2x2", 2, 2, 16, 1024)])
@pytest.mark.parametrize("forward", [True, False], ids=["fwd", "bwd"])
def test_apply_piv_program_has_no_loop_of_kt_nb_trips(cell, p, q, kt, nb,
                                                      forward):
    """``_apply_piv_jit`` compiled for the two cells' B: its longest
    loop is the nb steps in which all panels are replayed at once, with
    the kt compositions beside it; nothing runs kt·nb trips."""
    import re
    import jax
    import jax.numpy as jnp
    from slate_tpu.linalg.getrf import _apply_piv_jit
    grid = st.Grid(p, q, devices=jax.devices()[:p * q])
    b = jax.ShapeDtypeStruct((p, q, kt // p, 1, nb, nb), jnp.float32,
                             sharding=grid.sharding())
    B = st.Matrix(data=b, m=kt * nb, n=1, nb=nb, grid=grid)
    piv = jax.ShapeDtypeStruct((kt, nb), jnp.int32)
    text = _apply_piv_jit.lower(B, piv, forward=forward).compile().as_text()
    trips = sorted(int(n) for n in re.findall(
        r'"known_trip_count":\{"n":"(\d+)"\}', text))
    assert trips == sorted([kt, nb]), trips
