"""The VMEM-resident Pallas bulge chaser of hb2st
(internal/band_wave_vmem.py) against the numpy twin
(internal/band_bulge.py), in interpret mode on the CPU test mesh; the
compiled kernel runs in ``heev_8192_vec_1x1`` on the chip. The XLA wave
chaser's tests are in tests/test_band_wave.py, the bidiagonal twin's in
tests/test_band_wave_vmem_bd.py.

The interpreted kernel's compile does not depend on n (one task body in
a loop, 20-30 s at band 128); its run does, so a chase runs at the
smallest order that walks the path its test names."""

import numpy as np
import pytest

from slate_tpu.internal import band_bulge, band_wave_vmem
from slate_tpu.internal.band_wave_vmem import (TAUP, _geometry,
                                               hb2st_wave_vmem, shear_form,
                                               vmem_applies)
from slate_tpu.internal.band_wave_vmem_bd import (tb2bd_wave_vmem,
                                                  vmem_applies_bd)
from tests.test_band_wave import _dense_from_band, _rand_band, _rand_uband


def _counted_forms(fn, *args, **kw):
    """fn's result and the ``hb2st.shear`` counts it added, by form."""
    from slate_tpu import obs
    from slate_tpu.obs import metrics
    was = obs.metrics_enabled()
    obs.metrics_on()
    try:
        before = dict(metrics.counters_named("hb2st.shear"))
        out = fn(*args, **kw)
        after = metrics.counters_named("hb2st.shear")
    finally:
        if not was:
            obs.metrics_off()
    return out, {dict(k)["form"]: v - before.get(k, 0)
                 for k, v in after.items() if v != before.get(k, 0)}


@pytest.mark.parametrize("n,band", [(50, 8), (70, 8), (100, 16)])
def test_vmem_matches_numpy_twin(n, band):
    ab = _rand_band(n, band, np.float32, seed=n * band)
    d0, e0, V0, t0 = band_bulge.hb2st(ab.copy())
    # bands under 128 keep the masked-roll ladder (FW = 4b, col0s that
    # differ by frame): the single-pass forms are the band-128 layout's
    assert band_wave_vmem.chase_shear_form(band) == "ladder"
    (d1, e1, V1, t1), forms = _counted_forms(
        hb2st_wave_vmem, ab.copy(), interpret=True)
    assert forms == {"ladder": 1}
    # f32 only (the kernel's envelope): same loose tolerance as the
    # f32 XLA-wave rows — the chase is a long sequential recurrence
    # and the sheared lane reductions associate differently
    tol = 5e-3
    assert np.allclose(d0, d1, atol=tol, rtol=tol)
    assert np.allclose(e0, e1, atol=tol, rtol=tol)
    assert V1.shape == V0.shape and t1.shape == t0.shape
    assert np.allclose(V0, V1, atol=tol, rtol=tol)
    assert np.allclose(t0, t1, atol=tol, rtol=tol)


# The smallest order whose sweeps have a seed task, an interior chase
# and a last one (three tasks a sweep: n >= 2 b + 2), the last block
# three rows tall: two wave slots, so slot 1 of parity 0 chains from
# slot 0 of parity 1. The run goes with n; the 30 s compile of the
# interpreted body has no n in it.
FRAMES_N, FRAMES_BAND = 260, 128


@pytest.fixture(scope="module")
def frames_run():
    """One interpret-mode run of the chaser at band 128 (the FRAMES
    layout, whose shears are single-pass), shared by the tests that
    read it: the strided rotate expands to a roll a row in interpret
    mode, so the program is slow to compile on the CPU."""
    assert band_bulge.max_chase(FRAMES_N, FRAMES_BAND) == 3
    assert _geometry(FRAMES_N, FRAMES_BAND)[1] == 2      # wave slots
    ab = _rand_band(FRAMES_N, FRAMES_BAND, np.float32, seed=31)
    out, forms = _counted_forms(hb2st_wave_vmem, ab.copy(),
                                interpret=True)
    return ab, out, forms


def test_vmem_frames_path_matches_twin(frames_run):
    """The half-width FRAMES layout (b % 128 == 0 — the production
    bands' code path: frame slicing, c0 remaps, zb-concat delta
    recomposition) differentially checked against the numpy twin in
    interpret mode at band 128."""
    ab, (d1, e1, V1, t1), forms = frames_run
    assert forms == {"single_pass": 1}
    d0, e0, V0, t0 = band_bulge.hb2st(ab.copy())
    tol = 5e-3
    assert np.allclose(d0, d1, atol=tol, rtol=tol)
    assert np.allclose(e0, e1, atol=tol, rtol=tol)
    assert V1.shape == V0.shape and t1.shape == t0.shape
    # spectrum vs dense (no element-wise V/tau at this chain depth —
    # see test_tb2bd_vmem_frames_path_matches_twin)
    lam = np.linalg.eigvalsh(
        np.diag(d1.astype(np.float64))
        + np.diag(e1.astype(np.float64), 1)
        + np.diag(e1.astype(np.float64), -1))
    ref = np.linalg.eigvalsh(_dense_from_band(ab).astype(np.float64))
    assert np.allclose(lam, ref, atol=2e-3 * max(1, np.abs(ref).max()))


def test_vmem_frames_single_pass_matches_the_ladder(frames_run,
                                                    monkeypatch):
    """The same chase with every shear built by the ladder (the
    reference form, kept callable): d, e, V, tau agree to the twin
    tests' tolerance. Not bitwise: the sheared arrays are, but XLA's
    CPU fuses the sublane sum after the rotate another way, and the
    chase is a long recurrence."""
    import jax
    n, band = FRAMES_N, FRAMES_BAND
    ab, single, _ = frames_run
    monkeypatch.setattr(band_wave_vmem, "shear_form",
                        lambda *a, **k: "ladder")

    def ladder_chaser(ab, band, n, interpret):
        # a function of its own: jit's trace cache is keyed on it
        return band_wave_vmem._hb2st_vmem_jit.__wrapped__(
            ab, band, n, interpret)

    ladder = jax.jit(ladder_chaser, static_argnames=(
        "band", "n", "interpret"))(ab.copy(), band=band, n=n,
                                   interpret=True)
    tol = 5e-3
    for got, want in zip(single, ladder):
        got, want = np.asarray(got), np.asarray(want)
        assert got.shape == want.shape
        assert np.allclose(got, want, atol=tol, rtol=tol)


def _shear_pair(rows, vec, Q):
    """Both forms of both helpers on one [rows, 2 rows] frame, in
    interpret mode: (single-pass shear, ladder shear, single-pass
    rotate, ladder rotate, single-pass column sums, ladder's)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    W = 2 * rows

    def kern(v_ref, q_ref, s1, s0, a1, a0, c1, c0):
        v, q = v_ref[...], q_ref[...]
        s1[...] = band_wave_vmem._shear_rowvec(v, rows - 1, rows, W)
        s0[...] = band_wave_vmem._shear_rowvec_ladder(v, rows - 1, rows, W)
        a1[...] = band_wave_vmem._antishear(q, rows, W)
        a0[...] = band_wave_vmem._antishear_ladder(q, rows, W)
        c1[...] = band_wave_vmem._antishear_sum(q, rows, W)
        c0[...] = jnp.sum(band_wave_vmem._antishear_ladder(q, rows, W),
                          axis=0, keepdims=True)

    blk = jax.ShapeDtypeStruct((rows, W), jnp.float32)
    row = jax.ShapeDtypeStruct((1, W), jnp.float32)
    return [np.asarray(x) for x in pl.pallas_call(
        kern, out_shape=(blk, blk, blk, blk, row, row),
        interpret=True)(vec, Q)]


@pytest.fixture(scope="module", params=[128, 256])
def shear_pair(request):
    rows = request.param
    rng = np.random.default_rng(rows)
    vec = np.zeros((1, 2 * rows), np.float32)
    vec[0, :rows] = rng.standard_normal(rows)
    Q = rng.standard_normal((rows, 2 * rows)).astype(np.float32)
    return rows, vec, Q, _shear_pair(rows, vec, Q)


# the masks a task body puts on a sheared vector, by the block's own
# column index col = c - col0 + i: mB / mD / mU are (0 <= col < L) and
# a row bound; Zb adds col >= 1
@pytest.mark.parametrize("lo", [0, 1], ids=["mB", "mB_past_col0"])
@pytest.mark.parametrize("L", [1.0, 0.71, 0.3, 1 / 64])
def test_single_pass_shear_is_the_ladders_under_the_masks(shear_pair, L,
                                                          lo):
    rows, vec, _, (s1, s0, *_rest) = shear_pair
    assert shear_form(rows, 2 * rows, rows - 1) == "single_pass"
    L = max(1, int(L * rows))
    i = np.arange(rows)[:, None]
    col = np.arange(2 * rows)[None, :] - (rows - 1) + i
    m = (col >= lo) & (col < L) & (i < L)
    assert m.any()
    # bitwise: both forms only move data
    assert np.array_equal(np.where(m, s1, 0), np.where(m, s0, 0))
    assert np.array_equal(np.where(m, s1, 0),
                          np.where(m, vec[0][np.clip(col, 0, rows - 1)],
                                   0))


def test_single_pass_antishear_is_the_ladders(shear_pair):
    rows, _, Q, (_s1, _s0, a1, a0, c1, c0) = shear_pair
    assert shear_form(rows, 2 * rows) == "single_pass"
    # the rotated block bitwise; its column sums to a few ulp of the
    # column's absolute sum (the CPU fuses that reduction otherwise)
    assert np.array_equal(a1, a0)
    assert np.array_equal(a1, np.stack([np.roll(Q[r], r)
                                        for r in range(rows)]))
    ulp = np.finfo(np.float32).eps * np.abs(a0).sum(axis=0)
    assert (np.abs(c1[0] - c0[0]) <= 4 * ulp).all()


@pytest.mark.parametrize("rows, W4, col0, form", [
    (128, 256, 127, "single_pass"), (256, 512, 255, "single_pass"),
    (128, 256, None, "single_pass"),
    (128, 512, 127, "ladder"),      # full-width frames
    (128, 256, 255, "ladder"),      # another col0
    (64, 256, 63, "ladder"), (8, 32, 7, "ladder"),
    (96, 192, 95, "ladder")])       # not a lane-tile multiple
def test_shear_form_is_read_off_the_shape(rows, W4, col0, form):
    assert shear_form(rows, W4, col0) == form


def test_vmem_eigenvalues_match_dense():
    n, band = 80, 8
    ab = _rand_band(n, band, np.float32, seed=5)
    d, e, _, _ = hb2st_wave_vmem(ab, interpret=True)
    lam = np.linalg.eigvalsh(
        np.diag(d.astype(np.float64))
        + np.diag(e.astype(np.float64), 1)
        + np.diag(e.astype(np.float64), -1))
    ref = np.linalg.eigvalsh(_dense_from_band(ab).astype(np.float64))
    assert np.allclose(lam, ref, atol=2e-3 * max(1, np.abs(ref).max()))


def test_vmem_gate_and_fallback():
    # gate: band bounds, power-of-two, dtype, VMEM ceiling
    assert vmem_applies(8192, 128, np.float32)
    assert not vmem_applies(8192, 96, np.float32)     # not a pow2
    assert not vmem_applies(8192, 4, np.float32)      # below envelope
    assert not vmem_applies(8192, 512, np.float32)    # above envelope
    assert not vmem_applies(8192, 128, np.float64)    # dtype
    assert not vmem_applies(200_000, 128, np.float32)  # ribbon > VMEM
    # unsupported shapes fall back to the XLA wave, same contract
    ab = _rand_band(40, 3, np.float64, seed=2)
    d0, e0, V0, t0 = band_bulge.hb2st(ab.copy())
    d1, e1, V1, t1 = hb2st_wave_vmem(ab.copy())
    assert np.allclose(d0, d1, atol=1e-11)
    assert np.allclose(e0, e1, atol=1e-11)


def test_hb2st_dispatch_vmem(monkeypatch):
    """SLATE_HB2ST=vmem routes hb2st through the VMEM chaser (interpret
    mode off-TPU) and matches the numpy twin."""
    from slate_tpu.linalg.he2hb import hb2st
    monkeypatch.setenv("SLATE_HB2ST", "vmem")
    n, band = 50, 8
    ab = _rand_band(n, band, np.float32, seed=9)
    d0, e0, V0, t0 = band_bulge.hb2st(ab.copy())
    d1, e1, V1, t1 = hb2st(ab.copy())
    tol = 5e-3
    assert np.allclose(d0, d1, atol=tol, rtol=tol)
    assert np.allclose(e0, e1, atol=tol, rtol=tol)
    assert np.allclose(V0, V1, atol=tol, rtol=tol)


def test_vmem_gate_slot_capacity():
    """P = T//2+1 chase slots must fit the kernel's one 128-lane tau
    tile; past it the store drops lanes >= 128 and the packed
    read-back clamps to lane 127 — silently wrong eigenvalues
    (ADVICE r5, high). The gate must reject, for BOTH twins."""
    # band 8: P = 128 at n = 2041, P = 129 at n = 2042
    assert _geometry(2041, 8)[1] == TAUP
    assert vmem_applies(2041, 8, np.float32)
    assert vmem_applies_bd(2041, 8, np.float32)
    assert _geometry(2042, 8)[1] == TAUP + 1
    assert not vmem_applies(2042, 8, np.float32)
    assert not vmem_applies_bd(2042, 8, np.float32)
    # band 128 (the production heev band): capacity runs out at
    # n = 32642 — BEFORE the r5 failure shapes (n >= 32770)
    assert vmem_applies(32641, 128, np.float32)
    assert not vmem_applies(32642, 128, np.float32)


def test_vmem_slot_overflow_routes_to_wave(monkeypatch):
    """Shapes with P > TAUP must take the XLA wave fallback, never
    the VMEM kernel (pre-fix they compiled the kernel and corrupted
    tau). Sentinel-patch the fallbacks and check the routing."""
    from slate_tpu.internal import band_bulge_wave, band_bulge_wave_bd

    sentinel = object()
    monkeypatch.setattr(band_bulge_wave, "hb2st_wave",
                        lambda ab: sentinel)
    monkeypatch.setattr(band_bulge_wave_bd, "tb2bd_wave",
                        lambda ub: sentinel)
    n, band = 2050, 8                     # P = 129 > TAUP
    ab = _rand_band(n, band, np.float32, seed=1)
    assert hb2st_wave_vmem(ab) is sentinel
    ub = _rand_uband(n, band, np.float32, seed=1)
    assert tb2bd_wave_vmem(ub) is sentinel


def test_two_stage_chase_band():
    """eig.py's lowered dense/two-stage threshold must gate the VMEM
    chaser on the band the pipeline ACTUALLY chases at (ADVICE r5,
    low: it tested the preferred band even when heev_two_stage keeps
    A.nb)."""
    from slate_tpu.linalg.he2hb import two_stage_chase_band
    # re-block happens: nb > band_nb and n > 2*band_nb
    assert two_stage_chase_band(16384, 256, 128) == 128
    # nb already at the preferred band
    assert two_stage_chase_band(16384, 128, 128) == 128
    # nb SMALLER than preferred: pipeline keeps nb (pre-fix the
    # threshold gate tested 128 here)
    assert two_stage_chase_band(16384, 64, 128) == 64
    # matrix too small to re-block: pipeline keeps nb
    assert two_stage_chase_band(200, 256, 128) == 256
