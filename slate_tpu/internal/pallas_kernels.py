"""Pallas TPU kernel suite: tile factorizations and panel kernels.

Reference analog: the device-side panel kernels the reference gets
from vendor libraries — device LAPACK ``potrf`` used by
internal_potrf.cc:132 / src/potrf.cc:195-215, the ``getrf_nopiv``
tile kernel (src/internal/internal_getrf_nopiv.cc), and the
tile-level trsm/gemm of Tile_blas.hh. On TPU, XLA's
``lax.linalg.cholesky``/``lu`` lower to blocked HLO While loops whose
per-iteration dynamic-update-slices round-trip HBM; these Pallas
kernels keep the whole block resident in VMEM and do the blocked
factorization with MXU panel updates and VPU mask-select column
sweeps (no dynamic lane indexing — column j is extracted with
``where(jj == j, ·, 0).sum()``, the Mosaic-friendly idiom).

Kernel inventory (each with a registered VMEM footprint estimator in
``VMEM_FOOTPRINTS`` cross-checked by slatesan's ``vmem.gate_drift``):

* ``potrf_tile_pallas`` / ``lu_nopiv_tile_pallas`` — [nb, nb] tile
  factorizations (blocked, MXU trailing updates);
* ``panel_plu_pallas`` — fused panel PLU: in-VMEM partial-pivot
  search + row swap + rank-1 update in one ``pallas_call``, emitting
  the LAPACK-order pivot vector (bitwise-compatible ipiv for getrf);
* ``trsm_left_lower_pallas`` / ``trsm_right_lower_t_pallas`` —
  blocked triangular solves against a factored panel (the getrf
  U-row and potrf L-column updates), pinned to the bf16_6x MXU
  passes (``panel_precision`` = HIGHEST) per the precision policy;
* ``rank_k_tail_pallas`` — rank-k trailing-tail update for the
  sub-``nb`` remainder XLA otherwise pads to a full lane tile.

Rung selection: every dispatch site (tile_kernels.py) consults
``active_rung(kernel)`` — the SLATE_PALLAS_* env force, then the
in-process rung registry the autotuner arms (slate_tpu/tune). The
rung is read at **trace** time, so flipping it in-process requires a
retrace (``forced_rung`` clears the relevant jit caches; persisted
executables are safe because cached_jit keys carry the tuning-table
token). Validated on CPU via ``interpret=True`` — non-TPU backends
always run interpret, so tier-1 tests exercise the same code path.
"""

from __future__ import annotations

import contextlib
import os
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BS = 128  # in-kernel panel width (one lane tile)


# ---------------------------------------------------------------------------
# capability table + rung registry (one answer for ladder and autotuner)
# ---------------------------------------------------------------------------

# kernel → dtype name → (nb_min, nb_max, nb_multiple). The TPU rows
# describe what Mosaic lowers today (f32/bf16 lane tiles); non-TPU
# backends run interpret=True, where the f64 parity suite also runs.
# rank_k is deliberately capped below one lane tile: it exists for the
# sub-nb remainder, full tiles belong to XLA's gemm. The nb gated here
# is the factor dimension; the trsm B window's free dimension is gated
# separately at the dispatch site (tile_kernels._trsm_pallas_ok) and
# must also be a 128 multiple — it is the window's lane dimension for
# the left solve, and Mosaic rejects sub-lane last dims at trace time
# rather than falling back.
_CAPS_TPU = {
    "tile":      {"float32": (128, 1024, 128),
                  "bfloat16": (128, 1024, 128)},
    "panel_plu": {"float32": (128, 256, 128)},
    "trsm":      {"float32": (128, 1024, 128),
                  "bfloat16": (128, 1024, 128)},
    "rank_k":    {"float32": (1, 127, 1),
                  "bfloat16": (1, 127, 1)},
}
_CAPS_INTERPRET = {
    "tile":      {"float32": (128, 1024, 128),
                  "bfloat16": (128, 1024, 128)},
    "panel_plu": {"float32": (128, 256, 128),
                  "float64": (128, 256, 128)},
    "trsm":      {"float32": (128, 1024, 128),
                  "float64": (128, 1024, 128),
                  "bfloat16": (128, 1024, 128)},
    "rank_k":    {"float32": (1, 127, 1),
                  "float64": (1, 127, 1),
                  "bfloat16": (1, 127, 1)},
}
CAPABILITY = {"tpu": _CAPS_TPU, "cpu": _CAPS_INTERPRET,
              "gpu": _CAPS_INTERPRET}


def pallas_supported(nb: int, dtype, platform: str | None = None,
                     kernel: str = "tile") -> bool:
    """Explicit capability table (dtype × nb × platform) answering
    "can this rung run here" — shared by the backend ladder's dispatch
    gates and the autotuner's candidate enumeration."""
    if platform is None:
        platform = jax.default_backend()
    spec = CAPABILITY.get(platform, {}).get(kernel, {}).get(
        jnp.dtype(dtype).name)
    if spec is None:
        return False
    lo, hi, mult = spec
    return lo <= nb <= hi and nb % mult == 0


# env forces (tile keeps its historical switch); the tune package arms
# the registry from the persisted table instead. The forces are part
# of cache/store.fingerprint() (via _pallas_forces): they change which
# kernels a trace emits, so executables compiled under a force live in
# a different store generation than unforced ones.
_RUNG_ENV = {"tile": "SLATE_PALLAS_TILE",
             "panel_plu": "SLATE_PALLAS_PANEL",
             "trsm": "SLATE_PALLAS_TRSM",
             "rank_k": "SLATE_PALLAS_RANKK"}
_RUNGS: dict[str, str] = {}


def set_rung(kernel: str, rung: str | None) -> None:
    """Arm ("pallas") / disarm ("xla" or None) one kernel rung.
    Trace-time state: callers that flip it mid-process must retrace
    (see forced_rung); the autotuner sets it per call, deterministic
    in the call's shape bucket, so each traced shape sees one value."""
    if rung is None:
        _RUNGS.pop(kernel, None)
    else:
        _RUNGS[kernel] = rung


def active_rung(kernel: str) -> str:
    if os.environ.get(_RUNG_ENV.get(kernel, ""), "0") == "1":
        return "pallas"
    return _RUNGS.get(kernel, "xla")


def rung_enabled(kernel: str) -> bool:
    return active_rung(kernel) == "pallas"


def clear_traces() -> None:
    """Rung flips are invisible to jit — drop in-process traces so the
    next call re-reads the registry (persisted executables are keyed
    by the tune table token and need no clearing)."""
    try:
        from ..cache import jitcache
        jitcache.clear_in_process()
    except Exception:  # noqa: BLE001 — cache layer is optional here
        pass
    try:
        jax.clear_caches()
    except Exception:  # noqa: BLE001
        pass


@contextlib.contextmanager
def forced_rung(kernel: str, rung: str = "pallas"):
    """Test/sweep helper: flip one rung with the retrace bookkeeping
    both ways."""
    prev = _RUNGS.get(kernel)
    set_rung(kernel, rung)
    clear_traces()
    try:
        yield
    finally:
        set_rung(kernel, prev)
        clear_traces()


def default_interpret() -> bool:
    """Non-TPU backends run the kernels under the Pallas interpreter —
    tier-1 CPU tests exercise the same code path as the TPU rung."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# VMEM footprint gates (slatelint SL003 / slatesan vmem.gate_drift)
# ---------------------------------------------------------------------------

_PANEL_VMEM_BUDGET = 40 * 1024 * 1024


def tile_vmem_bytes(nb: int) -> int:
    """[nb, nb] tile kernels: aliased-pair-free in/out windows plus the
    per-block f32 temporaries (diag block, its inverse, the panel
    column and the trailing product)."""
    return (2 * nb * nb + 2 * _BS * _BS + 2 * nb * _BS + nb * nb) * 4


def panel_plu_vmem_bytes(h: int, w: int) -> int:
    """Fused panel-PLU: the aliased [h, w] window (double-buffered) +
    the rank-1 update temporary + per-column extracts (column, score,
    swap rows, multipliers) + the pivot/info output tiles."""
    return (2 * h * w + h * w + 4 * h + 4 * w + 2 * w + 8) * 4


def trsm_vmem_bytes(n: int, m: int) -> int:
    """Blocked trsm: L [n, n] + the aliased B/X window
    (double-buffered) + the [bs, bs] diagonal-inverse pair + block
    row/column temporaries."""
    return (n * n + 2 * n * m + 2 * _BS * _BS + 2 * n + 2 * m) * 4


def rank_k_vmem_bytes(m: int, n: int, k: int) -> int:
    """Rank-k tail: A [m, k] + B [k, n] + the aliased C window
    (double-buffered) + the product temporary."""
    return (m * k + k * n + 2 * m * n + m * n) * 4


def tile_vmem_applies(nb: int) -> bool:
    return tile_vmem_bytes(nb) <= _PANEL_VMEM_BUDGET


def panel_plu_vmem_applies(h: int, w: int) -> bool:
    return panel_plu_vmem_bytes(h, w) <= _PANEL_VMEM_BUDGET


def trsm_vmem_applies(n: int, m: int) -> bool:
    return trsm_vmem_bytes(n, m) <= _PANEL_VMEM_BUDGET


def rank_k_vmem_applies(m: int, n: int, k: int) -> bool:
    return rank_k_vmem_bytes(m, n, k) <= _PANEL_VMEM_BUDGET


# estimator registry: slatesan's gate_drift cross-check enumerates
# this (tests trace each kernel and compare Ref-aval residency against
# the closed form — the hand-model must never undercount the trace).
VMEM_FOOTPRINTS = {
    "potrf_tile": tile_vmem_bytes,
    "lu_nopiv_tile": tile_vmem_bytes,
    "panel_plu": panel_plu_vmem_bytes,
    "trsm": trsm_vmem_bytes,
    "rank_k": rank_k_vmem_bytes,
}




# ---------------------------------------------------------------------------
# in-kernel [bs, bs] unblocked factorizations (VPU mask-select sweeps)
# ---------------------------------------------------------------------------

def _outer(a_col, b_row, dtype):
    """[bs,1] × [1,bs] → [bs,bs] (2-D shapes only — Mosaic has no 1-D
    vector layout)."""
    return jax.lax.dot_general(
        a_col, b_row, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=dtype)


def _chol_diag(D, bs):
    """Unblocked lower Cholesky of a [bs, bs] block (full-tile VPU ops
    per column; ~bs³ flops, negligible next to the MXU updates)."""
    ii = lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
    jj = lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
    ic = lax.broadcasted_iota(jnp.int32, (bs, 1), 0)         # [bs,1]

    def col(j, D):
        d = jnp.sqrt(jnp.sum(jnp.where((ii == j) & (jj == j), D, 0.0),
                             axis=1, keepdims=True).sum(
                                 axis=0, keepdims=True))     # [1,1]
        colv = jnp.sum(jnp.where(jj == j, D, 0.0), axis=1,
                       keepdims=True)                        # [bs,1]
        colv = jnp.where(ic > j, colv / d, 0.0)
        outer = _outer(colv, jnp.transpose(colv), D.dtype)
        D = D - jnp.where(jj > j, outer, 0.0)
        D = jnp.where((jj == j) & (ii > j), colv, D)
        D = jnp.where((jj == j) & (ii == j), d, D)
        return D

    return jnp.tril(lax.fori_loop(0, bs, col, D))


def _lu_diag(D, bs):
    """Unblocked LU (no pivoting) of a [bs, bs] block: unit-L strictly
    below, U on/above. Zero pivots keep their 0 on the diagonal (the
    elimination uses a safe substitute)."""
    ii = lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
    jj = lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
    ic = lax.broadcasted_iota(jnp.int32, (bs, 1), 0)         # [bs,1]
    jr = lax.broadcasted_iota(jnp.int32, (1, bs), 1)         # [1,bs]

    def col(j, D):
        d = jnp.sum(jnp.where((ii == j) & (jj == j), D, 0.0),
                    axis=1, keepdims=True).sum(
                        axis=0, keepdims=True)               # [1,1]
        ds = jnp.where(d == 0.0, 1.0, d)
        l = jnp.sum(jnp.where(jj == j, D, 0.0), axis=1,
                    keepdims=True)                           # [bs,1]
        l = jnp.where(ic > j, l / ds, 0.0)
        u = jnp.sum(jnp.where(ii == j, D, 0.0), axis=0,
                    keepdims=True)                           # [1,bs]
        u = jnp.where(jr > j, u, 0.0)
        D = D - jnp.where((ii > j) & (jj > j), _outer(l, u, D.dtype),
                          0.0)
        D = jnp.where((jj == j) & (ii > j), l, D)
        return D

    return lax.fori_loop(0, bs, col, D)


def _inv_lower(L, bs, unit: bool):
    """Inverse of a [bs, bs] lower-triangular block by forward
    substitution (row sweep, mask-select, all shapes 2-D)."""
    ii = lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
    jj = lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
    jr = lax.broadcasted_iota(jnp.int32, (1, bs), 1)         # [1,bs]

    def row(i, X):
        lrow = jnp.sum(jnp.where(ii == i, L, 0.0), axis=0,
                       keepdims=True)                        # [1,bs]
        d = jnp.sum(jnp.where((ii == i) & (jj == i), L, 0.0),
                    axis=1, keepdims=True).sum(
                        axis=0, keepdims=True)               # [1,1]
        if unit:
            d = jnp.ones_like(d)
        lrow_s = jnp.where(jr < i, lrow, 0.0)
        contrib = jax.lax.dot_general(                       # [1,bs]
            lrow_s, X, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=L.dtype)
        e = (jr == i).astype(L.dtype)
        newrow = (e - contrib) / d
        return jnp.where(ii == i, newrow, X)

    return lax.fori_loop(0, bs, row, jnp.zeros_like(L))


# ---------------------------------------------------------------------------
# blocked tile kernels
# ---------------------------------------------------------------------------

def _potrf_kernel(a_ref, out_ref, *, nb, bs):
    f32 = jnp.float32
    out_ref[:] = a_ref[:]
    ii_c = lax.broadcasted_iota(jnp.int32, (nb, 1), 0)       # [nb,1]
    jj_r = lax.broadcasted_iota(jnp.int32, (1, nb), 1)       # [1,nb]

    def blk(kb, _):
        j0 = pl.multiple_of(kb * bs, bs)
        D = out_ref[pl.ds(j0, bs), pl.ds(j0, bs)].astype(f32)
        L = _chol_diag(D, bs)
        out_ref[pl.ds(j0, bs), pl.ds(j0, bs)] = L.astype(out_ref.dtype)
        Li = _inv_lower(L, bs, unit=False)
        T = out_ref[:, pl.ds(j0, bs)].astype(f32)            # [nb, bs]
        Pn = jax.lax.dot_general(                            # T · Li^T
            T, Li, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=f32)
        below = ii_c >= j0 + bs                              # [nb,1]
        Pm = jnp.where(below, Pn, 0.0)
        out_ref[:, pl.ds(j0, bs)] = jnp.where(
            below, Pm, out_ref[:, pl.ds(j0, bs)].astype(f32)
        ).astype(out_ref.dtype)
        G = jax.lax.dot_general(                             # Pm · Pmᵀ
            Pm, Pm, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=f32)
        trail = jj_r >= j0 + bs                              # [1,nb]
        out_ref[:] = (out_ref[:].astype(f32)
                      - jnp.where(trail, G, 0.0)).astype(out_ref.dtype)
        return 0

    lax.fori_loop(0, nb // bs, blk, 0)
    low = ii_c >= jj_r
    out_ref[:] = jnp.where(low, out_ref[:],
                           jnp.zeros_like(out_ref[:]))


def _lu_nopiv_kernel(a_ref, out_ref, *, nb, bs):
    f32 = jnp.float32
    out_ref[:] = a_ref[:]
    ii_c = lax.broadcasted_iota(jnp.int32, (nb, 1), 0)       # [nb,1]
    jj_r = lax.broadcasted_iota(jnp.int32, (1, nb), 1)       # [1,nb]

    def blk(kb, _):
        j0 = pl.multiple_of(kb * bs, bs)
        D = out_ref[pl.ds(j0, bs), pl.ds(j0, bs)].astype(f32)
        D = _lu_diag(D, bs)
        out_ref[pl.ds(j0, bs), pl.ds(j0, bs)] = D.astype(out_ref.dtype)
        Lb = jnp.tril(D, -1) + jnp.eye(bs, dtype=f32)
        Ub = jnp.triu(D)
        dmask = (lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
                 == lax.broadcasted_iota(jnp.int32, (bs, bs), 1))
        Ub = jnp.where(dmask & (Ub == 0.0), 1.0, Ub)         # safe diag
        Ui = jnp.transpose(_inv_lower(jnp.transpose(Ub), bs, unit=False))
        Li = _inv_lower(Lb, bs, unit=True)
        # L21 = A[:, j0:j0+bs] · U⁻¹ (rows below the block)
        T = out_ref[:, pl.ds(j0, bs)].astype(f32)
        L21 = jax.lax.dot_general(
            T, Ui, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=f32)
        below = ii_c >= j0 + bs                              # [nb,1]
        L21 = jnp.where(below, L21, 0.0)
        out_ref[:, pl.ds(j0, bs)] = jnp.where(
            below, L21, out_ref[:, pl.ds(j0, bs)].astype(f32)
        ).astype(out_ref.dtype)
        # U12 = L⁻¹ · A[j0:j0+bs, :] (cols right of the block)
        R = out_ref[pl.ds(j0, bs), :].astype(f32)            # [bs, nb]
        U12 = jax.lax.dot_general(
            Li, R, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=f32)
        right = jj_r >= j0 + bs                              # [1,nb]
        U12 = jnp.where(right, U12, 0.0)
        out_ref[pl.ds(j0, bs), :] = jnp.where(
            right, U12, out_ref[pl.ds(j0, bs), :].astype(f32)
        ).astype(out_ref.dtype)
        # trailing: A22 −= L21 · U12
        G = jax.lax.dot_general(
            L21, U12, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=f32)
        out_ref[:] = (out_ref[:].astype(f32)
                      - jnp.where(right, G, 0.0)
                      ).astype(out_ref.dtype)
        return 0

    lax.fori_loop(0, nb // bs, blk, 0)


@partial(jax.jit, static_argnames=("interpret",))
def potrf_tile_pallas(a: jax.Array, interpret: bool = False) -> jax.Array:
    """Lower Cholesky of one [nb, nb] tile, fully VMEM-resident."""
    nb = a.shape[0]
    assert tile_vmem_bytes(nb) <= _PANEL_VMEM_BUDGET
    return pl.pallas_call(
        partial(_potrf_kernel, nb=nb, bs=min(_BS, nb)),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_PANEL_VMEM_BUDGET),
    )(a)


@partial(jax.jit, static_argnames=("interpret",))
def lu_nopiv_tile_pallas(a: jax.Array, interpret: bool = False):
    """Unpivoted LU of one [nb, nb] tile (unit-L/U compact) + zero-pivot
    count, fully VMEM-resident. Zero pivots keep their 0 on the U
    diagonal (trailing updates use a safe substitute), so the count is
    read off the result."""
    nb = a.shape[0]
    assert tile_vmem_bytes(nb) <= _PANEL_VMEM_BUDGET
    out = pl.pallas_call(
        partial(_lu_nopiv_kernel, nb=nb, bs=min(_BS, nb)),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_PANEL_VMEM_BUDGET),
    )(a)
    info = jnp.sum(jnp.diagonal(out) == 0).astype(jnp.int32)
    return out, info


# ---------------------------------------------------------------------------
# fused panel PLU: pivot search + row swap + rank-1 update in VMEM
# ---------------------------------------------------------------------------

def _panel_plu_kernel(a_ref, out_ref, piv_ref, info_ref, *, h, w):
    dt = out_ref.dtype
    out_ref[:] = a_ref[:]
    piv_ref[:] = jnp.zeros((1, w), jnp.int32)
    info_ref[:] = jnp.zeros((1, 1), jnp.int32)
    ii = lax.broadcasted_iota(jnp.int32, (h, 1), 0)      # [h,1] rows
    jr = lax.broadcasted_iota(jnp.int32, (1, w), 1)      # [1,w] cols
    jjm = lax.broadcasted_iota(jnp.int32, (h, w), 1)     # [h,w] cols

    def col(j, _):
        A = out_ref[:]
        colv = jnp.sum(jnp.where(jjm == j, A, 0), axis=1,
                       keepdims=True)                    # [h,1]
        score = jnp.where(ii >= j, jnp.abs(colv),
                          jnp.full((h, 1), -1, dt))
        mx = jnp.max(score)
        # max + index-min: the Mosaic-stable pivot select (argmax
        # variants fail TPU lowering); ties → lowest row, LAPACK's
        # isamax semantics, so ipiv stays bitwise-compatible
        r = jnp.min(jnp.where(score >= mx, ii, h))
        rowj = jnp.sum(jnp.where(ii == j, A, 0), axis=0,
                       keepdims=True)                    # [1,w]
        rowr = jnp.sum(jnp.where(ii == r, A, 0), axis=0,
                       keepdims=True)
        A = jnp.where(ii == j, rowr, jnp.where(ii == r, rowj, A))
        # column j after the swap, without a second full sweep
        vj = jnp.sum(jnp.where(ii == j, colv, 0))
        vr = jnp.sum(jnp.where(ii == r, colv, 0))        # pivot value
        colv = jnp.where(ii == j, vr, jnp.where(ii == r, vj, colv))
        info_ref[:] = info_ref[:] + (vr == 0).astype(jnp.int32)
        safe = jnp.where(vr == 0, jnp.ones_like(vr), vr)
        lcol = jnp.where(ii > j, colv / safe,
                         jnp.zeros((h, 1), dt))          # multipliers
        urow = jnp.where(jr > j, rowr, jnp.zeros((1, w), dt))
        A = A - _outer(lcol, urow, dt)
        A = jnp.where((jjm == j) & (ii > j), lcol, A)
        out_ref[:] = A
        piv_ref[:] = jnp.where(jr == j, r, piv_ref[:])
        return 0

    lax.fori_loop(0, min(h, w), col, 0)


@partial(jax.jit, static_argnames=("interpret",))
def panel_plu_pallas(a: jax.Array, interpret: bool = False):
    """Fused panel PLU of a rows-at-origin [h, w] panel: the in-VMEM
    pivot search, row swap and rank-1 update run in one pallas_call.

    Returns (lu, piv, info): L (unit diag implicit) strictly below /
    U on and above the diagonal; ``piv[j]`` = row swapped with row j
    at elimination step j (LAPACK sequential-swap ipiv, matching
    ``lax.linalg.lu``'s pivots vector bitwise for the same pivot
    choices); info = zero-pivot count."""
    h, w = a.shape
    assert panel_plu_vmem_bytes(h, w) <= _PANEL_VMEM_BUDGET
    lu, piv, info = pl.pallas_call(
        partial(_panel_plu_kernel, h=h, w=w),
        out_shape=(jax.ShapeDtypeStruct((h, w), a.dtype),
                   jax.ShapeDtypeStruct((1, w), jnp.int32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)),
        input_output_aliases={0: 0},
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_PANEL_VMEM_BUDGET),
    )(a)
    return lu, piv[0], info[0, 0]


# ---------------------------------------------------------------------------
# blocked triangular solves against a factored panel (bf16_6x pinned)
# ---------------------------------------------------------------------------

def _panel_prec():
    """Panels/trsm are pinned to the full-precision MXU passes
    (bf16_6x ⇔ HIGHEST for f32 operands) per the precision policy."""
    from .precision import panel_precision
    return panel_precision()


def _trsm_ll_kernel(l_ref, b_ref, x_ref, *, n, bs, unit):
    dt = x_ref.dtype
    x_ref[:] = b_ref[:]
    ii = lax.broadcasted_iota(jnp.int32, (n, 1), 0)

    def blk(kb, _):
        j0 = pl.multiple_of(kb * bs, bs)
        Lkk = l_ref[pl.ds(j0, bs), pl.ds(j0, bs)]
        Li = _inv_lower(Lkk, bs, unit=unit)
        Xk = jax.lax.dot_general(
            Li, x_ref[pl.ds(j0, bs), :],
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=_panel_prec(), preferred_element_type=dt)
        x_ref[pl.ds(j0, bs), :] = Xk
        Lcol = jnp.where(ii >= j0 + bs, l_ref[:, pl.ds(j0, bs)],
                         jnp.zeros((n, bs), dt))
        upd = jax.lax.dot_general(
            Lcol, Xk, dimension_numbers=(((1,), (0,)), ((), ())),
            precision=_panel_prec(), preferred_element_type=dt)
        x_ref[:] = x_ref[:] - upd
        return 0

    lax.fori_loop(0, n // bs, blk, 0)


def _trsm_rlt_kernel(l_ref, b_ref, x_ref, *, n, bs, unit):
    dt = x_ref.dtype
    x_ref[:] = b_ref[:]
    ii = lax.broadcasted_iota(jnp.int32, (n, 1), 0)

    def blk(kb, _):
        j0 = pl.multiple_of(kb * bs, bs)
        Lkk = l_ref[pl.ds(j0, bs), pl.ds(j0, bs)]
        Li = _inv_lower(Lkk, bs, unit=unit)
        Xk = jax.lax.dot_general(                        # Bk · Lkk⁻ᵀ
            x_ref[:, pl.ds(j0, bs)], Li,
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=_panel_prec(), preferred_element_type=dt)
        x_ref[:, pl.ds(j0, bs)] = Xk
        Lblk = jnp.where(ii >= j0 + bs, l_ref[:, pl.ds(j0, bs)],
                         jnp.zeros((n, bs), dt))
        upd = jax.lax.dot_general(                       # Xk · Lblkᵀ
            Xk, Lblk, dimension_numbers=(((1,), (1,)), ((), ())),
            precision=_panel_prec(), preferred_element_type=dt)
        x_ref[:] = x_ref[:] - upd
        return 0

    lax.fori_loop(0, n // bs, blk, 0)


@partial(jax.jit, static_argnames=("unit", "interpret"))
def trsm_left_lower_pallas(l: jax.Array, b: jax.Array,
                           unit: bool = False,
                           interpret: bool = False) -> jax.Array:
    """X = L⁻¹·B, blocked forward substitution against the panel's
    [n, n] lower factor (the getrf U-row update), fully VMEM-resident;
    MXU passes pinned to panel precision (bf16_6x)."""
    n, m = b.shape
    assert trsm_vmem_bytes(n, m) <= _PANEL_VMEM_BUDGET
    return pl.pallas_call(
        partial(_trsm_ll_kernel, n=n, bs=min(_BS, n), unit=unit),
        out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_PANEL_VMEM_BUDGET),
    )(l, b)


@partial(jax.jit, static_argnames=("unit", "interpret"))
def trsm_right_lower_t_pallas(l: jax.Array, b: jax.Array,
                              unit: bool = False,
                              interpret: bool = False) -> jax.Array:
    """X = B·L⁻ᵀ, blocked column substitution (the potrf L-column
    panel update), fully VMEM-resident; MXU passes pinned to panel
    precision (bf16_6x)."""
    m, n = b.shape
    assert trsm_vmem_bytes(n, m) <= _PANEL_VMEM_BUDGET
    return pl.pallas_call(
        partial(_trsm_rlt_kernel, n=n, bs=min(_BS, n), unit=unit),
        out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_PANEL_VMEM_BUDGET),
    )(l, b)


# ---------------------------------------------------------------------------
# rank-k trailing tail (the sub-nb remainder XLA pads to a lane tile)
# ---------------------------------------------------------------------------

def _rank_k_kernel(c_ref, a_ref, b_ref, o_ref, *, alpha, beta, prec):
    dt = o_ref.dtype
    acc = jax.lax.dot_general(
        a_ref[:], b_ref[:], dimension_numbers=(((1,), (0,)), ((), ())),
        precision=prec, preferred_element_type=dt)
    o_ref[:] = alpha * acc + beta * c_ref[:]


@partial(jax.jit, static_argnames=("alpha", "beta", "tier", "interpret"))
def rank_k_tail_pallas(c: jax.Array, a: jax.Array, b: jax.Array,
                       alpha: float = -1.0, beta: float = 1.0,
                       tier: str | None = None,
                       interpret: bool = False) -> jax.Array:
    """alpha·A·B + beta·C with k = a.shape[1] below one lane tile —
    the sub-nb trailing remainder XLA pads to 128. The contraction
    runs at the requested precision tier (trailing update policy,
    unlike the pinned trsm/panel kernels)."""
    from .precision import trailing_dot_kwargs
    m, k = a.shape
    n = c.shape[1]
    assert rank_k_vmem_bytes(m, n, k) <= _PANEL_VMEM_BUDGET
    prec = trailing_dot_kwargs(tier, a.dtype).get("precision")
    return pl.pallas_call(
        partial(_rank_k_kernel, alpha=alpha, beta=beta, prec=prec),
        out_shape=jax.ShapeDtypeStruct(c.shape, c.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_PANEL_VMEM_BUDGET),
    )(c, a, b)
