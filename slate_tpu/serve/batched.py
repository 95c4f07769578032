"""Batched drivers: vmapped-over-leading-axis factorizations/solves.

Serving traffic is thousands of small/medium solves, and on TPUs that
workload is amortized the way inference kernels amortize it — stack
the instances along a leading axis and run ONE device program per
(routine, shape bucket, batch rung, precision tier).  This is the
batched-BLAS role cuBLAS plays in the reference's L3 (PAPER.md): the
single-matrix drivers distribute one large problem across the mesh;
these kernels keep each problem on-device-local and parallelize across
problems instead.

Each kernel is ``jax.vmap`` of a dense blocked core that takes the
single-matrix fast paths' steps (``linalg.potrf._potrf_dense_loop``,
the dense twin of the one-chip ``potrf``'s loop on stored tiles; the
LU core mirrors ``linalg.getrf._getrf_dense_1dev``'s partial-pivot
loop), so per-instance semantics are preserved exactly:

* pivoting is per-instance — every batch member runs its own pivot
  search (``lax.linalg.lu`` vmaps the panel factorization), and the
  returned permutation is per-member;
* SPD handling is per-instance — ``finite_guard`` info codes are
  per-member scalars, so one non-SPD / singular / NaN instance reports
  through its own ``info`` slot while its batchmates' results remain
  untouched (the guards zero-fill poison so it cannot spread);
* ``TrailingPrecision`` tiers thread through ``trailing_dot_kwargs``
  exactly as in the single-matrix paths (trace-time static, so the
  tier is part of the executable key).

Every entry point routes through ``cache.cached_jit`` — the batch
size and bucket order are part of the traced shape and the tier/nb
are static arguments, so the executable cache holds one program per
(routine, bucket, batch rung, tier) and ``python -m slate_tpu.serve
warmup`` can AOT-fill the whole cross product.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .. import obs
from ..cache.jitcache import cached_jit
from ..internal.precision import resolve_tier, trailing_dot_kwargs
from ..internal.tile_kernels import _factor_dtype
from ..robust import guards


def _check_stack(a, b=None):
    if a.ndim != 3 or a.shape[1] != a.shape[2]:
        raise ValueError(
            f"batched driver expects a [batch, n, n] stack, got {a.shape}")
    if b is not None:
        if b.ndim != 3 or b.shape[0] != a.shape[0] or b.shape[1] != a.shape[1]:
            raise ValueError(
                f"rhs stack {b.shape} does not match matrix stack {a.shape}"
                " (expected [batch, n, nrhs])")


def _resolve_nb(n: int, nb: int | None) -> int:
    from ..cache import buckets
    nb = nb or buckets.default_nb(n)
    nb = min(nb, n)
    if n % nb:
        raise ValueError(
            f"batched drivers need nb | n (bucket orders are tile "
            f"multiples); got n={n}, nb={nb}")
    return nb


def _count(routine: str, a):
    obs.count("serve.batched_dispatch", routine=routine,
              bucket=str(a.shape[1]), b=str(a.shape[0]))


# ---------------------------------------------------------------------------
# cores (single instance, dense [n, n] — vmapped by the public wrappers)
# ---------------------------------------------------------------------------

def _potrf_one(a, nb, tier):
    """Blocked Cholesky on one dense [n, n]: the unrolled steps of the
    single-matrix fast path (first-block info convention)."""
    from ..linalg.potrf import _potrf_dense_loop
    n = a.shape[0]
    l, info = _potrf_dense_loop(a, nb, n, n, tier=tier)
    return jnp.tril(l), info


def _safe_lower(l):
    """Cholesky factor with zero diagonal entries (a guarded failure's
    zero-fill) replaced by 1 so the triangular solve stays finite; the
    nonzero ``info`` still owns the failure report."""
    d = jnp.diagonal(l)
    return l + jnp.diag(jnp.where(d == 0, jnp.ones_like(d),
                                  jnp.zeros_like(d)))


def _potrs_one(l, b, cplx):
    fd = _factor_dtype(l.dtype)
    ls = _safe_lower(l).astype(fd)
    y = lax.linalg.triangular_solve(ls, b.astype(fd), left_side=True,
                                    lower=True)
    x = lax.linalg.triangular_solve(ls, y, left_side=True, lower=True,
                                    transpose_a=True, conjugate_a=cplx)
    return guards.zero_nonfinite(x.astype(b.dtype))


def _getrf_one(a, nb, tier):
    """Blocked partial-pivot LU on one dense [n, n] — the unrolled-path
    loop of ``_getrf_dense_1dev`` on a plain array: per-panel native
    ``lax.linalg.lu``, one row-swap gather per panel, zero-pivot COUNT
    info.  Returns ``(lu, perm, info)`` where ``perm`` is the full row
    permutation (``x = solve(a[perm])`` ordering) — per-instance under
    vmap, so every batch member keeps its own pivot order."""
    n = a.shape[0]
    fd = _factor_dtype(a.dtype)
    pk = trailing_dot_kwargs(tier, a.dtype)
    info = jnp.zeros((), jnp.int32)
    gperm = jnp.arange(n, dtype=jnp.int32)
    for k in range(n // nb):
        r0 = k * nb
        pan = a[r0:, r0:r0 + nb]
        lu, _, perm = lax.linalg.lu(pan.astype(fd))
        # containment: a NaN/Inf panel zero-fills (poison cannot reach
        # batchmates or later panels) and counts into info alongside
        # any exact zero pivots
        lu, pbad = guards.finite_guard(lu.astype(a.dtype),
                                       jnp.zeros((), jnp.int32), 1)
        a = a.at[r0:, r0:r0 + nb].set(lu)
        if r0:
            a = a.at[r0:, :r0].set(jnp.take(a[r0:, :r0], perm, axis=0))
        gperm = gperm.at[r0:].set(jnp.take(gperm[r0:], perm))
        dg = jnp.diagonal(lu[:nb, :nb])
        info = info + jnp.sum(dg == 0).astype(jnp.int32) + pbad
        if r0 + nb < n:
            right = jnp.take(a[r0:, r0 + nb:], perm, axis=0)
            unit = (jnp.tril(lu[:nb, :nb], -1)
                    + jnp.eye(nb, dtype=a.dtype))
            urow = lax.linalg.triangular_solve(
                unit.astype(fd), right[:nb].astype(fd), left_side=True,
                lower=True, unit_diagonal=True).astype(a.dtype)
            a = a.at[r0:r0 + nb, r0 + nb:].set(urow)
            trail = right[nb:] - jnp.matmul(lu[nb:, :nb], urow, **pk)
            a = a.at[r0 + nb:, r0 + nb:].set(guards.zero_nonfinite(trail))
    return a, gperm, info


def _getrs_one(lu, perm, b):
    n = lu.shape[0]
    fd = _factor_dtype(lu.dtype)
    pb = jnp.take(b, perm, axis=0).astype(fd)
    unit_l = jnp.tril(lu, -1).astype(fd) + jnp.eye(n, dtype=fd)
    y = lax.linalg.triangular_solve(unit_l, pb, left_side=True,
                                    lower=True, unit_diagonal=True)
    u = jnp.triu(lu)
    d = jnp.diagonal(u)
    # singular U: solve against a unit-substituted diagonal so this
    # member's NaNs never materialize; its nonzero info flags the result
    safe_u = (u + jnp.diag(jnp.where(d == 0, jnp.ones_like(d),
                                     jnp.zeros_like(d)))).astype(fd)
    x = lax.linalg.triangular_solve(safe_u, y, left_side=True,
                                    lower=False)
    return guards.zero_nonfinite(x.astype(b.dtype))


# ---------------------------------------------------------------------------
# vmapped + cached_jit program bodies
# ---------------------------------------------------------------------------

def _potrf_batch(a, nb, tier):
    return jax.vmap(lambda x: _potrf_one(x, nb, tier))(a)


def _posv_batch(a, b, nb, tier):
    cplx = jnp.issubdtype(a.dtype, jnp.complexfloating)

    def one(ai, bi):
        l, info = _potrf_one(ai, nb, tier)
        return _potrs_one(l, bi, cplx), l, info

    return jax.vmap(one)(a, b)


def _getrf_batch(a, nb, tier):
    return jax.vmap(lambda x: _getrf_one(x, nb, tier))(a)


def _gesv_batch(a, b, nb, tier):
    def one(ai, bi):
        lu, perm, info = _getrf_one(ai, nb, tier)
        return _getrs_one(lu, perm, bi), lu, perm, info

    return jax.vmap(one)(a, b)


def _trsm_batch(a, b, side, lower, trans, unit, cplx):
    fd = _factor_dtype(a.dtype)

    def one(ai, bi):
        return lax.linalg.triangular_solve(
            ai.astype(fd), bi.astype(fd), left_side=(side == "left"),
            lower=lower, transpose_a=trans, conjugate_a=(trans and cplx),
            unit_diagonal=unit).astype(b.dtype)

    return jax.vmap(one)(a, b)


_potrf_jit = cached_jit(_potrf_batch, routine="serve.potrf",
                        static_argnames=("nb", "tier"))
_posv_jit = cached_jit(_posv_batch, routine="serve.posv",
                       static_argnames=("nb", "tier"))
_getrf_jit = cached_jit(_getrf_batch, routine="serve.getrf",
                        static_argnames=("nb", "tier"))
_gesv_jit = cached_jit(_gesv_batch, routine="serve.gesv",
                       static_argnames=("nb", "tier"))
_trsm_jit = cached_jit(_trsm_batch, routine="serve.trsm",
                       static_argnames=("side", "lower", "trans",
                                        "unit", "cplx"))


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def batched_potrf(a, opts=None, *, nb: int | None = None):
    """Cholesky-factor a ``[batch, n, n]`` stack (lower).  Returns
    ``(l, info)`` with per-instance first-block info codes."""
    a = jnp.asarray(a)
    _check_stack(a)
    nb = _resolve_nb(a.shape[1], nb)
    _count("potrf", a)
    return _potrf_jit(a, nb=nb, tier=resolve_tier(opts))


def batched_posv(a, b, opts=None, *, nb: int | None = None):
    """Solve ``a[i] @ x[i] = b[i]`` for an SPD stack.  Returns
    ``(x, l, info)``; a failed member's ``x`` slot is zero-filled and
    its ``info`` nonzero, with batchmates unaffected."""
    a, b = jnp.asarray(a), jnp.asarray(b)
    _check_stack(a, b)
    nb = _resolve_nb(a.shape[1], nb)
    _count("posv", a)
    return _posv_jit(a, b, nb=nb, tier=resolve_tier(opts))


def batched_getrf(a, opts=None, *, nb: int | None = None):
    """Partial-pivot LU of a ``[batch, n, n]`` stack.  Returns
    ``(lu, perm, info)`` — ``perm[i]`` is instance i's full row
    permutation, ``info[i]`` its zero-pivot count."""
    a = jnp.asarray(a)
    _check_stack(a)
    nb = _resolve_nb(a.shape[1], nb)
    _count("getrf", a)
    return _getrf_jit(a, nb=nb, tier=resolve_tier(opts))


def batched_gesv(a, b, opts=None, *, nb: int | None = None):
    """General solve via per-instance partial-pivot LU.  Returns
    ``(x, lu, perm, info)``."""
    a, b = jnp.asarray(a), jnp.asarray(b)
    _check_stack(a, b)
    nb = _resolve_nb(a.shape[1], nb)
    _count("gesv", a)
    return _gesv_jit(a, b, nb=nb, tier=resolve_tier(opts))


def batched_trsm(a, b, *, side: str = "left", lower: bool = True,
                 trans: bool = False, unit: bool = False):
    """Triangular solve over a leading batch axis (one executable per
    (side/uplo/trans/unit, bucket, batch rung))."""
    a, b = jnp.asarray(a), jnp.asarray(b)
    if side not in ("left", "right"):
        raise ValueError(f"side must be 'left' or 'right', got {side!r}")
    _check_stack(a, b if side == "left" else None)
    _count("trsm", a)
    cplx = bool(jnp.issubdtype(a.dtype, jnp.complexfloating))
    return _trsm_jit(a, b, side=side, lower=lower, trans=trans,
                     unit=unit, cplx=cplx)


def san_cases(grid=None, opts=None, n=32, nb=16, batch=2):
    """slatesan sweep entries for the serving surface: the batched
    potrf and gesv executables (see tools/slatesan).  ``grid`` is
    accepted for signature parity with the linalg drivers; the
    batched path is single-device vmap and ignores it."""
    import numpy as np

    def run_potrf():
        rng = np.random.default_rng(12)
        a = rng.standard_normal((batch, n, n)).astype(np.float32)
        a = a @ a.transpose(0, 2, 1) + n * np.eye(n, dtype=np.float32)
        l, info = batched_potrf(a, opts, nb=nb)
        return info.block_until_ready()

    def run_gesv():
        rng = np.random.default_rng(13)
        a = rng.standard_normal((batch, n, n)).astype(np.float32)
        a += n * np.eye(n, dtype=np.float32)
        b = rng.standard_normal((batch, n, 2)).astype(np.float32)
        x, _, _, info = batched_gesv(a, b, opts, nb=nb)
        return info.block_until_ready()

    return [("serve.potrf", run_potrf), ("serve.gesv", run_gesv)]
