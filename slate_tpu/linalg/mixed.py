"""Mixed-precision solvers with iterative refinement.

Reference: src/gesv_mixed.cc:20-47 (factor in single, refine residual
in double, fall back to a full-precision factorization if IR stalls
after itermax=30), src/posv_mixed.cc, src/gesv_mixed_gmres.cc:391 and
src/posv_mixed_gmres.cc (GMRES-IR, preconditioned by the low-precision
factors).

TPU precision ladder (SURVEY §2.6): f64/c128 inputs lower STORAGE to
f32/c64 like the reference's double/single pair (f64 ops are emulated
on TPU — supported for parity, not for speed). f32/c64 inputs instead
keep full-precision storage and factor with **bf16_3x trailing
updates** (internal/precision.py): the O(n³) gemm/syrk work runs the
3-pass bf16 MXU split (~2× the f32-equivalent 6-pass throughput,
per-dot eps ≈ 2⁻¹⁸) while panels and triangular solves stay at full
f32 accuracy — so IR recovers f32-level backward error in O(1)
iterations instead of fighting bf16 storage rounding. The IR loop runs
on the host driving jitted distributed ops, exactly like the
reference's driver loop around internal kernels.

All four solvers return ``(X, iters, info)``. ``iters`` follows LAPACK
``dsgesv`` and the reference: ≥ 0 — the refinement met the stop
criterion after that many steps (IR corrections; Arnoldi steps over
all restarts for GMRES-IR); < 0 — it did not, and X is the
full-precision fallback's (or, with ``Option.UseFallbackSolver``
false, the last iterate): ``-(MaxIterations + 1)`` after the steps
were spent, ``-3`` when the low factor broke down (a residual that is
not finite). ``info`` is the low factorization's, or the fallback's
when that ran.

What a call reports (docs/observability.md): a root span
``slate.<routine>`` (``routine``, ``n``, ``nb``, ``nrhs``, ``grid``,
``tier_lo``; at its end ``outer``, ``inner``, ``converged``,
``fallback``), below it ``mixed.factor_lo``, ``mixed.solve_lo``
(``phase``), ``mixed.residual``, ``mixed.matvec``, ``mixed.cycle``
(``outer``, ``steps``), ``mixed.fallback``; every blocking read is an
``obs.sync_read`` named ``mixed.<what>``; the counters are
:data:`COUNTERS`.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from .. import obs
from ..matrix import Matrix, HermitianMatrix
from ..types import Norm, Option, get_option, Op
from ..ops.blas import gemm
from ..ops.norms import norm
from ..utils import trace


_LOWER = {jnp.dtype(jnp.float64): jnp.float32,
          jnp.dtype(jnp.float32): jnp.bfloat16,
          jnp.dtype(jnp.complex128): jnp.complex64}

# what a refinement counts (``/metrics``): ``mixed.iters{routine,kind}``
# (kind = ``outer``: residual checks that asked for more, ``inner``:
# corrections / Arnoldi steps), ``mixed.fallback{routine}`` (calls
# answered by the full-precision solver), ``mixed.solve_lo{routine,
# pivots}`` (applications of the low factors, by the program that
# applies their row order: ``order_gather`` / ``swap_sim`` / ``dist``,
# ``none`` for Cholesky)
COUNTERS = ("mixed.iters", "mixed.fallback", "mixed.solve_lo")

GMRES_RESTART = 30


def _lo_plan(dt, opts):
    """(factor_dtype, factor_opts) for the low-precision leg.

    f64/c128 → lower storage (f32/c64), caller's opts unchanged.
    f32/c64 → SAME storage dtype, opts extended with
    ``Option.TrailingPrecision: "bf16_3x"`` (unless the caller pinned a
    tier) so the factorization's trailing updates take the 3-pass bf16
    MXU path while panels/solves stay full precision.
    """
    d = jnp.dtype(dt)
    if d in (jnp.dtype(jnp.float64), jnp.dtype(jnp.complex128)):
        return _LOWER[d], opts
    lo_opts = dict(opts) if opts else {}
    lo_opts.setdefault(Option.TrailingPrecision, "bf16_3x")
    return d, lo_opts


def _hi_opts(opts):
    """The caller's opts for the full-precision fallback: a pinned
    ``Option.TrailingPrecision`` names the LOW leg's tier
    (:func:`_lo_plan`), so it is not passed on — the fallback solves at
    the working tier."""
    return {k: v for k, v in (opts or {}).items()
            if k != Option.TrailingPrecision}


class _Legs(NamedTuple):
    """What one routine gives the shared loops. ``factor()`` → the low
    factors (its ``info`` goes to ``info_box``); ``solve(factors, R)``
    → their application to R, in B's dtype; ``full(B)`` → the
    full-precision solver's ``(X, info)``; ``pivots(factors, R)`` →
    the ``mixed.solve_lo`` counter's ``pivots`` label."""
    routine: str
    factor: object
    solve: object
    full: object
    pivots: object


class _Outcome(NamedTuple):
    X: Matrix
    outer: int          # residual checks that did not pass
    inner: int          # corrections (IR) / Arnoldi steps (GMRES-IR)
    converged: bool
    broke_down: bool    # a residual that is not finite


def _read(site: str, x) -> float:
    """One blocking device→host read of a real scalar."""
    return obs.sync_read("mixed." + site, float, x)


def _stop_factor(A, B) -> float:
    """``cte`` of the reference's stop criterion (gesv_mixed.cc,
    gesv_mixed_gmres.cc: ``iterRefConverged``): refinement ends when
    ‖R‖max ≤ ‖X‖max · cte, cte = ‖A‖∞ · ε · √n in the working
    precision (the reference's default tolerance). The reference tests
    each column of R against its column of X; here the two max-norms
    are taken over the whole of R and X, which is the same thing for
    the one right-hand side GMRES-IR is defined for there and never
    stricter than it otherwise."""
    eps = float(jnp.finfo(B.dtype).eps)
    return _read("anorm", norm(Norm.Inf, A)) * eps * math.sqrt(A.n)


def _residual(A, X, B):
    """R = B − A·X at the working tier (no opts: a pinned
    ``TrailingPrecision`` is the low leg's)."""
    with trace.block("mixed.residual"):
        return gemm(-1.0, A, X, 1.0, _copy(B))


def _solve_lo(legs, factors, R, phase):
    obs.count("mixed.solve_lo", 1, routine=legs.routine,
              pivots=legs.pivots(factors, R))
    with trace.block("mixed.solve_lo", phase=phase):
        return legs.solve(factors, R)


def _ir_loop(A, B, legs, opts) -> _Outcome:
    """Classical iterative refinement (reference gesv_mixed.cc DAG)."""
    itermax = get_option(opts, Option.MaxIterations, 30)
    cte = _stop_factor(A, B)
    with trace.block("mixed.factor_lo"):
        factors = legs.factor()
    X = _solve_lo(legs, factors, B, "initial")
    it = 0
    while True:
        R = _residual(A, X, B)
        rnorm = _read("rnorm", norm(Norm.Max, R))
        xnorm = _read("xnorm", norm(Norm.Max, X))
        if rnorm <= xnorm * cte:
            return _Outcome(X, it, it, True, False)
        if not math.isfinite(rnorm) or it >= itermax:
            return _Outcome(X, it, it, False, not math.isfinite(rnorm))
        D = _solve_lo(legs, factors, R, "update")
        X = _axpy(1.0, D, X)
        it += 1


def _copy(B):
    return B._replace(data=B.data)


def _axpy(alpha, D, X):
    from ..ops.elementwise import add
    return add(alpha, D, 1.0, X)


# ---------------------------------------------------------------------------
# GMRES-IR (reference src/gesv_mixed_gmres.cc / posv_mixed_gmres.cc):
# right-preconditioned restarted GMRES in working precision with the
# low-precision factorization as the preconditioner.
# ---------------------------------------------------------------------------

def _rotation(a, b):
    """Givens (c, s) with [c s; −s̄ c]·[a; b] = [r; 0], c real
    (LAPACK ``rotg``'s convention; b is real here: a vector's norm)."""
    if b == 0:
        return 1.0, 0.0
    if a == 0:
        return 0.0, 1.0
    scale = math.hypot(abs(a), abs(b))
    return abs(a) / scale, (a / abs(a)) * b / scale


def _rotate(c, s, x, y):
    return c * x + s * y, -np.conj(s) * x + c * y


def _gmres_ir(A, B, legs, opts, restart: int = GMRES_RESTART) -> _Outcome:
    """GMRES-IR as the reference runs it: solve with the low factors,
    then cycles of right-preconditioned GMRES on A·M⁻¹ in the working
    precision, the Hessenberg matrix reduced by Givens rotations as it
    grows, the inner loop left as soon as the rotated residual estimate
    meets the stop criterion, the update formed from the steps taken.
    ``Option.MaxIterations`` bounds the Arnoldi steps over all cycles
    (the reference's ``iter``), a cycle has at most ``restart`` of
    them (its 30).

    Stop criterion, as the reference's: between cycles ‖R‖max ≤
    ‖X‖max·cte on the true residual (:func:`_stop_factor`); inside a
    cycle the same bound on the estimate |g[j+1]|, a 2-norm (so never
    looser), against the ‖X‖max of the cycle's start.

    Departures that stay, each because taking it away is a change of
    its own (PERF.md section 7): (a) the basis is orthogonalised by
    modified Gram–Schmidt, one inner product and one blocking read at
    a time, where the reference runs classical Gram–Schmidt twice with
    two ``gemm``; (b) only V is stored and the update is one more
    application of the factors, M⁻¹·(V·y), where the reference also
    stores W = M⁻¹·V and forms X += W·y; (c) H and g live on the host
    in f64, not in a tile in the working precision; (d) a B of several
    columns is taken as one long vector (the Frobenius inner product),
    where the reference refuses it ("block-GMRES is not yet
    supported").
    """
    itermax = get_option(opts, Option.MaxIterations, 30)
    cte = _stop_factor(A, B)
    cplx = jnp.issubdtype(B.dtype, jnp.complexfloating)
    as_scalar = complex if cplx else float
    hdt = np.complex128 if cplx else np.float64

    def matvec(V):
        with trace.block("mixed.matvec"):
            out = Matrix.zeros(A.m, V.n, A.nb, A.grid, dtype=B.dtype)
            return gemm(1.0, A, V, 0.0, out)

    with trace.block("mixed.factor_lo"):
        factors = legs.factor()
    X = _solve_lo(legs, factors, B, "initial")
    outer = inner = 0
    while True:
        R = _residual(A, X, B)
        rnorm = _read("rnorm", norm(Norm.Max, R))
        xnorm = _read("xnorm", norm(Norm.Max, X))
        if rnorm <= xnorm * cte:
            return _Outcome(X, outer, inner, True, False)
        if not math.isfinite(rnorm) or inner >= itermax:
            return _Outcome(X, outer, inner, False,
                            not math.isfinite(rnorm))
        with trace.block("mixed.cycle", outer=outer) as cycle:
            beta = _read("beta", norm(Norm.Fro, R))
            Vs = [scaled(R, 1.0 / beta)]
            H = np.zeros((restart, restart), hdt)   # R of H's QR
            g = np.zeros(restart + 1, hdt)
            g[0] = beta
            rots = []
            steps = 0
            for j in range(min(restart, itermax - inner)):
                # Arnoldi on the preconditioned operator A·M⁻¹
                Z = _solve_lo(legs, factors, Vs[j], "arnoldi")
                W = matvec(Z)
                h = np.zeros(j + 2, hdt)
                for i in range(j + 1):
                    h[i] = obs.sync_read("mixed.h", as_scalar,
                                         _dot(Vs[i], W))
                    W = _axpy(-h[i], Vs[i], W)
                hn = _read("hn", norm(Norm.Fro, W))
                h[j + 1] = hn
                for i, (c, s) in enumerate(rots):
                    h[i], h[i + 1] = _rotate(c, s, h[i], h[i + 1])
                c, s = _rotation(as_scalar(h[j]), hn)
                rots.append((c, s))
                h[j], _ = _rotate(c, s, h[j], h[j + 1])
                g[j], g[j + 1] = _rotate(c, s, g[j], g[j + 1])
                H[:j + 1, j] = h[:j + 1]
                steps = j + 1
                # |g[j+1]| is the 2-norm of the cycle's residual after
                # j+1 steps; hn == 0: the Krylov space is exhausted
                if abs(g[j + 1]) <= xnorm * cte or not hn > 0.0:
                    break
                Vs.append(scaled(W, 1.0 / hn))
            cycle.label(steps=steps)
            # H is upper triangular by now; lstsq so that an exhausted
            # Krylov space (a zero on its diagonal) still has an answer
            y = np.linalg.lstsq(H[:steps, :steps], g[:steps],
                                rcond=None)[0]
            Zsum = scaled(Vs[0], as_scalar(y[0]))
            for i in range(1, steps):
                Zsum = _axpy(as_scalar(y[i]), Vs[i], Zsum)
            D = _solve_lo(legs, factors, Zsum, "update")
            X = _axpy(1.0, D, X)
        outer += 1
        inner += steps


def scaled(V, s):
    return V._replace(data=V.data * s)


def _dot(U, V):
    """⟨U, V⟩ (Frobenius inner product) of two same-shape matrices."""
    return jnp.sum(jnp.conj(U.data) * V.data)


# ---------------------------------------------------------------------------
# the four drivers: one root span, one fallback rule
# ---------------------------------------------------------------------------

def _refine(loop, A, B, legs, tier_lo, opts, info_box):
    """Run ``loop`` (:func:`_ir_loop` / :func:`_gmres_ir`) under the
    root span ``slate.<routine>`` and apply the reference's fallback
    rule to what it reports. Returns ``(X, iters, info)``."""
    routine = legs.routine
    with trace.block("slate." + routine, routine=routine, n=A.n, nb=A.nb,
                     nrhs=B.n, grid=f"{A.grid.p}x{A.grid.q}",
                     tier_lo=tier_lo) as root:
        out = loop(A, B, legs, opts)
        X = out.X
        obs.count("mixed.iters", out.outer, routine=routine, kind="outer")
        obs.count("mixed.iters", out.inner, routine=routine, kind="inner")
        fallback = (not out.converged
                    and get_option(opts, Option.UseFallbackSolver, True))
        if fallback:
            # the refinement stalled or broke down: the full-precision
            # solver's answer and its info (gesv_mixed.cc:33-47)
            obs.count("mixed.fallback", 1, routine=routine)
            with trace.block("mixed.fallback"):
                X, info_box["info"] = legs.full(B)
        root.label(outer=out.outer, inner=out.inner,
                   converged=int(out.converged), fallback=int(fallback))
    if out.converged:
        iters = out.inner
    else:
        itermax = get_option(opts, Option.MaxIterations, 30)
        iters = -3 if out.broke_down else -(itermax + 1)
    return X, iters, info_box.get("info")


def _lu_legs(routine, A, lo, lo_opts, opts, info_box) -> _Legs:
    from .getrf import (_apply_pivots_kind, _getrf_native, getrs, gesv)

    def factor():
        # the pivots in the form the factor produced: an order from the
        # one-chip fast path, which every solve below applies as one
        # gather, not as a replay of n swaps
        LU, piv, info_box["info"] = _getrf_native(A.astype(lo), lo_opts)
        return LU, piv

    def solve(f, R):
        LU, piv = f
        return getrs(LU, piv, R.astype(lo), Op.NoTrans,
                     opts).astype(R.dtype)

    def full(B_):
        X, _, _, info = gesv(A, B_, _hi_opts(opts))
        return X, info

    return _Legs(routine, factor, solve, full,
                 lambda f, R: _apply_pivots_kind(R, f[1]))


def _chol_legs(routine, A, lo, lo_opts, opts, info_box) -> _Legs:
    from .potrf import potrf, potrs, posv

    def factor():
        L, info_box["info"] = potrf(A.astype(lo), lo_opts)
        return L

    def solve(L, R):
        return potrs(L, R.astype(lo), opts).astype(R.dtype)

    def full(B_):
        X, _, info = posv(A, B_, _hi_opts(opts))
        return X, info

    return _Legs(routine, factor, solve, full, lambda f, R: "none")


def _mixed(routine, loop, legs_of, A, B, opts):
    lo, lo_opts = _lo_plan(A.dtype, opts)
    # what is low about the low leg: its storage, or its trailing tier
    tier_lo = (jnp.dtype(lo).name if jnp.dtype(lo) != jnp.dtype(A.dtype)
               else lo_opts[Option.TrailingPrecision])
    info_box = {}
    legs = legs_of(routine, A, lo, lo_opts, opts, info_box)
    return _refine(loop, A, B, legs, tier_lo, opts, info_box)


def gesv_mixed(A: Matrix, B: Matrix, opts=None):
    """LU in low precision + IR in working precision
    (reference src/gesv_mixed.cc). Returns (X, iters, info)."""
    return _mixed("gesv_mixed", _ir_loop, _lu_legs, A, B, opts)


def posv_mixed(A: HermitianMatrix, B: Matrix, opts=None):
    """Cholesky in low precision + IR (reference src/posv_mixed.cc).
    Returns (X, iters, info)."""
    return _mixed("posv_mixed", _ir_loop, _chol_legs, A, B, opts)


def gesv_mixed_gmres(A: Matrix, B: Matrix, opts=None):
    """GMRES-IR LU solver (reference src/gesv_mixed_gmres.cc).
    Returns (X, iters, info)."""
    return _mixed("gesv_mixed_gmres", _gmres_ir, _lu_legs, A, B, opts)


def posv_mixed_gmres(A: HermitianMatrix, B: Matrix, opts=None):
    """GMRES-IR Cholesky solver (reference src/posv_mixed_gmres.cc).
    Returns (X, iters, info)."""
    return _mixed("posv_mixed_gmres", _gmres_ir, _chol_legs, A, B, opts)
