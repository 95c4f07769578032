"""Flop accounting: closed-form operation counts per routine.

The table follows the LAPACK Users' Guide / LAWN 41 conventions the
repo has always used (potrf n³/3, gemm 2n³, getrf 2n³/3, geqrf
2mn² − 2n³/3), generalized to rectangular shapes, so a
span labeled ``routine=…`` plus its dims can report achieved GFLOP/s
without the call site hand-computing a formula.

``flop_count`` is deliberately forgiving: unknown routine or missing
dims return ``None`` (the span simply reports no GFLOP/s) rather than
raising — observability must never take down a driver.
"""

from __future__ import annotations

import inspect
import os

# Each formula takes keyword dims; m defaults to n (square) where
# that is the common call shape.

def _gemm(m, n, k):
    return 2.0 * m * n * k


def _potrf(n):
    return n ** 3 / 3.0


def _getrf(n, m=None):
    m = n if m is None else m
    return m * float(n) ** 2 - n ** 3 / 3.0


def _geqrf(m, n):
    return 2.0 * m * n ** 2 - 2.0 * n ** 3 / 3.0


def _gelqf(m, n):
    return _geqrf(n, m)


def _trsm(m, n, side="left"):
    return float(m) ** 2 * n if side == "left" else m * float(n) ** 2


def _syrk(n, k):
    return float(n) ** 2 * k


def _solve(n, nrhs=1):
    return 2.0 * float(n) ** 2 * nrhs


def _posv(n, nrhs=1):
    # factor + both triangular solves (the serve layer labels its
    # batched dispatch spans with the driver routine, not the parts)
    return _potrf(n) + _solve(n, nrhs)


def _gesv(n, nrhs=1):
    return _getrf(n) + _solve(n, nrhs)


def _he2hb(n, nb=None):
    return 4.0 * n ** 3 / 3.0


def _hb2st(n, b):
    # bulge-chasing stage 2: ~6 rotations-worth of work per band
    # element over n sweeps (Haidar et al. two-stage analysis)
    return 6.0 * float(n) ** 2 * b


def _ge2tb(m, n):
    # QR+LQ two-sided band reduction ≈ the sum of both one-sided
    # factorizations (8n³/3 at m = n)
    return _geqrf(m, n) + _gelqf(m, n)


def _heev(n):
    # tridiagonal reduction dominates (4n³/3); eigenvalue iteration is
    # O(n²) and not counted, matching the LAWN-41 convention
    return 4.0 * n ** 3 / 3.0


def _gesvd(m, n=None):
    # band-reduction-dominated SVD: same leading term as ge2tb
    n = m if n is None else n
    return _ge2tb(m, n)


FLOP_FORMULAS = {
    "gemm": _gemm,
    "potrf": _potrf,
    "pbtrf": None,              # band: O(n·kd²), dims not span-labeled
    "getrf": _getrf,
    "geqrf": _geqrf,
    "gelqf": _gelqf,
    "trsm": _trsm,
    "syrk": _syrk,
    "herk": _syrk,
    "potrs": _solve,
    "getrs": _solve,
    "posv": _posv,
    "gesv": _gesv,
    "he2hb": _he2hb,
    "hb2st": _hb2st,
    "ge2tb": _ge2tb,
    "heev": _heev,
    "gesvd": _gesvd,
}


def flop_count(routine: str, **dims) -> float | None:
    """Closed-form flop count for ``routine`` at ``dims``; None when
    the routine is unknown or the dims don't satisfy the formula."""
    fn = FLOP_FORMULAS.get(routine)
    if fn is None:
        return None
    # spans label every dim they know (n, nb, platform-extra keys are
    # already filtered by the caller); drop the ones this formula
    # doesn't take instead of failing the whole count
    accepted = inspect.signature(fn).parameters
    try:
        return float(fn(**{k: v for k, v in dims.items()
                           if v is not None and k in accepted}))
    except (TypeError, ValueError):
        return None


# Per-(platform, dtype) peak GFLOP/s for %-of-peak. Only entries the
# repo has measured/stated are listed (the v5e bf16 peak is Google's
# published one); everything else reports no pct_peak rather than a guess.
PEAK_GFLOPS = {
    ("tpu", "bfloat16"): 197e3,       # v5e bf16
}


def peak_gflops(platform: str | None, dtype: str | None,
                precision: str | None = None) -> float | None:
    """Peak GFLOP/s for a (platform, dtype) pair.  Overridable via
    ``SLATE_TPU_PEAK_GFLOPS`` (applies to every pair — a single-SKU
    escape hatch for fleets the table doesn't know).

    ``precision`` is the trailing-update tier a span was labeled with
    (internal/precision.py). On TPU an f32/c64 span's attainable peak
    is the bf16 MXU peak divided by the tier's pass count — bf16_6x
    runs 6 MXU passes per dot (≈32.8 TFLOP/s on v5e), bf16_3x runs 3
    (≈65.7), mxu_bf16 runs 1 — so %peak for a ``precision=``-labeled
    span is measured against the ladder rung it actually bought, not
    the raw bf16 number it can never reach.
    """
    env = os.environ.get("SLATE_TPU_PEAK_GFLOPS", "")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    if platform is None or dtype is None:
        return None
    platform, dtype = str(platform), str(dtype)
    base = PEAK_GFLOPS.get((platform, dtype))
    if base is not None:
        return base
    if precision is not None and dtype in ("float32", "complex64"):
        from ..internal.precision import TIER_MXU_PASSES
        passes = TIER_MXU_PASSES.get(str(precision))
        bf16 = PEAK_GFLOPS.get((platform, "bfloat16"))
        if passes and bf16:
            return bf16 / passes
    return None
