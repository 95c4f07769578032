"""A plain blocked solver in numpy, with the trailing update's products
computed as a TPU computes them at a lower precision. It is the
*control* of the correctness check where no chip is there (the tests
under ``benchmarks/tests``): put in the program's place, its answer has
to FAIL the cell's limit at ``bf16_3x`` and ``mxu_bf16`` and pass in
plain f32. On the chip the program's own ``Option.TrailingPrecision``
serves as the control (``benchmarks/control.py``).

Nothing of ``slate_tpu`` is imported. Like the program, only the
trailing update takes the lower precision; panels and triangular solves
stay f32.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.linalg as sla


def _bf16(a):
    return a.astype(ml_dtypes.bfloat16).astype(np.float32)


def dot_as(a, b, precision: str):
    """``a @ b`` in f32 accumulation with the operands as the MXU sees
    them: ``f32`` exact operands; ``bf16_3x`` (``lax.Precision.HIGH``)
    hi·hi + hi·lo + lo·hi of a two-term bf16 split; ``mxu_bf16`` one
    pass on operands rounded to bf16."""
    if precision == "f32":
        return a @ b
    a_hi, b_hi = _bf16(a), _bf16(b)
    if precision == "mxu_bf16":
        return a_hi @ b_hi
    if precision == "bf16_3x":
        a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
        return a_hi @ b_hi + (a_hi @ b_lo + a_lo @ b_hi)
    raise ValueError(f"unknown precision {precision!r}")


def posv(A, B, nb: int, precision: str = "f32"):
    """Right-looking blocked Cholesky of the SPD ``A`` and the two
    triangular solves."""
    A = np.array(A, np.float32)
    n = A.shape[0]
    for k in range(0, n, nb):
        e = min(k + nb, n)
        A[k:e, k:e] = np.linalg.cholesky(A[k:e, k:e])
        if e < n:
            A[e:, k:e] = sla.solve_triangular(
                A[k:e, k:e], A[e:, k:e].T, lower=True).T
            A[e:, e:] -= dot_as(A[e:, k:e], A[e:, k:e].T.copy(), precision)
    L = np.tril(A)
    Y = sla.solve_triangular(L, np.asarray(B, np.float32), lower=True)
    return sla.solve_triangular(L.T, Y, lower=False).astype(np.float32)


def gesv(A, B, nb: int, precision: str = "f32"):
    """Right-looking blocked LU with partial pivoting and the solve."""
    A = np.array(A, np.float32)
    n = A.shape[0]
    perm = np.arange(n)
    for k in range(0, n, nb):
        e = min(k + nb, n)
        P, L, U = sla.lu(A[k:, k:e])            # panel, f32
        order = np.argmax(P, axis=0)            # rows of the panel, pivoted
        A[k:, :] = A[k:, :][order]
        perm[k:] = perm[k:][order]
        A[k:, k:e] = np.tril(L, -1) + np.pad(U, ((0, L.shape[0] - U.shape[0]), (0, 0)))
        if e < n:
            A[k:e, e:] = sla.solve_triangular(
                np.tril(A[k:e, k:e], -1) + np.eye(e - k, dtype=np.float32),
                A[k:e, e:], lower=True)
            A[e:, e:] -= dot_as(A[e:, k:e], A[k:e, e:], precision)
    Lf = np.tril(A, -1) + np.eye(n, dtype=np.float32)
    Y = sla.solve_triangular(Lf, np.asarray(B, np.float32)[perm], lower=True)
    return sla.solve_triangular(np.triu(A), Y, lower=False).astype(np.float32)
