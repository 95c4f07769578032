"""The plain reference of an overdetermined least-squares solve,
independent of the program: min ||A X - B||_F over X for a tall A of
full column rank, solved in float64 on the host by LAPACK (a Householder
QR through ``numpy.linalg.qr`` and one triangular solve), and three
numbers of an answer X against it, all in float64. ``numpy`` / ``scipy``
only, nothing of ``slate_tpu`` and nothing of jax.

The three numbers (``numbers``; plain floats, not yet in units):

* ``optimality`` = ||A^T (B - A X)||_F / (||A||_F ||B - A X||_F): the
  normal equations hold, LAPACK's own test of ``?gels`` (``?qrt17``).
  It is what a backward-stable QR keeps at a few eps whatever kappa(A).
* ``forward`` = ||X - X_ref||_F / ||X_ref||_F against the float64
  solution.
* ``residual_excess`` = (||B - A X||_F - ||B - A X_ref||_F) / ||B||_F:
  X is a minimiser, not merely a solution of something. At a minimiser
  it is second order in the error of X, so it is the loosest of the
  three and reads near 0 (it may read a hair below: the float64
  reference is itself rounded).

Also a plain blocked Householder QR least-squares solver in numpy
(``gels_qr``) whose trailing products are computed as the MXU computes
them at a lower tier (``plain_solver.dot_as``): the control of this
check where no chip is there. On the chip the program's own
``Option.TrailingPrecision`` is the control (``benchmarks/control.py``).
"""

from __future__ import annotations

import numpy as np
import scipy.linalg as sla

from benchmarks.harness.plain_solver import dot_as

EPS = 2.0 ** -24          # f32 unit roundoff, the unit of every limit


def reference(A, B) -> np.ndarray:
    """The least-squares solution of float64(A), float64(B): LAPACK's
    Householder QR and a triangular solve with R (about 3 s at
    16384 x 1024 on the host)."""
    A64, B64 = np.asarray(A, np.float64), np.asarray(B, np.float64)
    Q, R = np.linalg.qr(A64, mode="reduced")
    return sla.solve_triangular(R, Q.T @ B64, lower=False)


def numbers(A, B, X, X_ref) -> dict:
    """``{"optimality", "forward", "residual_excess"}`` of the answer
    ``X`` (see the module's docstring); nan throughout for an X of
    another shape than the reference's."""
    A64, B64 = np.asarray(A, np.float64), np.asarray(B, np.float64)
    X64 = np.asarray(X, np.float64)
    if X64.shape != X_ref.shape:
        return dict.fromkeys(("optimality", "forward", "residual_excess"),
                             float("nan"))
    R = B64 - A64 @ X64
    r_norm = np.linalg.norm(R)
    r_ref = np.linalg.norm(B64 - A64 @ X_ref)
    return {
        "optimality": float(np.linalg.norm(A64.T @ R)
                            / (np.linalg.norm(A64) * r_norm)),
        "forward": float(np.linalg.norm(X64 - X_ref)
                         / np.linalg.norm(X_ref)),
        "residual_excess": float((r_norm - r_ref) / np.linalg.norm(B64)),
    }


# ----------------------------------------- the control where no chip is

def gels_qr(A, B, nb: int, precision: str = "f32") -> np.ndarray:
    """Textbook blocked Householder QR least squares in f32: for each
    block column a QR of the panel (LAPACK through numpy), the
    compact-WY update of the trailing columns A2 -= V (T^T (V^T A2)),
    whose two large products take ``precision`` as the program's
    trailing products take its tier, then Q^T B panel by panel in f32
    and the triangular solve with R. Panels, T and everything small
    stay f32, as in the program."""
    A = np.array(A, np.float32)
    C = np.array(B, np.float32)
    m, n = A.shape
    for k in range(0, n, nb):
        e = min(k + nb, n)
        (qr_, tau), _ = sla.qr(A[k:, k:e], mode="raw")
        qr_ = np.asarray(qr_, np.float32)
        w = e - k
        V = np.tril(qr_, -1)
        V[np.arange(w), np.arange(w)] = 1.0
        A[k:, k:e] = np.triu(qr_)
        # forward compact-WY T (LAPACK larft), in f32
        T = np.zeros((w, w), np.float32)
        G = V.T @ V
        for j in range(w):
            T[:j, j] = -tau[j] * (T[:j, :j] @ G[:j, j])
            T[j, j] = tau[j]
        if e < n:
            W1 = dot_as(V.T.copy(), A[k:, e:], precision)
            A[k:, e:] -= dot_as(V, T.T @ W1, precision)
        C[k:] -= V @ (T.T @ (V.T @ C[k:]))
    return sla.solve_triangular(np.triu(A[:n]), C[:n],
                                lower=False).astype(np.float32)
