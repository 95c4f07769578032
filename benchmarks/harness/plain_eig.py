"""The plain reference of a symmetric eigendecomposition, independent
of the program: (i) the defining equations, R = A Z - Z diag(lam) and
O = Z^T Z - I, in blocks of columns with every product at
``precision="highest"``; (ii) the eigenvalues against
``numpy.linalg.eigvalsh`` of the gathered A in float64 on the host.
``jnp`` / ``numpy`` only, nothing of ``slate_tpu``.

Also a plain numpy band reduction (``band_reduce``) with the trailing
products computed as the MXU computes them at a lower tier
(``plain_solver.dot_as``): the control of this check where no chip is
there. On the chip the program's own ``Option.TrailingPrecision`` is
the control (``benchmarks/control.py``).
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from benchmarks.harness.plain_solver import dot_as

BLOCK = 2048


def symmetric_of(lower):
    """The dense symmetric matrix whose lower triangle ``lower`` holds
    (the ``uplo`` Lower contract of the operand)."""
    return jnp.tril(lower) + jnp.tril(lower, -1).T


def equations(Ad, lam, Zd, block: int = BLOCK) -> dict:
    """How far (lam, Z) is from A Z = Z diag(lam), Z^T Z = I:
    ``residual_max`` = max_j ||A z_j - lam_j z_j||_2 / ||A||_F,
    ``residual_fro`` = ||R||_F / (||A||_F ||Z||_F),
    ``orth_fro`` = ||Z^T Z - I||_F / sqrt(n). Plain floats."""
    n = Ad.shape[0]
    lam = jnp.asarray(lam, Ad.dtype)
    col_max = r2 = o2 = 0.0
    for c0 in range(0, n, block):
        Zc = Zd[:, c0:c0 + block]
        R = jnp.matmul(Ad, Zc, precision="highest") \
            - Zc * lam[None, c0:c0 + block]
        col_max = max(col_max, float(jnp.max(jnp.linalg.norm(R, axis=0))))
        r2 += float(jnp.sum(R * R))
        O = jnp.matmul(Zd.T, Zc, precision="highest")
        O = O - jnp.eye(n, Zc.shape[1], -c0, dtype=O.dtype)
        o2 += float(jnp.sum(O * O))
    a_fro = float(jnp.linalg.norm(Ad))
    z_fro = float(jnp.linalg.norm(Zd))
    return {"residual_max": col_max / a_fro,
            "residual_fro": math.sqrt(r2) / (a_fro * z_fro),
            "orth_fro": math.sqrt(o2) / math.sqrt(n)}


def reference_values(Ad) -> np.ndarray:
    """All eigenvalues of the gathered A, ascending, in float64 on the
    host (LAPACK through numpy; about a minute at n=8192)."""
    return np.linalg.eigvalsh(np.asarray(Ad, np.float64))


def values_error(lam, ref) -> float:
    """max_j |lam_j - ref_j| / ||A||_2 (the largest |ref|)."""
    lam = np.asarray(lam, np.float64)
    if lam.shape != ref.shape:
        return float("nan")
    return float(np.max(np.abs(lam - ref)) / np.max(np.abs(ref)))


def ascending(lam) -> bool:
    lam = np.asarray(lam)
    return bool(np.all(np.isfinite(lam)) and np.all(np.diff(lam) >= 0))


# ----------------------------------------- the control where no chip is

def band_reduce(A, nb: int, precision: str = "f32"):
    """Textbook blocked reduction of the symmetric ``A`` to bandwidth
    ``nb``: for each block column a Householder QR of the panel below
    the diagonal block, then the two-sided update of the trailing
    matrix, whose products take ``precision`` (``plain_solver.dot_as``)
    as the program's trailing products take its tier; the panel QR and
    everything small stay f32. Returns (B, Q) with A ~ Q B Q^T, B banded
    (dense storage), Q accumulated in float64."""
    B = np.array(A, np.float32)
    n = B.shape[0]
    Q = np.eye(n)
    for k in range(0, n - nb, nb):
        s = k + nb
        Qk, R = np.linalg.qr(B[s:, k:k + nb].astype(np.float64),
                             mode="complete")
        Qk = Qk.astype(np.float32)
        B[s:, k:k + nb] = np.triu(R)
        B[k:k + nb, s:] = B[s:, k:k + nb].T
        T = dot_as(dot_as(Qk.T.copy(), B[s:, s:], precision), Qk, precision)
        B[s:, s:] = 0.5 * (T + T.T)
        Q[:, s:] = Q[:, s:] @ Qk.astype(np.float64)
    return B, Q


def eig_via_band(A, nb: int, precision: str = "f32"):
    """(lam, Z) of ``A`` through ``band_reduce`` at ``precision`` and a
    float64 eigensolve of the band: the first stage's tier is the only
    thing that differs from exact."""
    B, Q = band_reduce(A, nb, precision)
    lam, Zb = np.linalg.eigh(B.astype(np.float64))
    return lam, (Q @ Zb).astype(np.float32)
