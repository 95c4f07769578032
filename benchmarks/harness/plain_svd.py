"""The plain reference of an economy singular value decomposition,
independent of the program: (i) the defining equations of an answer
(s, U, VT) to A = U diag(s) VT, in blocks of rows and columns with
every product at ``precision="highest"``, so that it fits beside a
cell's memory; (ii) the singular values against float64 LAPACK on the
host. ``jnp`` / ``numpy`` only, nothing of ``slate_tpu``.

The numbers (``equations``; plain floats, not yet in units), with k =
min(m, n) the number of triplets:

* ``residual_fro`` = ||A - U diag(s) VT||_F / ||A||_F: the whole
  decomposition, a mean over all m n entries, steady from seed to seed.
* ``residual_max`` = max_j ||A v_j - s_j u_j||_2 / ||A||_F: the worst
  single triplet.
* ``orth_u`` = ||U^T U - I||_F / sqrt(k), ``orth_v`` = ||VT VT^T -
  I||_F / sqrt(k): what a zeroed or repeated column reads far above.

``reference_values`` takes the float64 Gram route, sqrt(eigvalsh(A^T A))
(of A A^T for a wide A): one float64 product and one symmetric
eigensolve of order k, about four times quicker at 12288 x 8192 than
LAPACK's ``?gesdd`` with no vectors, most of which is a memory-bound
bidiagonalisation. Its error in a singular value is at most
eps64 ||A||_2^2 / s, and never more than sqrt(eps64) ||A||_2 = 0.25
units of 2^-24 of ||A||_2: under anything a check of an f32 answer
can see, whatever the rank.

Also a plain two-sided blocked band reduction in numpy
(``band_reduce``) with the trailing products computed as the MXU
computes them at a lower tier (``plain_solver.dot_as``): the control of
this check where no chip is there. On the chip the program's own
``Option.TrailingPrecision`` is the control (``benchmarks/control.py``).
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from benchmarks.harness.plain_solver import dot_as

BLOCK = 2048
NAMES = ("residual_fro", "residual_max", "orth_u", "orth_v")


def _orth(Q, block: int) -> float:
    """||Q^T Q - I||_F for the columns of Q, a block of columns at a
    time."""
    k = Q.shape[1]
    o2 = 0.0
    for c0 in range(0, k, block):
        Qc = Q[:, c0:c0 + block]
        O = jnp.matmul(Q.T, Qc, precision="highest")
        O = O - jnp.eye(k, Qc.shape[1], -c0, dtype=O.dtype)
        o2 += float(jnp.sum(O * O))
    return math.sqrt(o2)


def equations(Ad, s, Ud, VTd, block: int = BLOCK) -> dict:
    """How far (s, U, VT) is from A = U diag(s) VT, U^T U = I,
    VT VT^T = I (see the module's docstring); nan throughout for an
    answer of other shapes than A's economy decomposition has."""
    m, n = Ad.shape
    k = min(m, n)
    s = jnp.asarray(np.asarray(s), Ad.dtype)
    if s.shape != (k,) or Ud.shape != (m, k) or VTd.shape != (k, n):
        return dict.fromkeys(NAMES, float("nan"))
    r2 = 0.0
    col2 = jnp.zeros((k,), Ad.dtype)
    for r0 in range(0, m, block):
        Ar, Us = Ad[r0:r0 + block], Ud[r0:r0 + block] * s[None, :]
        R = Ar - jnp.matmul(Us, VTd, precision="highest")
        r2 += float(jnp.sum(R * R))
        Rv = jnp.matmul(Ar, VTd.T, precision="highest") - Us
        col2 = col2 + jnp.sum(Rv * Rv, axis=0)
    a_fro = float(jnp.linalg.norm(Ad))
    return {"residual_fro": math.sqrt(r2) / a_fro,
            "residual_max": math.sqrt(float(jnp.max(col2))) / a_fro,
            "orth_u": _orth(Ud, block) / math.sqrt(k),
            "orth_v": _orth(VTd.T, block) / math.sqrt(k)}


def reference_values(Ad) -> np.ndarray:
    """All min(m, n) singular values of the gathered A, descending, in
    float64 on the host by the Gram route (the module's docstring says
    what that loses); about 20 s at 12288 x 8192."""
    A64 = np.asarray(Ad, np.float64)
    G = A64.T @ A64 if A64.shape[0] >= A64.shape[1] else A64 @ A64.T
    return np.sqrt(np.maximum(np.linalg.eigvalsh(G)[::-1], 0.0))


def values_error(s, ref) -> float:
    """max_j |s_j - ref_j| / ||A||_2 (the largest ref)."""
    s = np.asarray(s, np.float64)
    if s.shape != ref.shape:
        return float("nan")
    return float(np.max(np.abs(s - ref)) / ref[0])


def descending(s) -> bool:
    """Finite, non-negative and descending."""
    s = np.asarray(s)
    return bool(np.all(np.isfinite(s)) and np.all(s >= 0)
                and np.all(np.diff(s) <= 0))


# ----------------------------------------- the control where no chip is

def band_reduce(A, nb: int, precision: str = "f32"):
    """Textbook blocked reduction of a tall or square ``A`` to an upper
    triangular band of width ``nb`` + 1: for each block column a
    Householder QR of the panel on and below the diagonal and the update
    of the columns to its right, then an LQ of the block row to the
    right of the diagonal block and the update of the rows below it;
    the products with the trailing matrix take ``precision``
    (``plain_solver.dot_as``) as the program's trailing products take
    its tier; the panel factorisations and everything small stay f32.
    Returns (B, Q, P) with A ~ Q[:, :n] B P^T, B banded (dense n x n),
    Q and P accumulated in float64."""
    W = np.array(A, np.float32)
    m, n = W.shape
    Q, P = np.eye(m), np.eye(n)
    for k in range(0, n, nb):
        e = min(k + nb, n)
        Qk, R = np.linalg.qr(W[k:, k:e].astype(np.float64),
                             mode="complete")
        Qk = Qk.astype(np.float32)
        W[k:, k:e] = R
        if e < n:
            W[k:, e:] = dot_as(Qk.T.copy(), W[k:, e:], precision)
        Q[:, k:] = Q[:, k:] @ Qk.astype(np.float64)
        if e >= n:
            break
        Pk, L = np.linalg.qr(W[k:e, e:].T.astype(np.float64),
                             mode="complete")
        Pk = Pk.astype(np.float32)
        W[k:e, e:] = L.T
        W[e:, e:] = dot_as(W[e:, e:], Pk, precision)
        P[:, e:] = P[:, e:] @ Pk.astype(np.float64)
    return np.triu(W[:n]), Q, P


def svd_via_band(A, nb: int, precision: str = "f32"):
    """(s, U, VT) of ``A`` through ``band_reduce`` at ``precision`` and
    a float64 SVD of the band: the first stage's tier is the only thing
    that differs from exact."""
    n = np.shape(A)[1]
    B, Q, P = band_reduce(A, nb, precision)
    Ub, s, VbT = np.linalg.svd(B.astype(np.float64))
    return (s, (Q[:, :n] @ Ub).astype(np.float32),
            (VbT @ P.T).astype(np.float32))
