"""A plain mixed-precision solver in numpy: a blocked LU whose trailing
products are computed as a TPU computes them at a lower precision
(``plain_solver.dot_as``), then textbook restarted GMRES,
right-preconditioned by those factors, in the working precision
(float32 or float64: the dtype of ``A``). It is the reference that
``slate.gesv_mixed_gmres`` is compared with where no chip is there
(``tests/test_mixed_gmres.py``, ``benchmarks/tests``), and with the
refinement left out (``refine=False``) it is that comparison's control.

Nothing of ``slate_tpu`` is imported. By the published description
(Saad & Schultz 1986, GMRES(m); Carson & Higham 2017/2018, GMRES-IR;
HPL-MxP's rules): x0 = U⁻¹L⁻¹P·b; each cycle builds an orthonormal
basis V of the Krylov space of A·M⁻¹ from r/‖r‖ by Arnoldi with
modified Gram–Schmidt, keeps H's QR by Givens rotations as it grows,
leaves the cycle when the rotated residual estimate |g[j+1]| meets the
stop criterion, and updates x += M⁻¹·(V·y), H·y = g. The stop criterion
is the one SLATE's routine and LAPACK's ``dsgesv`` use: ‖r‖max ≤
‖x‖max · ‖A‖∞ · ε · √n, ε the working precision's ``finfo.eps``.

Departures from that description: none in the method. From SLATE's
``gesv_mixed_gmres.cc``: classical Gram–Schmidt twice there, modified
here; W = M⁻¹·V stored there, one more application of the factors at
the update here (the textbook's right preconditioning).
"""

from __future__ import annotations

import numpy as np
import scipy.linalg as sla

from benchmarks.harness.plain_solver import dot_as


def lu_factor(A, nb: int, precision: str = "f32"):
    """Right-looking blocked LU with partial pivoting, panels and
    triangular solves in A's dtype, trailing products through
    ``dot_as(..., precision)`` (float32 operands only below ``f32``).
    Returns ``(LU, order)``: unit-lower L below the diagonal and U on
    and above, and the elimination order (``order[j]`` = the row of A
    that became row j)."""
    A = np.array(A)
    dtype = A.dtype
    n = A.shape[0]
    order = np.arange(n)
    for k in range(0, n, nb):
        e = min(k + nb, n)
        P, L, U = sla.lu(A[k:, k:e])
        rows = np.argmax(P, axis=0)
        A[k:, :] = A[k:, :][rows]
        order[k:] = order[k:][rows]
        A[k:, k:e] = np.tril(L, -1) + np.pad(
            U, ((0, L.shape[0] - U.shape[0]), (0, 0)))
        if e < n:
            A[k:e, e:] = sla.solve_triangular(
                np.tril(A[k:e, k:e], -1) + np.eye(e - k, dtype=dtype),
                A[k:e, e:], lower=True)
            A[e:, e:] -= dot_as(A[e:, k:e], A[k:e, e:],
                                precision).astype(dtype)
    return A, order


def lu_solve(LU, order, b):
    """U⁻¹·L⁻¹·P·b in LU's dtype."""
    n = LU.shape[0]
    dtype = LU.dtype
    y = sla.solve_triangular(
        np.tril(LU, -1) + np.eye(n, dtype=dtype),
        np.asarray(b, dtype)[order], lower=True)
    return sla.solve_triangular(np.triu(LU), y, lower=False).astype(dtype)


def stop_factor(A, tol: float | None = None) -> float:
    """‖A‖∞ · tol; tol = ε · √n in A's dtype unless given."""
    if tol is None:
        tol = float(np.finfo(A.dtype).eps * np.sqrt(A.shape[0]))
    return float(np.linalg.norm(A, np.inf)) * tol


def gmres_ir(A, b, LU, order, restart: int = 30, itermax: int = 30,
             refine: bool = True, tol: float | None = None):
    """Refine x0 = M⁻¹·b by GMRES(``restart``) on A·M⁻¹, M = P⁻¹·L·U,
    in A's dtype; ``itermax`` bounds the Arnoldi steps over all cycles.
    Returns ``(x, report)``; ``report`` has ``outer`` (cycles run),
    ``inner`` (Arnoldi steps), ``steps`` (per cycle) and ``converged``.
    ``refine=False``: x0 and the verdict on it, nothing more."""
    dtype = A.dtype
    b = np.asarray(b, dtype).reshape(-1)
    cte = stop_factor(A, tol)

    def apply(v):               # M⁻¹·v, back in the working precision
        return lu_solve(LU, order, v).astype(dtype)

    x = apply(b)
    report = {"outer": 0, "inner": 0, "steps": [], "converged": False}
    while True:
        r = (b - A @ x).astype(dtype)
        xmax = float(np.abs(x).max())
        if float(np.abs(r).max()) <= xmax * cte:
            report["converged"] = True
            return x, report
        if not refine or report["inner"] >= itermax \
                or not np.all(np.isfinite(r)):
            return x, report
        beta = float(np.linalg.norm(r))
        m = min(restart, itermax - report["inner"])
        V = [r / dtype.type(beta)]
        H = np.zeros((m, m), np.float64)        # R of H's QR
        g = np.zeros(m + 1, np.float64)
        g[0] = beta
        rots = []
        steps = 0
        for j in range(m):
            w = (A @ apply(V[j])).astype(dtype)
            h = np.zeros(j + 2, np.float64)
            for i in range(j + 1):
                h[i] = float(V[i] @ w)
                w = w - dtype.type(h[i]) * V[i]
            hn = float(np.linalg.norm(w))
            h[j + 1] = hn
            for i, (c, s) in enumerate(rots):
                h[i], h[i + 1] = c * h[i] + s * h[i + 1], \
                    -s * h[i] + c * h[i + 1]
            scale = np.hypot(h[j], hn)
            c, s = (1.0, 0.0) if scale == 0 else (h[j] / scale, hn / scale)
            rots.append((c, s))
            h[j] = c * h[j] + s * h[j + 1]
            g[j], g[j + 1] = c * g[j], -s * g[j]
            H[:j + 1, j] = h[:j + 1]
            steps = j + 1
            if abs(g[j + 1]) <= xmax * cte or not hn > 0.0:
                break
            V.append(w / dtype.type(hn))
        y = np.linalg.lstsq(H[:steps, :steps], g[:steps], rcond=None)[0]
        z = sum(dtype.type(y[i]) * V[i] for i in range(steps))
        x = (x + apply(z)).astype(dtype)
        report["outer"] += 1
        report["inner"] += steps
        report["steps"].append(steps)


def gesv_mixed_gmres(A, b, nb: int, precision: str = "bf16_3x",
                     refine: bool = True, restart: int = 30,
                     itermax: int = 30, tol: float | None = None):
    """The deployment in one call: LU at ``precision``, then GMRES-IR in
    A's dtype. Returns ``(x, report)``."""
    LU, order = lu_factor(A, nb, precision)
    return gmres_ir(A, b, LU, order, restart, itermax, refine, tol)
