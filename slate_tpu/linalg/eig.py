"""Hermitian eigensolvers: heev / hegv / hegst + tridiagonal kernels
(sterf, steqr, stedc).

Reference: src/heev.cc:56-180 — two-stage reduction he2hb (full→band,
src/he2hb.cc) then hb2st (band→tridiagonal bulge chasing, src/hb2st.cc
— run **on rank 0 only**, heev.cc:113-131), tridiagonal eigensolver
(sterf values-only / steqr2 ◆Fortran / stedc divide & conquer), then
distributed back-transform (unmtr_hb2st / unmtr_he2hb).

What runs here.  ``heev`` has two paths.  *Two-stage* is the
reference's pipeline, every stage on the device but the O(k) scalar
work of a merge: ``he2hb`` (one jitted ``shard_map`` loop over block
columns, ``linalg/he2hb.py``), the band gathered to the host
(2·nt tiles), ``hb2st`` by the ``robust.ladder`` rung that takes the
problem (on a TPU in f32 the VMEM-resident Pallas chaser at band 128,
``internal/band_wave_vmem.py``), the tridiagonal eigenproblem
(``MethodEig.DC``: ``linalg/stedc.py`` with Z, the secular solves and
the merge products on the device, a level of the tree a batch; ``MethodEig.QR``: host values +
device inverse iteration, ``linalg/stein.py``), then the two
back-transforms ``unmtr_hb2st`` (``linalg/bulge.py``) and
``unmtr_he2hb``.  *Dense* is one replicated ``jnp.linalg.eigh`` (XLA's
QDWH), a one-chip shortcut the reference does not have; ``Auto`` takes
it below the crossover in :func:`heev`.  ``Option.TrailingPrecision``
reaches stage 1's trailing products only: the merge products, the
packed-reflector sweeps and ``unmtr_he2hb`` run at the package default
(``highest``).  hegst (the generalized → standard reduction) is fully
distributed via trsm/hemm.

What a call reports (docs/observability.md): the spans :data:`SPANS`
(a root ``slate.heev`` with ``routine``, ``n``, ``nb``, ``grid``,
``jobz``, ``method``, ``path``; at its end ``method`` as resolved and,
two-stage, ``band`` and ``chase_backend``), every blocking read as an
``obs.sync_read`` (``band.gather``, ``hb2st.tridiagonal``,
``stedc.zrow`` and ``stedc.roots``: one each a level of the D&C tree,
with ``level``, ``k``, ``m``; ``heev.values``), and the counters
:data:`COUNTERS`.

The host tridiagonal kernels sterf/steqr (scipy LAPACK) are kept for
API parity and for the values-only and QR paths (the reference equally
runs sterf/steqr2 on the host CPUs).
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from ..matrix import (Matrix, HermitianMatrix, TriangularMatrix,
                      conj_transpose)
from .. import obs
from ..types import Norm, Uplo, Side, Op, MethodEig, Option, get_option
from ..errors import slate_error_if
from ..ops.blas import trsm, gemm
from ..utils import trace


def _he_to_dense(A: HermitianMatrix):
    """Replicated dense Hermitian matrix from the significant half."""
    d = A.to_dense()
    if A.uplo == Uplo.Lower:
        lo = jnp.tril(d)
        full = lo + jnp.tril(d, -1).conj().T
    else:
        up = jnp.triu(d)
        full = up + jnp.triu(d, 1).conj().T
    return full


# what an eigensolve reports: the spans (the root first, then its
# children in the order they open; ``heev.dense`` alone on the dense
# path) and the counters (``/metrics``): ``heev.path{path}``,
# ``hb2st.backend{rung}`` (the rung whose answer was used),
# ``hb2st.demotion{from,to}`` (a rung that was stepped past: the
# ladder's own ``ladder.demotions`` by another name, so a caller of
# heev need not know the ladder), ``hb2st.shear{form}`` (how the VMEM
# chaser built its sheared vectors, ``single_pass`` or ``ladder``:
# once a call that the ``vmem`` rung ran), and ``linalg/stedc.py``'s
# (merges, poles, deflated poles, and the levels those merges ran in)
SPANS = ("slate.heev", "heev.stage1", "heev.gather", "heev.stage2",
         "heev.tridiag", "heev.back.hb2st", "heev.back.he2hb",
         "heev.dense")
COUNTERS = ("heev.path", "hb2st.backend", "hb2st.demotion",
            "hb2st.shear", "stedc.merges", "stedc.poles", "stedc.deflated",
            "stedc.levels")

# one chip: below this n ``Auto`` takes XLA's eigh. The number is
# round 5's (two-stage with vectors was then slower than eigh, ~5 s at
# n=8192, until eigh's n² replication threatens HBM near 24k f32 on
# 16 GB) and the chip has since contradicted both halves (PERF.md
# section 6, PR 41, n=8192 f32 on one v5e, jax 0.9.0): two-stage DC
# with vectors is 4.14 s a call, and ``jnp.linalg.eigh`` did not
# come back at n=4096 or n=8192: the process passed 30 GiB of host
# memory 286 / 341 s in and was stopped (left alone it was killed at
# the machine's 40 GiB). Not moved here: ROADMAP R7b
DENSE_BELOW = 24576


def _takes_two_stage(A, opts, method, want_vectors) -> bool:
    if method != MethodEig.Auto:
        # QR/DC name the tridiagonal stage of the two-stage pipeline
        # (reference MethodEig semantics, src/heev.cc:139-156)
        return method in (MethodEig.TwoStage, MethodEig.QR, MethodEig.DC)
    # two-stage whenever the grid is parallel OR the problem is too
    # big for a replicated dense eigh on one chip (its n² replication
    # and workspace threaten HBM near 24k f32 on 16 GB).  The
    # reference is ALWAYS two-stage (src/heev.cc:104-172); the dense
    # path is a single-chip shortcut only
    thresh = DENSE_BELOW
    if not want_vectors:
        try:
            import jax as _jax
            from ..internal.band_wave_vmem import (preferred_eig_band,
                                                   vmem_applies)
            # test the band the two-stage pipeline will ACTUALLY
            # use (a user Option.EigBand override included) — the
            # lowered threshold is only justified when the VMEM
            # chaser takes that band. heev_two_stage re-blocks to
            # band_nb only when A.nb > band_nb and n > 2*band_nb;
            # otherwise the chase runs at A.nb, so gate on that
            band_nb = get_option(opts, Option.EigBand,
                                 preferred_eig_band(A.n, A.dtype))
            from .he2hb import two_stage_chase_band
            chase_nb = two_stage_chase_band(A.n, A.nb, band_nb)
            if (_jax.default_backend() == "tpu"
                    and vmem_applies(A.n, chase_nb,
                                     np.dtype(A.dtype))):
                thresh = 8192
        except Exception:  # pragma: no cover
            pass
    return (A.grid.size > 1 and A.nt >= 4) or A.n >= thresh


def heev(A: HermitianMatrix, opts=None, want_vectors: bool = True):
    """Eigendecomposition A = Z·Λ·Zᴴ (reference src/heev.cc).

    Method dispatch (Option.MethodEig): TwoStage / QR / DC = he2hb
    band reduction + hb2st bulge chase + the tridiagonal solver named
    (DC by default) + the two back-transforms (the reference's
    pipeline, src/heev.cc:104-172); Dense = replicated XLA eigh
    (QDWH). Auto: two-stage on multi-chip grids with enough tiles
    (the he2hb flops — the O(n³) term — then run distributed) and
    from ``DENSE_BELOW`` up, dense otherwise.

    Returns (Lambda [n] ascending, Z distributed Matrix or None).
    """
    slate_error_if(A.m != A.n, "heev needs square")
    method = get_option(opts, Option.MethodEig, MethodEig.Auto)
    two = _takes_two_stage(A, opts, method, want_vectors)
    path = "two_stage" if two else "dense"
    obs.count("heev.path", 1, path=path)
    with trace.block("slate.heev", routine="heev", n=A.n, nb=A.nb,
                     grid=f"{A.grid.p}x{A.grid.q}",
                     jobz="V" if want_vectors else "N",
                     method=method.name, path=path) as root:
        if two:
            from .he2hb import heev_two_stage
            if A.uplo == Uplo.Upper:
                # mirror the stored Upper half into Lower storage — the
                # same Hermitian operator, so Λ and Z are unchanged
                # (reference he2hb handles Lower; heev.cc dispatches
                # the conjugated problem the same way)
                G = Matrix(data=A.data, m=A.m, n=A.n, nb=A.nb,
                           grid=A.grid)
                low = conj_transpose(G).materialize().data
                A = HermitianMatrix(data=low, m=A.m, n=A.n, nb=A.nb,
                                    grid=A.grid, uplo=Uplo.Lower)
            return heev_two_stage(A, opts, want_vectors, root=root)
        root.label(method=MethodEig.Dense.name)
        with trace.block("heev.dense", n=A.n):
            lam, z = jnp.linalg.eigh(_he_to_dense(A))
            Z = (Matrix.from_dense(z, nb=A.nb, grid=A.grid)
                 if want_vectors else None)
        return obs.sync_read("heev.values", np.asarray, lam), Z


def hegst(itype: int, A: HermitianMatrix, L: TriangularMatrix, opts=None):
    """Reduce generalized problem to standard form (src/hegst.cc):
    itype 1: A ← L⁻¹·A·L⁻ᴴ ; itype 2/3: A ← Lᴴ·A·L. Fully distributed
    via trsm/trmm chains."""
    from ..ops.blas import trmm, _mirror_full
    Af = _mirror_full(A, conj=jnp.issubdtype(A.dtype, jnp.complexfloating))
    if itype == 1:
        # L⁻¹ A L⁻ᴴ : two triangular solves
        Y = trsm(Side.Left, 1.0, L, Af, opts)
        C = trsm(Side.Right, 1.0, conj_transpose(L), Y, opts)
    else:
        Y = trmm(Side.Left, 1.0, conj_transpose(L), Af, opts)
        C = trmm(Side.Right, 1.0, L, Y, opts)
    return HermitianMatrix(data=C.data, m=A.m, n=A.n, nb=A.nb,
                           grid=A.grid, uplo=A.uplo)


def hegv(itype: int, A: HermitianMatrix, B: HermitianMatrix, opts=None):
    """Generalized Hermitian eigensolver (src/hegv.cc):
    B = L·Lᴴ, reduce, heev, back-transform. Returns (Λ, Z, info)."""
    from .potrf import potrf
    with trace.block("hegv"):
        L, info = potrf(B, opts)
        C = hegst(itype, A, L, opts)
        lam, Z = heev(C, opts)
        if itype in (1, 2):
            # LAPACK xHEGV: x = L⁻ᴴ·y for itype 1 and 2
            Z = trsm(Side.Left, 1.0, conj_transpose(L), Z, opts)
        else:
            from ..ops.blas import trmm
            Z = trmm(Side.Left, 1.0, L, Z, opts)
    return lam, Z, info


# ---------------------------------------------------------------------------
# Tridiagonal kernels (host, like the reference's rank-0 sterf/steqr2)
# ---------------------------------------------------------------------------

def sterf(d, e):
    """Eigenvalues of a symmetric tridiagonal matrix (src/sterf.cc —
    values-only QR iteration on rank 0, result broadcast)."""
    d = np.asarray(d, np.float64)
    e = np.asarray(e, np.float64)
    try:
        from scipy.linalg import eigh_tridiagonal
        return eigh_tridiagonal(d, e, eigvals_only=True)
    except ImportError:  # pragma: no cover
        T = np.diag(d) + np.diag(e, 1) + np.diag(e, -1)
        return np.linalg.eigvalsh(T)


def steqr(d, e, want_vectors: bool = True, grid=None, dtype=None):
    """Tridiagonal QR iteration with vectors (reference src/steqr2.cc
    over ◆Fortran dsteqr2.f — distributed Z updates: no rank ever
    holds the dense Z).

    With ``grid``, the same contract holds here: eigenVALUES by host
    QR iteration (O(n) memory), eigenVECTORS computed ON DEVICE by
    batched inverse iteration with per-cluster device QR
    (linalg/stein.py) — Z returns as a column-sharded jax array and
    host memory stays O(n). Without a grid: host LAPACK (rank-0
    semantics)."""
    d = np.asarray(d, np.float64)
    e = np.asarray(e, np.float64)
    if grid is not None and want_vectors:
        from .stein import stein_vectors
        lam = sterf(d, e)       # host values, scipy w/ numpy fallback
        Z = stein_vectors(d, e, lam, grid=grid, dtype=dtype)
        return lam, Z
    try:
        from scipy.linalg import eigh_tridiagonal
        if want_vectors:
            return eigh_tridiagonal(d, e)
        return eigh_tridiagonal(d, e, eigvals_only=True), None
    except ImportError:  # pragma: no cover
        T = np.diag(d) + np.diag(e, 1) + np.diag(e, -1)
        lam, z = np.linalg.eigh(T)
        return (lam, z) if want_vectors else (lam, None)


def stedc(d, e, want_vectors: bool = True, grid=None, dtype=None):
    """Divide & conquer tridiagonal eigensolver (reference src/stedc.cc
    + stedc_{deflate,merge,secular,solve,sort,z_vector}.cc — LAPACK
    dlaed0-4 structure).  Real secular-equation D&C: deflation walk,
    vectorized bisection + pole-solve refinement, Gu-Eisenstat
    z-vector.  With ``grid``, Z accumulates on device row-sharded and
    host memory stays O(n) (the merge gemm chain is the distributed-Z
    analog of the reference's steqr2/unmtr path).  See
    linalg/stedc.py."""
    from .stedc import stedc as _stedc
    return _stedc(d, e, want_vectors, grid=grid, dtype=dtype)
