"""SVD: gesvd (reference src/gesvd.cc:77-102 — two-stage ge2tb →
tb2bd bulge chasing → bdsqr, back-transforms unmbr_tb2bd and
unmbr_ge2tb).

What runs here.  ``gesvd`` has two paths.  *Two-stage* is the
reference's pipeline (``linalg/ge2tb.gesvd_two_stage``), every stage
on the device but the O(k) scalar work of a merge: ``ge2tb`` (one
jitted ``shard_map`` loop alternating QR and LQ panels), the band
gathered to the host (2·nt tiles), ``tb2bd`` by the ``robust.ladder``
rung that takes the band (on a TPU in f32 the VMEM-resident Pallas
chaser at band 128, ``internal/band_wave_vmem_bd.py``), the bidiagonal
SVD (``bulge.bdsdc``: the Golub-Kahan tridiagonal of order 2n through
``linalg/stedc.py``'s device divide & conquer, U_B and V_B cut out of
its Z on the device; ``bulge.bdsqr`` on the host for values alone and
for a rank-deficient B), then two back-transforms a side:
``unmbr_tb2bd`` (``bulge.apply_bulge_reflectors``) and ``unmbr_ge2tb``.
A wide A (m < n) is factored through Aᴴ.  *Dense* is one replicated
``jnp.linalg.svd``, a one-chip shortcut the reference does not have;
``Auto`` takes it below min(m, n) = 12288 on one chip and on a grid
with fewer than four tiles a side.  ``Option.TrailingPrecision``
reaches stage 1's trailing products only: panels, T factors, the
merges and the back-transforms run at the package default
(``highest``).

What a call reports (docs/observability.md): the spans :data:`SPANS`
(a root ``slate.gesvd`` with ``routine``, ``m``, ``n``, ``nb``,
``grid``, ``jobu``, ``jobvt``, ``method``, ``path``; at its end
``method`` as resolved and, two-stage, ``band``, ``chase_backend`` and
``bidiag``, the route that answered the bidiagonal problem), every
blocking read as an ``obs.sync_read`` (``band.gather``,
``tb2bd.bidiagonal``, ``stedc.zrow`` and ``stedc.roots``: one each a
level of the D&C tree; ``gesvd.values``), and the counters
:data:`COUNTERS`.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from ..matrix import Matrix, conj_transpose
from ..types import MethodSVD, Option, get_option
from ..utils import trace
from .. import obs

# what an SVD reports: the spans (the root first, then its children in
# the order they open with both sets of vectors; ``gesvd.dense`` alone
# on the dense path) and the counters (``/metrics``):
# ``gesvd.path{path}``, ``tb2bd.backend{rung}`` (the rung whose answer
# was used), ``tb2bd.demotion{from,to}`` (a rung that was stepped
# past), ``gesvd.bidiag{route}`` (``gk_stedc``: the device divide &
# conquer on the Golub-Kahan form; ``host``: ``bulge.bdsqr``),
# ``linalg/stedc.py``'s four, and ``ge2tb.path{program}`` (``exact``:
# the one-chip program on what is left of the matrix; ``spmd``: the
# ``shard_map`` loop), which the ``ge2tb`` span under ``gesvd.stage1``
# carries as ``program`` beside ``panel`` (``xla`` in both)
SPANS = ("slate.gesvd", "gesvd.stage1", "gesvd.gather", "gesvd.stage2",
         "gesvd.bidiag", "gesvd.back.tb2bd.u", "gesvd.back.ge2tb.u",
         "gesvd.back.tb2bd.v", "gesvd.back.ge2tb.v", "gesvd.dense")
COUNTERS = ("gesvd.path", "tb2bd.backend", "tb2bd.demotion",
            "gesvd.bidiag", "stedc.merges", "stedc.poles",
            "stedc.deflated", "stedc.levels", "ge2tb.path")

# one chip: below this min(m, n) ``Auto`` takes XLA's svd (round 5's
# number; not moved here: ROADMAP R7b)
DENSE_BELOW = 12288


def _takes_two_stage(A, method) -> bool:
    if method != MethodSVD.Auto:
        return method == MethodSVD.TwoStage
    # parallel grids OR single-chip problems big enough that the
    # replicated dense SVD is the wrong tool (the reference is always
    # two-stage, src/gesvd.cc:77-102; dense is a small-n shortcut)
    return ((A.grid.size > 1 and min(A.mt, A.nt) >= 4)
            or min(A.m, A.n) >= DENSE_BELOW)


def gesvd(A: Matrix, opts=None, want_u: bool = False,
          want_vt: bool = False):
    """Singular values (and optional vectors) of A.

    Method dispatch (Option.MethodSVD): TwoStage = the reference's
    pipeline (ge2tb band reduction → tb2bd bulge chase → bidiagonal
    SVD → two back-transforms a side, linalg/ge2tb.py); Dense =
    replicated XLA SVD. Auto: two-stage on multi-chip grids with
    enough tiles and from ``DENSE_BELOW`` up, dense otherwise.

    Returns (Sigma [min(m,n)] descending, U | None, VT | None) with U
    and VT distributed on A's grid (reference gesvd.cc returns Σ and
    optionally U/VT in SLATE matrices).
    """
    from .ge2tb import gesvd_two_stage
    method = get_option(opts, Option.MethodSVD, MethodSVD.Auto)
    two = _takes_two_stage(A, method)
    path = "two_stage" if two else "dense"
    obs.count("gesvd.path", 1, path=path)
    with trace.block("slate.gesvd", routine="gesvd", m=A.m, n=A.n,
                     nb=A.nb, grid=f"{A.grid.p}x{A.grid.q}",
                     jobu="S" if want_u else "N",
                     jobvt="S" if want_vt else "N",
                     method=method.name, path=path) as root:
        if two:
            root.label(method=MethodSVD.TwoStage.name)
            Am = A.materialize()
            if Am.m >= Am.n:
                return gesvd_two_stage(Am, opts, want_u, want_vt,
                                       root=root)
            # m < n: factor Aᴴ = U'·Σ·VT' (tall), then A = VT'ᴴ·Σ·U'ᴴ —
            # the reference reaches wide inputs the same way (gesvd.cc
            # ge2tb requires m ≥ n; the driver conjugates)
            s, U2, VT2 = gesvd_two_stage(
                conj_transpose(Am).materialize(), opts, want_vt, want_u,
                root=root)
            U = (conj_transpose(VT2).materialize()
                 if want_u and VT2 is not None else None)
            VT = (conj_transpose(U2).materialize()
                  if want_vt and U2 is not None else None)
            return s, U, VT
        root.label(method=MethodSVD.Dense.name)
        with trace.block("gesvd.dense", m=A.m, n=A.n):
            d = A.materialize().to_dense()
            U = VT = None
            if want_u or want_vt:
                u, s, vt = jnp.linalg.svd(d, full_matrices=False)
                if want_u:
                    U = Matrix.from_dense(u, nb=A.nb, grid=A.grid)
                if want_vt:
                    VT = Matrix.from_dense(vt, nb=A.nb, grid=A.grid)
            else:
                s = jnp.linalg.svd(d, compute_uv=False)
        return obs.sync_read("gesvd.values", np.asarray, s), U, VT
