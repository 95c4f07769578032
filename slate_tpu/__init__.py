"""slate_tpu — TPU-native distributed dense linear algebra.

A ground-up JAX/XLA/Pallas re-design of the capabilities of SLATE
(Software for Linear Algebra Targeting Exascale; reference:
/root/reference, see its include/slate/slate.hh): tiled distributed
matrices, Level-3 BLAS, matrix norms, linear solvers (LU, Cholesky,
band, mixed precision), least squares (QR/CholQR), SVD and Hermitian
eigensolvers — expressed TPU-first:

* a matrix is a stack of tiles laid out 2-D block-cyclically over a
  ``jax.sharding.Mesh(p, q)`` (the analog of SLATE's MPI process grid,
  reference BaseMatrix.hh:879-905),
* every driver is a single jitted ``jax.shard_map`` program whose
  k-loop is a ``lax.fori_loop`` (the analog of SLATE's OpenMP task DAG,
  reference src/potrf.cc:53-133) — XLA overlaps the collectives with
  compute instead of a host task scheduler,
* tile broadcasts/reductions ride ICI collectives (``psum`` /
  ``all_gather``) instead of MPI hypercube P2P
  (reference BaseMatrix.hh:1916-2485).
"""

# Precision contract: results match the storage dtype. TPU's MXU
# defaults f32 matmuls to bf16 inputs (worse when the platform forces
# --xla_allow_excess_precision), which silently degrades f32
# factorizations to ~1e-1 backward error at n=400 (measured on v5e).
# A numerical library cannot do that: f32 means f32. "highest" lowers
# f32 dots to the bf16_6x scheme (f32-equivalent accuracy, measured
# gesv backward error 6e-5 vs 3e-1 at default). Users who want MXU
# bf16 throughput say so in the type system — bf16 tiles — exactly how
# the reference separates s/d precisions. Override:
# SLATE_TPU_MATMUL_PRECISION={default,high,highest}.
# Per-routine, the trailing-update tier ladder (mxu_bf16 / bf16_3x /
# bf16_6x, Option.TrailingPrecision) out-ranks this global default —
# see docs/performance.md and internal/precision.py.
import os as _os
import sys as _sys
import time as _time

_import_start_ns = _time.perf_counter_ns()
_jax_preloaded = "jax" in _sys.modules      # else jax's import is in ours

import jax as _jax  # noqa: E402

if "SLATE_TPU_MATMUL_PRECISION" in _os.environ:
    _jax.config.update("jax_default_matmul_precision",
                       _os.environ["SLATE_TPU_MATMUL_PRECISION"])
elif ("JAX_DEFAULT_MATMUL_PRECISION" not in _os.environ
      and _jax.config.jax_default_matmul_precision is None):
    # only when the user expressed no preference of their own
    _jax.config.update("jax_default_matmul_precision", "highest")

from .version import __version__, version, id  # noqa: A004

from .types import (
    Op, Uplo, Diag, Side, Norm, NormScope, Layout, Target, GridOrder,
    Option, MethodGemm, MethodTrsm, MethodHemm, MethodLU, MethodGels,
    MethodCholQR, MethodEig, MethodSVD, TileReleaseStrategy,
)
from .errors import SlateError, InfoError, slate_error_if, raise_if_info

# slateguard: numerical-health reporting, fault injection, backend
# ladder, watchdog (docs/robustness.md)
from . import robust
from .robust import HealthReport
from .grid import Grid, default_grid, single_device_grid
from .matrix import (
    Matrix, SymmetricMatrix, HermitianMatrix, TriangularMatrix,
    TrapezoidMatrix, BandMatrix, TriangularBandMatrix, HermitianBandMatrix,
    transpose, conj_transpose,
)

# Level-3 BLAS (reference include/slate/slate.hh:42-420)
from .ops.blas import (
    gemm, symm, hemm, syrk, herk, syr2k, her2k, trmm, trsm,
    gbmm, tbsm, hbmm,
)

# Elementwise / utility (reference src/{add,copy,scale,set}.cc)
from .ops.elementwise import add, copy, scale, scale_row_col, set_matrix
from .ops.norms import norm, col_norms

# Linear solvers
from .linalg.potrf import (potrf, potrf_resume, potrs, posv, pbtrf, pbtrs,
                           pbsv, potrf_dense_inplace, posv_batched)
from .linalg.getrf import (
    getrf, getrf_resume, getrf_nopiv, getrf_tntpiv, getrs, getrs_nopiv,
    gesv, gesv_nopiv, gbtrf, gbtrs, gbsv, getrf_dense_inplace, gesv_batched,
)
from .linalg.trtri import trtri, trtrm, potri, getri
from .linalg.geqrf import geqrf, gelqf, unmqr, unmlq, cholqr, gels
from .linalg.mixed import gesv_mixed, posv_mixed, gesv_mixed_gmres, posv_mixed_gmres
from .linalg.condest import gecondest, pocondest, trcondest
from .linalg.eig import heev, hegv, hegst, sterf, steqr, stedc
from .linalg.svd import gesvd
from .linalg.hetrf import hetrf, hetrs, hesv

# Simplified verb-named API (reference include/slate/simplified_api.hh)
from .simplified import (
    multiply, triangular_multiply, triangular_solve, rank_k_update,
    rank_2k_update, lu_factor, lu_solve, lu_solve_using_factor,
    lu_inverse_using_factor, lu_factor_nopiv, lu_solve_nopiv,
    lu_solve_using_factor_nopiv, lu_inverse_using_factor_out_of_place,
    chol_factor, chol_solve,
    chol_solve_using_factor, chol_inverse_using_factor,
    indefinite_factor, indefinite_solve, indefinite_solve_using_factor,
    least_squares_solve,
    qr_factor, lq_factor, qr_multiply_by_q, lq_multiply_by_q,
    eig_vals, eig, svd_vals, svd,
)

from .utils.generator import generate_matrix, random_matrix, random_spd
from .utils.printing import print_matrix
from .utils import trace

# the package's own import on the program's clock, for whoever asks
# what a process's set-up went on (obs.compile_ledger())
from .obs import tracing as _tracing  # noqa: E402
_tracing.import_record(_import_start_ns, jax_preloaded=_jax_preloaded)
