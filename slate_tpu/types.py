"""Enums, options and algorithm-variant registry.

Mirrors the reference's ``include/slate/enums.hh`` (Target, Option,
GridOrder, NormScope, Layout …), ``include/slate/types.hh`` (Options map,
``get_option``) and ``include/slate/method.hh`` (MethodGemm/…/MethodEig
with ``select_algo`` heuristics) — re-expressed as Python enums. The
per-call ``opts`` dict is the analog of SLATE's
``Options = std::map<Option, OptionValue>`` (types.hh:61).
"""

from __future__ import annotations

import enum
from typing import Any, Mapping


class Op(enum.Enum):
    """Transposition flag (BLAS op; reference blaspp Op)."""
    NoTrans = "n"
    Trans = "t"
    ConjTrans = "c"


class Uplo(enum.Enum):
    Lower = "l"
    Upper = "u"
    General = "g"


class Diag(enum.Enum):
    NonUnit = "n"
    Unit = "u"


class Side(enum.Enum):
    Left = "l"
    Right = "r"


class Norm(enum.Enum):
    """Matrix norm kind (reference lapackpp Norm; src/norm.cc)."""
    One = "1"
    Two = "2"
    Inf = "i"
    Fro = "f"
    Max = "m"


class NormScope(enum.Enum):
    """Reference enums.hh NormScope: Columns / Rows / Matrix."""
    Columns = "c"
    Rows = "r"
    Matrix = "m"


class Layout(enum.Enum):
    """Tile element layout (reference Layout, enums.hh).

    On TPU all tiles are row-major XLA arrays; the enum is kept for API
    parity (e.g. the RowMajor-for-fast-row-swap trick of
    reference src/getrf.cc:56-58 is a no-op here).
    """
    ColMajor = "c"
    RowMajor = "r"


class Target(enum.Enum):
    """Execution target (reference enums.hh:33-39).

    SLATE compiles every internal op for HostTask/HostNest/HostBatch/
    Devices. On TPU there is exactly one meaningful target — XLA on the
    chips — so all values dispatch to the same jitted implementations.
    The enum exists so option-compatible call sites keep working.
    """
    Host = "h"
    HostTask = "t"
    HostNest = "n"
    HostBatch = "b"
    Devices = "d"


class GridOrder(enum.Enum):
    """Process-grid rank ordering (reference enums.hh:127-131)."""
    Col = "c"
    Row = "r"


class TileReleaseStrategy(enum.Enum):
    """Kept for options parity (reference enums.hh). Functional XLA
    programs free per-step workspace automatically, so this is advisory.
    """
    None_ = "n"
    Internal = "i"
    Slate = "s"
    All = "a"


class Option(enum.Enum):
    """Option keys (reference enums.hh:69-101)."""
    ChunkSize = enum.auto()
    Lookahead = enum.auto()
    BlockSize = enum.auto()
    InnerBlocking = enum.auto()
    MaxPanelThreads = enum.auto()
    Tolerance = enum.auto()
    Target = enum.auto()
    TileReleaseStrategy = enum.auto()
    HoldLocalWorkspace = enum.auto()
    Depth = enum.auto()
    MaxIterations = enum.auto()
    UseFallbackSolver = enum.auto()
    PivotThreshold = enum.auto()
    PrintVerbose = enum.auto()
    PrintEdgeItems = enum.auto()
    PrintWidth = enum.auto()
    PrintPrecision = enum.auto()
    MethodCholQR = enum.auto()
    MethodEig = enum.auto()
    MethodGels = enum.auto()
    MethodGemm = enum.auto()
    MethodHemm = enum.auto()
    MethodLU = enum.auto()
    MethodTrsm = enum.auto()
    MethodSVD = enum.auto()
    # band width used by the two-stage eig/SVD reductions (he2hb /
    # ge2tb); tiles are re-blocked to this when the input nb is larger,
    # keeping the stage-2 bulge chase O(n²·band) cheap while stage 1
    # still batches MXU-sized updates (reference: the ib/nb split of
    # src/he2hb.cc / internal_gebr).
    EigBand = enum.auto()
    # precision tier for the O(n³) trailing updates (internal/
    # precision.py): "bf16_6x" (default, f32-equivalent 6-pass MXU
    # split), "bf16_3x" (3-pass, ~2× throughput, ~2⁻¹⁸ per-dot eps —
    # pair with iterative refinement), or "mxu_bf16" (1-pass native
    # bf16 multiplies). Panels and triangular solves always run
    # bf16_6x regardless; only trailing gemm/syrk/herk honor this.
    TrailingPrecision = enum.auto()
    # software-pipeline depth of the SPMD factorization step loops
    # (linalg/potrf.py / getrf.py): 1 factors panel k+1 and launches
    # its broadcast while step k's trailing update runs (the SLATE
    # lookahead expressed inside one shard_map program); 0 (default)
    # runs the strictly sequential panel → broadcast → update loop.
    # Opt-in: the lookahead body is a larger program whose extra
    # compile time only pays off when trailing updates are long
    # enough to hide a broadcast under. The value is a static
    # cached_jit key component — pipelined and sequential programs
    # never share an executable.
    PipelineDepth = enum.auto()
    # algorithm-based fault tolerance (robust/abft.py): maintain
    # Huang–Abraham column checksums through the factorization chunk
    # loops and verify at every chunk boundary, detecting finite
    # silent-data-corruption that finite_guard cannot see. Default
    # off — the unarmed path is byte-identical (the abft state rides
    # the cached_jit key only when armed). Detection escalates
    # retry → scratch restart → SdcDetected (an InfoError), never a
    # silent wrong factor.
    Abft = enum.auto()


Options = Mapping[Option, Any]


_DEFAULTS = {
    Option.Lookahead: 1,
    Option.BlockSize: 256,
    Option.InnerBlocking: 16,
    Option.MaxPanelThreads: 1,
    Option.Tolerance: None,
    Option.Target: Target.Devices,
    Option.MaxIterations: 30,
    Option.UseFallbackSolver: True,
    Option.PivotThreshold: 1.0,
    Option.PrintVerbose: 4,
    Option.PrintEdgeItems: 16,
    Option.PrintWidth: 10,
    Option.PrintPrecision: 4,
    Option.TrailingPrecision: "bf16_6x",
    Option.PipelineDepth: 0,
    Option.Abft: False,
}


def get_option(opts: Options | None, key: Option, default: Any = None) -> Any:
    """Typed option getter (reference types.hh:166-200)."""
    if opts is not None and key in opts:
        return opts[key]
    if default is not None:
        return default
    return _DEFAULTS.get(key)


def superstep_chunk(kt: int, lcm_pq: int, opts: Options | None = None) -> int:
    """Block-columns per SPMD super-step chunk for the multi-chip
    factorizations (potrf/getrf).

    ``Option.ChunkSize`` sets the chunk length directly (rounded up to
    an lcm(p,q) multiple so every chunk starts grid-aligned).
    Otherwise ``Option.Lookahead`` scales the pipeline depth: the
    default ``la=1`` splits the factorization into ~8 chunks
    (re-jitting on a statically shrinking trailing window); higher
    lookahead gives fewer, longer chunks — a deeper uninterrupted
    XLA pipeline with fewer host synchronization points. This is the
    reference's ``Option::Lookahead`` panels-in-flight knob
    (src/potrf.cc:88-107) expressed in the super-step scheme, where
    in-chunk overlap is XLA's collective/compute pipelining.
    """
    def _cdiv(a, b):
        return -(-a // b)

    cs = get_option(opts, Option.ChunkSize)
    if cs:
        return max(lcm_pq, _cdiv(int(cs), lcm_pq) * lcm_pq)
    la = max(1, int(get_option(opts, Option.Lookahead)))
    n_chunks = max(1, 8 // la)
    return max(lcm_pq, _cdiv(_cdiv(kt, n_chunks), lcm_pq) * lcm_pq)


# ---------------------------------------------------------------------------
# Algorithm-variant registry (reference include/slate/method.hh:25-319).
# ---------------------------------------------------------------------------

class MethodGemm(enum.Enum):
    Auto = enum.auto()
    GemmA = enum.auto()   # stationary-A
    GemmC = enum.auto()   # stationary-C (default SUMMA)
    Ring = enum.auto()    # Cannon ring-systolic (ICI neighbor hops)

    @staticmethod
    def select_algo(A, B, opts=None) -> "MethodGemm":
        """Heuristic of reference method.hh:87-92: stationary-A when B is
        a single block-column (all-reduce of A·B beats broadcasting A)."""
        m = get_option(opts, Option.MethodGemm, MethodGemm.Auto)
        if m != MethodGemm.Auto:
            return m
        return MethodGemm.GemmA if B.nt < 2 else MethodGemm.GemmC


class MethodTrsm(enum.Enum):
    Auto = enum.auto()
    TrsmA = enum.auto()
    TrsmB = enum.auto()

    @staticmethod
    def select_algo(A, B, side, opts=None) -> "MethodTrsm":
        m = get_option(opts, Option.MethodTrsm, MethodTrsm.Auto)
        if m != MethodTrsm.Auto:
            return m
        nrhs_tiles = B.nt if side == Side.Left else B.mt
        return MethodTrsm.TrsmA if nrhs_tiles < 2 else MethodTrsm.TrsmB


class MethodHemm(enum.Enum):
    Auto = enum.auto()
    HemmA = enum.auto()
    HemmC = enum.auto()

    @staticmethod
    def select_algo(A, B, opts=None) -> "MethodHemm":
        m = get_option(opts, Option.MethodHemm, MethodHemm.Auto)
        if m != MethodHemm.Auto:
            return m
        return MethodHemm.HemmA if B.nt < 2 else MethodHemm.HemmC


class MethodLU(enum.Enum):
    Auto = enum.auto()
    PartialPiv = enum.auto()
    CALU = enum.auto()      # tournament pivoting (reference getrf_tntpiv.cc)
    NoPiv = enum.auto()

    @staticmethod
    def select_algo(A, opts=None) -> "MethodLU":
        m = get_option(opts, Option.MethodLU, MethodLU.Auto)
        return MethodLU.PartialPiv if m == MethodLU.Auto else m


class MethodGels(enum.Enum):
    Auto = enum.auto()
    Geqrf = enum.auto()
    Cholqr = enum.auto()

    @staticmethod
    def select_algo(A, B, opts=None) -> "MethodGels":
        m = get_option(opts, Option.MethodGels, MethodGels.Auto)
        if m != MethodGels.Auto:
            return m
        # reference gels.cc:96-110 defaults to CholQR for tall matrices:
        # at m >= 2n Auto runs herk + potrf + a right trsm + gemm + trsm
        # and none of geqrf / unmqr. CholQR factors A^H A, so it squares
        # kappa(A): in f32 it fails from kappa(A) ~ eps^-1/2 ~ 4096 up
        # and loses kappa(A)^2 eps below that, where Householder QR
        # loses kappa(A) eps. Pass MethodGels.Geqrf for LAPACK's gels
        # (PERF.md section 4, gels_qr_f32_1x1)
        return MethodGels.Cholqr if A.m >= 2 * A.n else MethodGels.Geqrf


class MethodCholQR(enum.Enum):
    Auto = enum.auto()
    GemmA = enum.auto()
    GemmC = enum.auto()
    HerkC = enum.auto()


class MethodEig(enum.Enum):
    Auto = enum.auto()
    QR = enum.auto()    # steqr path
    DC = enum.auto()    # divide & conquer (stedc path)
    Bisection = enum.auto()
    MRRR = enum.auto()
    # slate_tpu extensions: pipeline selection (the reference always
    # runs two-stage; here the dense XLA eigh path exists too)
    Dense = enum.auto()      # replicated XLA eigh (QDWH)
    TwoStage = enum.auto()   # he2hb → hbevd → unmtr_he2hb


class MethodSVD(enum.Enum):
    Auto = enum.auto()
    QRIteration = enum.auto()
    DC = enum.auto()
    Jacobi = enum.auto()
    # slate_tpu extensions: pipeline selection
    Dense = enum.auto()      # replicated XLA SVD
    TwoStage = enum.auto()   # ge2tb → band SVD → back-transforms
