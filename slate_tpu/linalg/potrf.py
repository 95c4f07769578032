"""Cholesky: potrf / potrs / posv (+ band pbtrf/pbtrs/pbsv).

Reference: src/potrf.cc (right-looking tile Cholesky with lookahead
task DAG, :53-133 HostTask / :140-314 Devices), src/potrs.cc,
src/posv.cc, src/pbtrf.cc.

TPU redesign: the whole factorization is ONE jitted ``shard_map``
program — a ``lax.fori_loop`` over block columns k with, per step:

1. diag tile bcast + redundant [nb,nb] Cholesky on every chip
   (cheaper than bcasting the factor; replaces the device LAPACK potrf
   + tileBcast of reference src/potrf.cc:213-219),
2. panel trsm on the owner mesh-column (batched XLA TriangularSolve —
   reference internal::trsm on the panel, src/potrf.cc:222-229),
3. panel all-gather down mesh rows + bcast across mesh columns (the
   listBcastMT hypercube of src/potrf.cc:232-242 becomes one ICI
   all-gather),
4. trailing her/gemm update as a single batched einsum over every
   chip's local trailing tiles (the ≤4-class batched cuBLAS herk+gemm
   of src/potrf.cc:254-287 becomes one MXU einsum).

XLA's async scheduling overlaps step-(k+1) collectives with step-k
einsums, which is the reference's Lookahead option without a host
scheduler. Numerical failure (non-SPD) is reported through ``info``
(index of first failing block column, 0 = success) — exceptions can't
cross jit, matching LAPACK/reference info semantics.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import PartitionSpec as P

from ..cache.jitcache import cached_jit
from ..grid import AXIS_P, AXIS_Q
from ..matrix import (BaseTiledMatrix, Matrix, TriangularMatrix,
                      HermitianMatrix, cdiv, conj_transpose)
from ..types import (Op, Uplo, Diag, Side, Option, get_option,
                     superstep_chunk)
from ..errors import slate_error_if
from ..robust.guards import finite_guard
from ..internal import comm, masks
from ..internal.tile_kernels import tile_potrf, _factor_dtype
from ..internal.masks import tile_diag_pad_identity
from ..internal.precision import resolve_tier, trailing_dot_kwargs
from .. import obs
from ..obs import timeline as tl
from ..runtime import dag
from ..utils import trace
from . import _superstep


def potrf(A: HermitianMatrix, opts=None, overwrite_a: bool = False,
          health: bool = False, checkpoint=None, _resume=None):
    """Cholesky factor A = L·Lᴴ (lower) or Uᴴ·U (upper).

    Returns ``(L, info)`` — a TriangularMatrix sharing A's geometry and
    an int32 scalar info (0 ⇒ success, else 1-based index of the first
    non-positive-definite block column).

    ``overwrite_a=True`` donates A's device buffer to the factor (the
    reference's in-place semantics, LAPACK lwork-free): A must not be
    used afterwards. Halves peak HBM — required for n=32k f32 on one
    16 GB chip.

    ``health=True`` returns a :class:`~slate_tpu.robust.guards
    .HealthReport` in the info slot instead of the raw scalar — same
    info value plus the first-bad tile coordinates and an rcond
    estimate via ``pocondest`` (host-synced; an opt-in convenience,
    not for inner loops).

    ``checkpoint`` controls factorization-state checkpointing on the
    chunked multi-device path (robust.ckpt, docs/robustness.md
    "Checkpoint & resume"): ``None``/``True`` follow the
    ``SLATE_TPU_CKPT_DIR`` arming (off-by-default passthrough),
    ``False`` disables for this call, an int sets the save stride in
    chunks.  :func:`potrf_resume` picks a killed run back up
    bitwise-identically.  ``_resume`` is the internal restart state
    (use :func:`potrf_resume`).
    """
    slate_error_if(A.m != A.n, "potrf needs a square matrix")
    from ..robust import faults as _faults
    A = _faults.maybe_corrupt("potrf", A)
    Anorm = _superstep.norm_one(A, opts) if health else None
    if A.uplo == Uplo.Upper:
        # Factor the mirrored lower problem; return upper view.
        Alow = HermitianMatrix(data=_conj_transpose_data(A), m=A.m, n=A.n,
                               nb=A.nb, grid=A.grid, uplo=Uplo.Lower)
        L, info = potrf(Alow, opts, overwrite_a=True,
                        checkpoint=checkpoint, _resume=_resume)
        U = TriangularMatrix(data=_conj_transpose_data(L), m=A.m, n=A.n,
                             nb=A.nb, grid=A.grid, uplo=Uplo.Upper,
                             diag=Diag.NonUnit)
        if health:
            return U, _potrf_health(U, info, Anorm, opts)
        return U, info
    from .. import tune
    tier, depth = tune.driver_config("potrf", A.n, opts)
    with trace.block("potrf", routine="potrf", n=A.n, nb=A.nb,
                     precision=tier):
        g = A.grid
        lcm_pq = g.p * g.q // math.gcd(g.p, g.q)
        nt = A.nt
        chunked = g.size > 1 and nt >= 2 * lcm_pq
        guard = _superstep.arm("potrf", A, opts, checkpoint, chunked)
        # which body factors: the unrolled one-chip loop on the stored
        # tiles, or the uniform SPMD step (one program or chunks)
        form = "tiles" if _one_chip_unrolled(A) else "spmd"
        obs.count("potrf.path", 1, form=form)
        if chunked:
            # chunked super-steps: re-jit on a statically shrinking
            # trailing window every lcm(p,q)-aligned chunk — the
            # uniform one-program fori pays ~3x the flops (every step
            # updates the full local stack); ~8 chunks cut that to
            # ~1.1x while keeping each chunk one SPMD program.
            # Option.Lookahead / Option.ChunkSize tune the granularity
            # (types.superstep_chunk); Option.PipelineDepth picks the
            # software-pipelined chunk body (panel k+1 broadcast in
            # flight under step-k trailing update) vs the sequential
            # one — distinct routines, never a shared executable.
            S = superstep_chunk(nt, lcm_pq, opts)

            def step(data, carried, k0, klen, donate):
                Ak = A._replace(data=data)
                if depth > 0:
                    fn = (_potrf_pipe_chunk_jit_overwrite if donate
                          else _potrf_pipe_chunk_jit)
                    return fn(Ak, *carried, k0, klen, depth=depth,
                              tier=tier)
                fn = (_potrf_chunk_jit_overwrite if donate
                      else _potrf_chunk_jit)
                return fn(Ak, *carried, k0, klen, tier=tier)

            data, info = _superstep.run_chunks(
                guard, A, step, (jnp.zeros((), jnp.int32),), ("info",),
                nt, S, overwrite_a, _resume)
        else:
            def launch(donate):
                return (_potrf_jit_overwrite if donate
                        else _potrf_jit)(A, tier, depth=depth)

            with trace.block("potrf.chunk", phase="one_program",
                             k0=0, klen=nt, form=form):
                data, info = _superstep.run_one_program(
                    guard, A, launch, nt, overwrite_a)
    L = TriangularMatrix(data=data, m=A.m, n=A.n, nb=A.nb, grid=A.grid,
                         uplo=Uplo.Lower, diag=Diag.NonUnit)
    if health:
        return L, _potrf_health(L, info, Anorm, opts)
    return L, info


def _potrf_health(L, info, Anorm, opts):
    """HealthReport for a finished potrf: first-bad tile from the
    first-failure info convention; rcond via pocondest."""
    from ..types import Norm
    from .condest import pocondest
    return _superstep.health(
        "potrf", info, Anorm, opts, "first_block",
        lambda anorm: pocondest(Norm.One, L, anorm, opts))


def potrf_resume(A: HermitianMatrix, opts=None,
                 overwrite_a: bool = False, health: bool = False,
                 checkpoint=None):
    """Resume a checkpointed potrf after a preempt (robust.ckpt).

    Loads the latest valid checkpoint for the (A, opts) job —
    validating fingerprint, payload checksum, and step hash — and
    re-enters the step loop at the saved chunk boundary, producing a
    factor bitwise equal to an uninterrupted run on both the
    sequential and PipelineDepth paths.  When no valid checkpoint
    exists (never saved, corrupt → quarantined, stale fingerprint,
    different options) the call demotes to a from-scratch
    :func:`potrf` and the demotion lands in
    ``robust.ladder.demotion_log()``.  An Upper operand mirrors to the
    lower problem exactly as :func:`potrf` does — the checkpoint job
    identity is geometry-only, so the state saved by the inner lower
    loop is found either way."""
    return _superstep.resume("potrf", potrf, A, opts,
                             overwrite_a=overwrite_a, health=health,
                             checkpoint=checkpoint)


def _conj_transpose_data(A):
    """Conj-transposed storage of a square matrix, via the canonical
    materialize path (single implementation of the layout transpose)."""
    from ..matrix import conj_transpose
    G = Matrix(data=A.data, m=A.m, n=A.n, nb=A.nb, grid=A.grid)
    return conj_transpose(G).materialize().data


def _syrk_update_inplace(a, r0, nsub, v, cplx, cutoff=2048, tier=None):
    """a[r0:r0+nsub, r0:r0+nsub] −= v·vᴴ touching (mostly) only the
    lower-triangular blocks: recursive 2×2 split — the diagonal halves
    recurse, the off-diagonal quarter is one rectangular gemm. Saves
    ~45% of the trailing flops a full square gemm would spend on the
    (junk-by-contract) upper half, with every op still a big MXU
    matmul. Reference analog: internal::herk's triangle-aware batching
    (src/internal/internal_herk.cc)."""
    pk = trailing_dot_kwargs(tier, a.dtype)
    if nsub <= cutoff:
        blk = a[r0:r0 + nsub, r0:r0 + nsub]
        vh = jnp.conj(v.T) if cplx else v.T
        return a.at[r0:r0 + nsub, r0:r0 + nsub].set(
            blk - jnp.matmul(v, vh, **pk))
    h = nsub // 2
    a = _syrk_update_inplace(a, r0, h, v[:h], cplx, cutoff, tier)
    vh = jnp.conj(v[:h].T) if cplx else v[:h].T
    c21 = a[r0 + h:r0 + nsub, r0:r0 + h]
    a = a.at[r0 + h:r0 + nsub, r0:r0 + h].set(
        c21 - jnp.matmul(v[h:], vh, **pk))
    return _syrk_update_inplace(a, r0 + h, nsub - h, v[h:], cplx, cutoff,
                                tier)


def _potrf_dense_loop(a, nb, n, Mp, tier=None):
    """Unrolled blocked Cholesky on a dense [Mp, ≥Mp] array (rows ≥ n
    padded with an identity diagonal by the caller). Peak memory =
    the array itself + one [*, nb] panel + ≤[*, 2048] syrk blocks.
    The body of the batched serving path, which holds dense instances
    (``serve/batched.py`` vmaps it); the one-chip ``potrf`` runs the
    same steps on its stored tiles (:func:`_potrf_dense_1dev`) and the
    64k-class dense-in-place entry in groups
    (:func:`_potrf_dense_group_core`)."""
    nt = cdiv(n, nb)
    cplx = jnp.issubdtype(a.dtype, jnp.complexfloating)
    info = jnp.zeros((), jnp.int32)
    for k in range(nt):
        r0 = k * nb
        with jax.named_scope("panel"):
            akk = a[r0:r0 + nb, r0:r0 + nb]
            low = jnp.tril(akk)
            strict = jnp.tril(akk, -1)
            akk = low + (jnp.conj(strict.T) if cplx else strict.T)
            lkk, info = finite_guard(tile_potrf(akk), info, k + 1,
                                     diag=True, cplx=cplx)
            a = a.at[r0:r0 + nb, r0:r0 + nb].set(jnp.tril(lkk))
        if r0 + nb < Mp:
            with jax.named_scope("panel"):
                # low-precision tiles solve the panel in f32 (XLA's
                # TriangularSolve needs >= f32; storage stays bf16)
                fd = _factor_dtype(a.dtype)
                pan = lax.linalg.triangular_solve(
                    lkk.astype(fd), a[r0 + nb:, r0:r0 + nb].astype(fd),
                    left_side=False, lower=True,
                    transpose_a=True, conjugate_a=cplx).astype(a.dtype)
                pan, info = finite_guard(pan, info, k + 1, cplx=cplx)
                a = a.at[r0 + nb:, r0:r0 + nb].set(pan)
            with jax.named_scope("trailing"):
                a = _syrk_update_inplace(a, r0 + nb, Mp - r0 - nb, pan, cplx,
                                         tier=tier)
    return a, info


def _potrf_dense_group_core(a, info0, k0, gcount, nb, tier=None):
    """One group of ``gcount`` unrolled panels of the dense in-place
    Cholesky, starting at row/col ``k0``. Groups keep each compiled
    program within the toolchain's AOT-helper limits (an n=45k fully
    unrolled 44-panel program crashes the remote compile helper; ≤32
    panels per program is the measured-good envelope)."""
    n = a.shape[0]
    cplx = jnp.issubdtype(a.dtype, jnp.complexfloating)
    info = info0
    for kk in range(gcount):
        r0 = k0 + kk * nb
        akk = a[r0:r0 + nb, r0:r0 + nb]
        low = jnp.tril(akk)
        strict = jnp.tril(akk, -1)
        akk = low + (jnp.conj(strict.T) if cplx else strict.T)
        lkk, info = finite_guard(tile_potrf(akk), info, r0 // nb + 1,
                                 diag=True, cplx=cplx)
        a = a.at[r0:r0 + nb, r0:r0 + nb].set(jnp.tril(lkk))
        if r0 + nb < n:
            fd = _factor_dtype(a.dtype)
            pan = lax.linalg.triangular_solve(
                lkk.astype(fd), a[r0 + nb:, r0:r0 + nb].astype(fd),
                left_side=False, lower=True,
                transpose_a=True, conjugate_a=cplx).astype(a.dtype)
            pan, info = finite_guard(pan, info, r0 // nb + 1, cplx=cplx)
            a = a.at[r0 + nb:, r0:r0 + nb].set(pan)
            a = _syrk_update_inplace(a, r0 + nb, n - r0 - nb, pan, cplx,
                                     tier=tier)
    return a, info


_potrf_dense_group_jit = cached_jit(_potrf_dense_group_core,
                                    routine="potrf.dense_group",
                                    donate_argnums=0,
                                    static_argnames=("k0", "gcount",
                                                     "nb", "tier"))


def potrf_dense_inplace(a, nb: int = 1024, group: int = 16, opts=None):
    """Cholesky of a dense LAPACK-layout array IN PLACE (donated
    buffer): the 64k-class single-chip entry, for a caller who holds
    the matrix dense. Bringing it into a tiled Matrix and back is a
    layout permutation each way — a full transient copy, which at an
    8 GB matrix exceeds HBM (the tiled :func:`potrf` itself converts
    nothing: on one chip it factors its tiles where they are stored);
    this entry skips the Matrix container entirely, peak memory ≈ the
    array itself. The factorization runs as ⌈nt/group⌉ donated jit programs
    of ``group`` unrolled panels each. n must be a multiple of nb.
    Returns (L_dense, info) — reference analog: slate::potrf's
    in-place semantics on fromLAPACK-style user storage
    (src/potrf.cc:366-394).
    """
    slate_error_if(a.ndim != 2 or a.shape[0] != a.shape[1],
                   "potrf_dense_inplace needs a square 2-D array")
    slate_error_if(a.shape[0] % nb != 0,
                   "potrf_dense_inplace: n must be a multiple of nb")
    nt = a.shape[0] // nb
    n = a.shape[0]
    info = jnp.zeros((), jnp.int32)
    tier = resolve_tier(opts)
    with trace.block("potrf_dense_inplace", routine="potrf",
                     n=n, nb=nb, precision=tier):
        for g0 in range(0, nt, group):
            with trace.block("potrf.dense_group", phase="dense_group",
                             k0=g0 * nb,
                             gcount=min(group, nt - g0)):
                a, info = _potrf_dense_group_jit(a, info, g0 * nb,
                                                 min(group, nt - g0),
                                                 nb=nb, tier=tier)
    return a, info


def _herk_update_tiles(t, i0, p, cplx, ctiles, pk):
    """t[i0:i0+len(p), i0:i0+len(p)] −= p·pᴴ on stored tiles, the twin
    of :func:`_syrk_update_inplace` in tile coordinates: ``t`` is
    ``[mt, nt, nb, nb]``, ``p`` the panel tiles ``[nsub, nb, nb]`` of
    tile rows i0 onward. The split falls on tile indices; a window of at
    most ``ctiles`` tiles a side is one product."""
    nsub = p.shape[0]

    def window(t, r0, r1, c0, c1):
        # 'injm' + transpose writes the product in the tiles' own
        # layout, fused with the in-place update: with 'ijnm' XLA
        # copies the window to another layout and back (PERF.md §6
        # PR 50)
        cols = jnp.conj(p[c0:c1]) if cplx else p[c0:c1]
        upd = jnp.einsum("inb,jmb->injm", p[r0:r1], cols,
                         **pk).transpose(0, 2, 1, 3)
        return t.at[i0 + r0:i0 + r1, i0 + c0:i0 + c1].add(-upd)

    if nsub <= ctiles:
        return window(t, 0, nsub, 0, nsub)
    h = nsub // 2
    t = _herk_update_tiles(t, i0, p[:h], cplx, ctiles, pk)
    t = window(t, h, nsub, 0, h)
    return _herk_update_tiles(t, i0 + h, p[h:], cplx, ctiles, pk)


def _potrf_dense_1dev(A, tier=None):
    """One-chip fast path (the twin of ``_getrf_dense_1dev``; the name
    is the family's): the unrolled blocked Cholesky of
    :func:`_potrf_dense_loop`, step for step, on the stored tiles
    ``A.data[0, 0]`` themselves. The SPMD fori_loop path must keep
    every step uniform (full-matrix masked einsum, ~3x the flops on one
    chip); with no communication the loop unrolls at trace time with
    shrinking trailing shapes instead. Every slice is taken in tile
    coordinates — the diagonal tile ``t[k, k]``, the panel ``t[k+1:,
    k]`` (a row-major ``[rows, nb]`` panel by a free reshape), the
    trailing windows of :func:`_herk_update_tiles` — so the matrix is
    never brought to a dense ``[n, n]`` form and back: that cost five
    matrix-sized copies a call, 16.1 ms of ``posv_16k_1x1``'s 88.8 ms a
    solve (ledger, PR 49; PERF.md §6 PR 50). Same numerics, same info
    semantics; tiles past the last block column are not touched."""
    nb, n = A.nb, A.n
    nt = cdiv(n, nb)
    t = A.data[0, 0]
    cplx = jnp.issubdtype(t.dtype, jnp.complexfloating)
    pk = trailing_dot_kwargs(tier, t.dtype)
    fd = _factor_dtype(t.dtype)
    ctiles = max(1, 2048 // nb)       # _syrk_update_inplace's cut-off
    row_major = Layout(major_to_minor=(0, 1, 2, 3))
    info = jnp.zeros((), jnp.int32)
    for k in range(nt):
        with jax.named_scope("panel"):
            akk = t[k, k]
            if (k + 1) * nb > n:
                akk = tile_diag_pad_identity(akk, k, n, nb)
            low = jnp.tril(akk)
            strict = jnp.tril(akk, -1)
            akk = low + (jnp.conj(strict.T) if cplx else strict.T)
            lkk, info = finite_guard(tile_potrf(akk), info, k + 1,
                                     diag=True, cplx=cplx)
            t = t.at[k, k].set(jnp.tril(lkk))
        if k + 1 < nt:
            with jax.named_scope("panel"):
                # low-precision tiles solve the panel in f32 (XLA's
                # TriangularSolve needs >= f32; storage stays bf16)
                pan = lax.linalg.triangular_solve(
                    lkk.astype(fd),
                    t[k + 1:nt, k].reshape(-1, nb).astype(fd),
                    left_side=False, lower=True,
                    transpose_a=True, conjugate_a=cplx).astype(t.dtype)
                pan, info = finite_guard(pan, info, k + 1, cplx=cplx)
                pan = pan.reshape(-1, nb, nb)
                t = t.at[k + 1:nt, k].set(pan)
            with jax.named_scope("trailing"):
                t = _herk_update_tiles(t, k + 1, pan, cplx, ctiles, pk)
            # one layout from the first panel to the last: left to
            # itself XLA's layout assignment turns the tiles of the
            # whole array column-major for the tail of the loop and
            # back, two matrix-sized copies (getrf._fast_group_program
            # pins its parameter against the same flip). A complex
            # array cannot be pinned: the TPU compiler carries it as
            # two real ones and has no such rewrite of the constraint.
            if not cplx:
                t = with_layout_constraint(t, row_major)
    return t[None, None], info


def _one_chip_unrolled(A) -> bool:
    """The rule of the one-chip path, read off the operand: one device
    and few enough block columns to unroll at trace time (past ~64
    compile time outgrows the win and the uniform fori_loop program is
    the better trade)."""
    return A.grid.size == 1 and cdiv(A.n, A.nb) <= 64


def _potrf_core(A, tier=None, depth=0):
    g = A.grid
    if _one_chip_unrolled(A):
        return _potrf_dense_1dev(A, tier)
    if g.size > 1 and depth > 0:
        # software-pipelined lookahead loop (Option.PipelineDepth ≥ 1)
        return _potrf_pipe_chunk_core(A, jnp.zeros((), jnp.int32), 0,
                                      A.nt, depth=depth, tier=tier)
    # the uniform SPMD program is the k0=0, klen=nt chunk
    return _potrf_chunk_core(A, jnp.zeros((), jnp.int32), 0, A.nt,
                             tier=tier)


_potrf_jit = cached_jit(_potrf_core, routine="potrf",
                        static_argnames=("tier", "depth"))
# in-place variant: A's buffer is donated to the factor (the
# reference factors in place; without donation an n=32k f32 matrix
# needs 8 GB for the A/L pair — donation halves it)
_potrf_jit_overwrite = cached_jit(_potrf_core, routine="potrf.overwrite",
                                  donate_argnums=0,
                                  static_argnames=("tier", "depth"))


def _potrf_chunk_core(A, info0, k0, klen, win_hi=None, tier=None):
    """One chunk of the SPMD factorization: block columns
    [k0, k0+klen) with all compute restricted to the static trailing
    window [k0//p:, k0//q:] of the local tile stacks. ``k0`` must be a
    multiple of lcm(p, q) so the window is itself a valid block-cyclic
    layout (tile (i, j) keeps owner ((i−k0)%p, (j−k0)%q)).

    ``win_hi`` (static) restricts the trailing updates to tile columns
    < win_hi — the DAG runtime's factor tasks use it to leave the far
    trailing matrix to concurrent tail tasks (runtime/hosttask.py
    potrf_superstep_dag, reference lookahead split potrf.cc:88-107)."""
    g = A.grid
    p, q, nb = g.p, g.q, A.nb
    n, nt = A.n, A.nt
    mtl, ntl = A.data.shape[2], A.data.shape[3]
    cplx = jnp.issubdtype(A.dtype, jnp.complexfloating)
    pk = trailing_dot_kwargs(tier, A.dtype)
    r0s, c0s = k0 // p, k0 // q
    msub = mtl - r0s

    def body(a, info):
        a = a[0, 0]
        r, c = comm.coords()
        sub = a[r0s:, c0s:]
        gi = masks.local_tile_rows(mtl, p)[r0s:]   # global tile rows
        gj = masks.local_tile_cols(ntl, q)[c0s:]

        # slatetimeline device track: mesh ordinal r·q + c; step-
        # indexed barriers fence the panel collective and the trailing
        # einsum so the overlap analyzer can pair them (no-ops — and
        # absent from the traced program — unless capture is on)
        dev = r * q + c
        ndev = p * q

        def step(k, carry):
            sub, info = carry
            sub = tl.mark(sub, "step", step=k, device=dev,
                          kind=tl.KIND_STEP, edge="b", routine="potrf",
                          ndev=ndev)
            with jax.named_scope("panel"):
                akk = lax.dynamic_slice(
                    sub, (k // p - r0s, k // q - c0s, 0, 0),
                    (1, 1, nb, nb))[0, 0]
                akk = comm.bcast_from_owner(akk, k % p, k % q)
                akk = tile_diag_pad_identity(akk, k, n, nb)
                low = jnp.tril(akk)
                strict = jnp.tril(akk, -1)
                akk = low + (jnp.conj(strict.T) if cplx else strict.T)
                lkk, info = finite_guard(tile_potrf(akk), info, k + 1,
                                         diag=True, cplx=cplx)

                pcol = lax.dynamic_index_in_dim(sub, k // q - c0s, axis=1,
                                                keepdims=False)
                below = gi > k
                solved = lax.linalg.triangular_solve(
                    jnp.broadcast_to(lkk, (msub, nb, nb)), pcol,
                    left_side=False, lower=True, transpose_a=True,
                    conjugate_a=cplx)
                pcol_new = jnp.where(below[:, None, None], solved, pcol)
                pcol_new = jnp.where(
                    (gi == k)[:, None, None],
                    jnp.broadcast_to(jnp.tril(lkk), (msub, nb, nb)),
                    pcol_new)
                sub = jnp.where(
                    (c == k % q),
                    lax.dynamic_update_index_in_dim(
                        sub, pcol_new, k // q - c0s, axis=1), sub)

                panel_masked = jnp.where(below[:, None, None], pcol_new,
                                         jnp.zeros_like(pcol_new))
            panel_masked = tl.mark(panel_masked, "panel_bcast", step=k,
                                   device=dev, kind=tl.KIND_COLLECTIVE,
                                   edge="b", routine="potrf", ndev=ndev)
            with jax.named_scope("panel_bcast"):
                full = comm.allgather_panel_rows(panel_masked, p, k % q)
            full = tl.mark(full, "panel_bcast", step=k, device=dev,
                           kind=tl.KIND_COLLECTIVE, edge="e",
                           routine="potrf", ndev=ndev)
            # gathered index g = (slot−r0s)·p + r ⇒ global tile g+k0…
            with jax.named_scope("trailing"):
                lrows = jnp.take(full, gi - r0s * p, axis=0)
                lcols = jnp.take(
                    full, jnp.clip(gj - r0s * p, 0, msub * p - 1), axis=0)
                if cplx:
                    lcols = jnp.conj(lcols)
            lrows = tl.mark(lrows, "trailing", step=k, device=dev,
                            kind=tl.KIND_COMPUTE, edge="b",
                            routine="potrf", ndev=ndev)
            with jax.named_scope("trailing"):
                upd = jnp.einsum("aik,bjk->abij", lrows, lcols, **pk)
                keep = ((gi > k) & (gi < nt))[:, None, None, None] \
                    & ((gj > k) & (gj < nt))[None, :, None, None]
                if win_hi is not None:
                    keep = keep & (gj < win_hi)[None, :, None, None]
                sub = sub - jnp.where(keep, upd, jnp.zeros_like(upd))
            sub = tl.mark(sub, "trailing", step=k, device=dev,
                          kind=tl.KIND_COMPUTE, edge="e",
                          routine="potrf", ndev=ndev)
            sub = tl.mark(sub, "step", step=k, device=dev,
                          kind=tl.KIND_STEP, edge="e", routine="potrf",
                          ndev=ndev)
            return sub, info

        sub, info = lax.fori_loop(k0, k0 + klen, step, (sub, info))
        a = a.at[r0s:, c0s:].set(sub)
        return a[None, None], info

    return jax.shard_map(
        body, mesh=g.mesh, in_specs=(P(AXIS_P, AXIS_Q), P()),
        out_specs=(P(AXIS_P, AXIS_Q), P()), check_vma=False)(
            A.data, info0)


_potrf_chunk_jit = cached_jit(_potrf_chunk_core, routine="potrf.chunk",
                              static_argnames=("k0", "klen", "win_hi",
                                               "tier"))
_potrf_chunk_jit_overwrite = cached_jit(
    _potrf_chunk_core, routine="potrf.chunk.overwrite", donate_argnums=0,
    static_argnames=("k0", "klen", "win_hi", "tier"))


def _potrf_pipe_chunk_core(A, info0, k0, klen, depth=1, tier=None):
    """Software-pipelined chunk at lookahead depth ``depth``: the
    schedule comes from the DAG runtime (``runtime.dag.chunk_plan``),
    which validates it against the window's task DAG and the bitwise
    per-column contract before this trace consumes it (SLATE's
    ``Option::Lookahead`` task priorities, reference
    src/potrf.cc:88-107, as a scheduler parameter).

    Steady-state iteration k (effective depth d = min(depth, klen-1)):

    1. ``consume``  — retire the ring buffer holding step k's gathered
       panel (its all-gather went on the wire d iterations ago);
    2. ``advance``  — bring tile column k+d fully up to date by
       applying steps k … k+d-1 to it, in step order, from the ring;
    3. ``factor``   — factor panel k+d from that column and LAUNCH its
       all-gather: d panel broadcasts are now in flight at once;
    4. ``trailing`` — step k's big trailing update (columns > k+d)
       runs behind them, hiding up to d collectives.

    Per-element update order is identical to :func:`_potrf_chunk_core`
    at every depth — each tile column receives each step's contraction
    exactly once, in ascending step order — so results are bitwise
    reproducible across depths on a given mesh (the plan validator
    enforces the coverage half; this body keeps the arithmetic of each
    op unchanged).  Depth 1 is the degenerate one-deep ring, program-
    identical to the old hand-rolled pipeline.  ``depth`` is static
    and part of the executable-cache key: programs of different depth
    never share an executable."""
    plan = dag.chunk_plan("potrf", k0, klen, depth)
    d = plan.d_eff
    g = A.grid
    p, q, nb = g.p, g.q, A.nb
    n, nt = A.n, A.nt
    mtl, ntl = A.data.shape[2], A.data.shape[3]
    cplx = jnp.issubdtype(A.dtype, jnp.complexfloating)
    pk = trailing_dot_kwargs(tier, A.dtype)
    r0s, c0s = k0 // p, k0 // q
    msub = mtl - r0s
    k_last = k0 + klen - 1
    ep0 = k0 + klen - d               # first epilogue step

    def body(a, info):
        a = a[0, 0]
        r, c = comm.coords()
        sub = a[r0s:, c0s:]
        gi = masks.local_tile_rows(mtl, p)[r0s:]
        gj = masks.local_tile_cols(ntl, q)[c0s:]
        dev = r * q + c
        ndev = p * q

        def factor_panel(kk, sub, info):
            """Factor panel kk (diag bcast + redundant tile Cholesky +
            owner-column trsm), write it back, and ISSUE its
            all-gather; returns the in-flight gathered panel."""
            akk = lax.dynamic_slice(
                sub, (kk // p - r0s, kk // q - c0s, 0, 0),
                (1, 1, nb, nb))[0, 0]
            akk = comm.bcast_from_owner(akk, kk % p, kk % q)
            akk = tile_diag_pad_identity(akk, kk, n, nb)
            low = jnp.tril(akk)
            strict = jnp.tril(akk, -1)
            akk = low + (jnp.conj(strict.T) if cplx else strict.T)
            lkk, info = finite_guard(tile_potrf(akk), info, kk + 1,
                                     diag=True, cplx=cplx)
            pcol = lax.dynamic_index_in_dim(sub, kk // q - c0s, axis=1,
                                            keepdims=False)
            below = gi > kk
            solved = lax.linalg.triangular_solve(
                jnp.broadcast_to(lkk, (msub, nb, nb)), pcol,
                left_side=False, lower=True, transpose_a=True,
                conjugate_a=cplx)
            pcol_new = jnp.where(below[:, None, None], solved, pcol)
            pcol_new = jnp.where(
                (gi == kk)[:, None, None],
                jnp.broadcast_to(jnp.tril(lkk), (msub, nb, nb)),
                pcol_new)
            sub = jnp.where(
                (c == kk % q),
                lax.dynamic_update_index_in_dim(
                    sub, pcol_new, kk // q - c0s, axis=1), sub)
            panel_masked = jnp.where(below[:, None, None], pcol_new,
                                     jnp.zeros_like(pcol_new))
            panel_masked = dag.mark(panel_masked, "panel_bcast",
                                    step=kk, device=dev, edge="b",
                                    routine="potrf", ndev=ndev)
            return sub, info, comm.allgather_panel_rows(
                panel_masked, p, kk % q)

        def advance(s, j, sub, gathered):
            """Apply step s's rank-nb update to tile column j only,
            from step s's gathered panel."""
            lrows = jnp.take(gathered, gi - r0s * p, axis=0)
            lcol = lax.dynamic_index_in_dim(gathered, j - r0s * p,
                                            axis=0, keepdims=False)
            if cplx:
                lcol = jnp.conj(lcol)
            upd = jnp.einsum("aik,bjk->abij", lrows, lcol[None],
                             **pk)[:, 0]
            keep = (gi > s) & (gi < nt)
            ccur = lax.dynamic_index_in_dim(sub, j // q - c0s, axis=1,
                                            keepdims=False)
            cnew = ccur - jnp.where(keep[:, None, None], upd,
                                    jnp.zeros_like(upd))
            return jnp.where(
                (c == j % q),
                lax.dynamic_update_index_in_dim(
                    sub, cnew, j // q - c0s, axis=1), sub)

        def trailing(k, sub, gathered, jlo):
            """Step k's trailing einsum from the ring buffer,
            restricted to tile columns > jlo."""
            lrows = jnp.take(gathered, gi - r0s * p, axis=0)
            lcols = jnp.take(
                gathered, jnp.clip(gj - r0s * p, 0, msub * p - 1),
                axis=0)
            if cplx:
                lcols = jnp.conj(lcols)
            lrows = dag.mark(lrows, "trailing", step=k, device=dev,
                             edge="b", routine="potrf", ndev=ndev)
            upd = jnp.einsum("aik,bjk->abij", lrows, lcols, **pk)
            keep = ((gi > k) & (gi < nt))[:, None, None, None] \
                & ((gj > jlo) & (gj < nt))[None, :, None, None]
            sub = sub - jnp.where(keep, upd, jnp.zeros_like(upd))
            return dag.mark(sub, "trailing", step=k, device=dev,
                            edge="e", routine="potrf", ndev=ndev)

        # prologue (plan-driven): fill the ring — factor k0, then for
        # t < d advance column k0+t through every factored step and
        # factor it, putting d gathers in flight
        ring = ()
        for op in plan.prologue:
            if op[0] == "factor":
                sub, info, fresh = factor_panel(op[1], sub, info)
                ring = ring + (fresh,)
            else:                                    # ("advance", j, srcs)
                for s in op[2]:
                    sub = advance(s, op[1], sub, ring[s - k0])

        def step(k, carry):
            sub, info, ring = carry
            fresh = None
            sub = dag.mark(sub, "step", step=k, device=dev, edge="b",
                           routine="potrf", ndev=ndev)
            for op in plan.body:
                if op[0] == "consume":
                    ring = (dag.mark(ring[0], "panel_bcast", step=k,
                                     device=dev, edge="e",
                                     routine="potrf", ndev=ndev),
                            ) + ring[1:]
                elif op[0] == "advance":
                    for t in op[2]:
                        sub = advance(k + t, k + op[1], sub, ring[t])
                elif op[0] == "factor":
                    sub, info, fresh = factor_panel(k + op[1], sub,
                                                    info)
                else:                                # ("trailing", 0, d)
                    sub = trailing(k + op[1], sub, ring[0],
                                   k + op[1] + op[2])
            sub = dag.mark(sub, "step", step=k, device=dev, edge="e",
                           routine="potrf", ndev=ndev)
            return sub, info, ring[1:] + (fresh,)

        sub, info, ring = lax.fori_loop(plan.body_lo, plan.body_hi,
                                        step, (sub, info, ring))

        # epilogue (plan-driven): drain the ring — the last d steps
        # have no panel left to put in flight
        for op in plan.epilogue:
            k = op[1]
            if op[0] == "consume":
                sub = dag.mark(sub, "step", step=k, device=dev,
                               edge="b", routine="potrf", ndev=ndev)
                slot = k - ep0
                ring = ring[:slot] + (dag.mark(
                    ring[slot], "panel_bcast", step=k, device=dev,
                    edge="e", routine="potrf", ndev=ndev),
                    ) + ring[slot + 1:]
            else:                                    # ("trailing", k, None)
                sub = trailing(k, sub, ring[k - ep0], k_last)
                sub = dag.mark(sub, "step", step=k, device=dev,
                               edge="e", routine="potrf", ndev=ndev)

        a = a.at[r0s:, c0s:].set(sub)
        return a[None, None], info

    return jax.shard_map(
        body, mesh=g.mesh, in_specs=(P(AXIS_P, AXIS_Q), P()),
        out_specs=(P(AXIS_P, AXIS_Q), P()), check_vma=False)(
            A.data, info0)


_potrf_pipe_chunk_jit = cached_jit(
    _potrf_pipe_chunk_core, routine="potrf.chunk.pipe",
    static_argnames=("k0", "klen", "depth", "tier"))
_potrf_pipe_chunk_jit_overwrite = cached_jit(
    _potrf_pipe_chunk_core, routine="potrf.chunk.pipe.overwrite",
    donate_argnums=0,
    static_argnames=("k0", "klen", "depth", "tier"))


def _potrf_tail_core(A, k0, klen, lo, hi, tier=None):
    """Deferred trailing update of one factored chunk: subtract the
    chunk's panel contributions V·Vᴴ from tile columns [lo, hi) only
    (the factor task stopped at win_hi = lo). One gathered panel
    column + one masked einsum per chunk column — the tail half of the
    reference's lookahead DAG (src/potrf.cc:254-287 trailing tasks)."""
    g = A.grid
    p, q, nb = g.p, g.q, A.nb
    nt = A.nt
    mtl, ntl = A.data.shape[2], A.data.shape[3]
    cplx = jnp.issubdtype(A.dtype, jnp.complexfloating)
    pk = trailing_dot_kwargs(tier, A.dtype)
    mt_p = mtl * p

    def body(a):
        a = a[0, 0]
        gi = masks.local_tile_rows(mtl, p)
        gj = masks.local_tile_cols(ntl, q)

        def step(k, a):
            pcol = lax.dynamic_index_in_dim(a, k // q, axis=1,
                                            keepdims=False)
            below = gi > k
            panel_masked = jnp.where(below[:, None, None], pcol,
                                     jnp.zeros_like(pcol))
            full = comm.allgather_panel_rows(panel_masked, p, k % q)
            lrows = jnp.take(full, gi, axis=0)
            lcols = jnp.take(full, jnp.clip(gj, 0, mt_p - 1), axis=0)
            if cplx:
                lcols = jnp.conj(lcols)
            upd = jnp.einsum("aik,bjk->abij", lrows, lcols, **pk)
            keep = ((gi > k) & (gi < nt))[:, None, None, None] \
                & ((gj >= lo) & (gj < min(hi, nt)))[None, :, None, None]
            return a - jnp.where(keep, upd, jnp.zeros_like(upd))

        a = lax.fori_loop(k0, k0 + klen, step, a)
        return a[None, None]

    return jax.shard_map(
        body, mesh=g.mesh, in_specs=(P(AXIS_P, AXIS_Q),),
        out_specs=P(AXIS_P, AXIS_Q), check_vma=False)(A.data)


_potrf_tail_jit = cached_jit(_potrf_tail_core, routine="potrf.tail",
                             static_argnames=("k0", "klen", "lo", "hi",
                                              "tier"))


def potrs(L: TriangularMatrix, B: Matrix, opts=None) -> Matrix:
    """Solve A·X = B given the Cholesky factor (reference src/potrs.cc):
    L·Y = B then Lᴴ·X = Y (lower), or Uᴴ·Y = B then U·X = Y."""
    from ..ops.blas import trsm
    with trace.block("potrs"):
        Y = trsm(Side.Left, 1.0, L, B, opts)
        X = trsm(Side.Left, 1.0, conj_transpose(L), Y, opts)
    return X


def posv(A: HermitianMatrix, B: Matrix, opts=None):
    """Solve A·X = B by Cholesky (reference src/posv.cc).
    Returns (X, L, info)."""
    with trace.block("slate.posv", routine="posv", n=A.n, nb=A.nb,
                     nrhs=B.n, grid=f"{A.grid.p}x{A.grid.q}"):
        L, info = potrf(A, opts)
        X = potrs(L, B, opts)
    return X, L, info


def posv_batched(a, b, opts=None, *, nb: int | None = None):
    """Leading-axis batched SPD solve on dense ``[batch, n, n]`` /
    ``[batch, n, nrhs]`` stacks — the serving-path sibling of
    :func:`posv` (one executable per (bucket, batch rung, tier); see
    ``slate_tpu.serve.batched``).  Returns ``(x, l, info)`` with
    per-instance info codes."""
    from ..serve.batched import batched_posv
    return batched_posv(a, b, opts, nb=nb)


# ---------------------------------------------------------------------------
# Band Cholesky (reference src/pbtrf.cc / pbtrs.cc / pbsv.cc).
# Packed-band kernel: one jit, O(n·kd²) flops / O(n·kd) factor storage
# via a sliding dense window over LAPACK lower band layout — replaces
# the reference's kd-deep tile task DAG (see linalg/band.py).
# ---------------------------------------------------------------------------

def pbtrf(A, opts=None, health: bool = False):
    """Band Cholesky. Returns ``(BandCholFactor, info)`` — the packed
    lower factor (``.to_dense()`` for the dense L).  ``health=True``
    swaps the info scalar for a HealthReport (same convention as
    potrf: 1-based first non-SPD block column)."""
    from . import band as _band
    Am = A.materialize()          # resolves op views; flips uplo/kl/ku
    upper = Am.uplo == Uplo.Upper
    kd = Am.ku if upper else Am.kl
    nbw = _band._band_block(Am.n, kd)
    nt = cdiv(Am.n, nbw)
    ncols = nt * nbw + nbw + kd
    with trace.block("pbtrf"):
        ab = _band.pack_tiled(Am, kd, 0, ncols,
                              mode="mirror_upper" if upper else "full")
        ab, info = _band.pbtrf_packed(ab, Am.n, kd, nbw)
    F = _band.BandCholFactor(ab, Am.n, kd)
    if health:
        from ..robust.guards import health_report
        return F, health_report("pbtrf", int(info),
                                convention="first_block")
    return F, info


def pbtrs(L, B: Matrix, opts=None) -> Matrix:
    """Solve from a pbtrf ``BandCholFactor``."""
    from . import band as _band
    slate_error_if(L.n != B.m, "pbtrs dims")
    kd, n = L.kd, L.n
    nbw = _band._band_block(n, kd)
    pad = cdiv(n, nbw) * nbw + kd
    with trace.block("pbtrs"):
        b = _band._b_to_dense(B, pad)
        x = _band.pbtrs_packed(L.ab, b, n, kd, nbw)
        return _band._dense_to_b(x, B)


def pbsv(A, B: Matrix, opts=None):
    L, info = pbtrf(A, opts)
    X = pbtrs(L, B, opts)
    return X, L, info


def san_cases(grid, opts=None, n=64, nb=16):
    """slatesan sweep entry: (label, thunk) pairs running this
    driver's jitted surface once at a small shape on ``grid``, so
    every cached_jit compile-tier miss flows through the verifier
    (tools/slatesan; armed by SLATE_TPU_SAN=1 + an armed store)."""
    import numpy as np

    def run():
        rng = np.random.default_rng(12)
        a = rng.standard_normal((n, n)).astype(np.float32)
        a = a @ a.T + n * np.eye(n, dtype=np.float32)
        A = HermitianMatrix.from_dense(a, nb=nb, grid=grid)
        L, info = potrf(A, opts=opts)
        return info.block_until_ready()
    return [("potrf", run)]
