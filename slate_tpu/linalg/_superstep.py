"""The host side of a guarded factorization, written once for
``potrf`` and ``getrf``: checkpoint/resume (``robust.ckpt``), checksum
verification with retry → scratch → fail recovery (``robust.abft``)
and the chunk-boundary fault hooks (``robust.faults``) around the
routine's launches.  The routine keeps what only it knows — its
executables, the state it carries beside ``data`` and that state's
fresh value — and passes it in; nothing here names an executable.
With abft, checkpointing and fault injection off, both loops do no
host sync and no ``device_put``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .. import obs
from ..robust import abft, ckpt, faults
from ..robust.guards import health_report
from ..utils import trace


class Guard(NamedTuple):
    routine: str
    ck: object          # ckpt.CheckpointPlan or None
    ab: object          # abft.Monitor or None


def arm(routine, A, opts, checkpoint, chunked) -> Guard:
    """The guards of one call.  Checkpoints exist only on the chunked
    path: one program has no boundary to save at."""
    ck = (ckpt.plan(routine, A, opts, checkpoint=checkpoint)
          if chunked else None)
    return Guard(routine, ck, abft.monitor(routine, A, opts))


def _bitflip(routine, A, data, ci, n_chunks, k0, k1):
    g = A.grid
    return faults.maybe_bitflip_chunk(
        routine, data, chunk_idx=ci, n_chunks=n_chunks, nb=A.nb,
        p=g.p, q=g.q, mt=A.mt, k0t=k0, k1t=k1)


def _verdict(guard, data, info, k0, k1, phase):
    """What becomes of a launch that factored tiles ``[k0, k1)``: None
    — accepted (abft off, or ``info`` already reports a failure, or the
    checksums hold) — else the strike's ``"retry"`` or ``"scratch"``;
    a ``"fail"`` strike raises.  Only an armed abft pays the sync."""
    routine, _, ab = guard
    if ab is None or obs.sync_read(routine + ".info", int, info) != 0:
        return None
    v = ab.verify(data, k1, phase=phase)
    if v.ok:
        return None
    act = ab.strike(k0)
    if act == "fail":
        raise abft.SdcDetected(routine, phase=phase,
                               tile_col=v.tile_col, resid=v.resid)
    return act


def run_chunks(guard, A, step, fresh, names, kt, S, overwrite_a,
               resume=None):
    """The chunked super-step loop over block columns ``[0, kt)``, ``S``
    a chunk.  ``step(data, carried, k0, klen, donate)`` launches one
    chunk executable and returns its ``(data, *carried)``; ``carried``
    is the routine's state beside ``data`` (``info`` last), ``fresh``
    its value at chunk 0, ``names`` its checkpoint keys, ``resume`` a
    ``ckpt.load_for`` state.  Returns the last ``(data, *carried)``."""
    routine, ck, ab = guard
    data, carried, k_start = A.data, fresh, 0
    if resume is not None:
        # re-enter at the checkpointed chunk boundary with exactly the
        # uninterrupted run's state: the remaining chunks run the same
        # per-k0 executables and reproduce its result bitwise
        arrs = resume["arrays"]
        data = jax.device_put(arrs["data"], A.data.sharding)
        carried = tuple(jnp.asarray(arrs[nm]) for nm in names)
        k_start = int(resume["k_next"])
    chunk_starts = list(range(k_start, kt, S))
    if ab is not None:
        ab.init(A.data)
    ci = 0
    with abft.armed_scope(ab is not None):
        while ci < len(chunk_starts):
            k0 = chunk_starts[ci]
            if ck is not None:
                ck.check_preempt(k0)
            # later chunks always donate their (intermediate) input;
            # the first donates the caller's A only when overwrite_a
            # was requested; a buffer an async save still reads is
            # never donated — and abft never donates at all: the
            # chunk-entry buffer is the rollback state a detected SDC
            # re-runs from
            donate = ab is None and (overwrite_a or k0 > 0) and (
                ck is None or ck.donation_safe(data))
            klen = min(S, kt - k0)
            with trace.block(routine + ".chunk", phase="spmd_chunk",
                             k0=k0, klen=klen):
                new_data, *new_carried = step(data, carried, k0, klen,
                                              donate)
            new_data = _bitflip(routine, A, new_data, ci,
                                len(chunk_starts), k0, k0 + klen)
            act = _verdict(guard, new_data, new_carried[-1], k0,
                           k0 + klen, "chunk")
            if act == "retry":
                continue      # re-run from chunk entry
            if act == "scratch":
                chunk_starts = list(range(0, kt, S))
                data, carried, ci = A.data, fresh, 0
                continue
            data, carried = new_data, tuple(new_carried)
            # save only states that passed verification — a corrupted
            # chunk must never become a checkpoint
            if ck is not None and ck.due(k0, klen):
                ck.save_async(k0 + klen, data=data,
                              **dict(zip(names, carried)))
            ci += 1
    if ab is not None:
        ab.note()
    return (data, *carried)


def run_one_program(guard, A, launch, kt, overwrite_a):
    """The single-launch branch: ``launch(donate)`` runs the whole
    factorization and returns its ``(data, *carried)``, ``info`` last;
    with abft armed it is verified and launched again from the caller's
    A until it passes or the strikes are spent."""
    routine, _, ab = guard
    if ab is not None:
        ab.init(A.data)
    with abft.armed_scope(ab is not None):
        while True:
            data, *carried = launch(overwrite_a and ab is None)
            data = _bitflip(routine, A, data, 0, 1, 0, kt)
            if _verdict(guard, data, carried[-1], 0, kt,
                        "final") is None:
                break
    if ab is not None:
        ab.note()
    return (data, *carried)


def resume(routine, factor, A, opts, **kw):
    """``factor`` re-entered at the latest valid checkpoint of the
    (A, opts) job, or from scratch, the demotion recorded, when there
    is none."""
    state = ckpt.load_for(routine, A, opts)
    if state is None:
        ckpt.record_scratch_demotion(routine)
        return factor(A, opts, **kw)
    return factor(A, opts, _resume=state, **kw)


def norm_one(A, opts):
    """Host-synced ‖A‖₁ for the health path (None on failure — the
    report then simply omits the growth estimate)."""
    from ..ops.norms import norm as _mat_norm
    from ..types import Norm
    try:
        return float(_mat_norm(Norm.One, A, opts=opts))
    except Exception:
        return None


def health(routine, info, Anorm, opts, convention, condest):
    """HealthReport for a finished factorization: ``info`` under the
    routine's ``convention``; rcond via ``condest(Anorm)`` when the
    factor succeeded and ‖A‖₁ was available; abft verification outcome
    when ``Option.Abft`` was armed (the driver notes it per-thread,
    which also covers potrf's Upper-mirror path where the monitor
    lives in the inner lower call)."""
    i = int(info)
    growth = None
    if i == 0 and Anorm:
        try:
            growth = float(condest(Anorm))
        except Exception:
            growth = None
    verified, resid = (abft.take_result(routine)
                       if abft.armed(opts) else (None, None))
    return health_report(routine, i, convention=convention,
                         growth=growth, verified=verified,
                         checksum_resid=resid)
