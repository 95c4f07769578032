"""The comparison that decides ``correct``: the plain reference of a
linear solve is its residual. ``jnp`` only, nothing of the program
(copied from ``chip_smoke.backward_error_plain``)."""

from __future__ import annotations

import math

import jax.numpy as jnp

EPS = 2.0 ** -24          # f32 unit roundoff


def dense_of(A, hermitian: bool):
    """The gathered dense operand; a Hermitian one is rebuilt from the
    lower triangle its ``uplo`` contract guarantees."""
    d = A.to_dense()
    if hermitian:
        d = jnp.tril(d) + jnp.tril(d, -1).T
    return d


def backward_errors(Ad, Xd, Bd) -> dict:
    """Norm-wise backward error ‖A·X − B‖ / (‖A‖‖X‖ + ‖B‖) in the
    ∞-norm (the guarantee the configuration states, HPL's kind of
    residual) and in the Frobenius norm (a mean over all n·nrhs
    residual entries: steady from seed to seed, so it is the number
    that tells one precision tier from the next)."""
    R = jnp.matmul(Ad, Xd, precision="highest") - Bd
    out = {}
    for label, ord_ in (("inf", jnp.inf), ("fro", "fro")):
        def norm(M):
            return jnp.linalg.norm(M, ord=ord_)
        out[label] = float(norm(R) / (norm(Ad) * norm(Xd) + norm(Bd)))
    return out


def within(value: float, limit: float) -> bool:
    return math.isfinite(value) and value <= limit


def equal_shards(arr, chips: int) -> dict:
    """Where ``arr`` lives: how many devices, and whether in equal
    shards (public ``arr.sharding`` / ``addressable_shards`` only)."""
    shard_bytes = [s.data.nbytes for s in arr.addressable_shards]
    devices = len(arr.sharding.device_set)
    ok = (devices == chips
          and shard_bytes == [arr.nbytes // chips] * chips)
    return {"devices": devices, "shard_bytes": shard_bytes,
            "bytes": arr.nbytes, "ok": ok}
