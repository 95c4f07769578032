"""Operations a routine needs, from its shapes alone (LAPACK Users'
Guide / LAWN 41 leading terms; copied from ``slate_tpu/obs/flops.py``
so that no later PR to the program can move the yardstick)."""

from __future__ import annotations


def potrf(n: int) -> float:
    return n ** 3 / 3.0


def getrf(n: int) -> float:
    return 2.0 * n ** 3 / 3.0


def solve(n: int, nrhs: int) -> float:
    """Two triangular solves with ``nrhs`` right-hand sides."""
    return 2.0 * float(n) ** 2 * nrhs


def posv(n: int, nrhs: int) -> float:
    return potrf(n) + solve(n, nrhs)


def gesv(n: int, nrhs: int) -> float:
    return getrf(n) + solve(n, nrhs)


ROUTINE_FLOPS = {"posv": posv, "gesv": gesv}


def routine_flops(routine: str, n: int, nrhs: int) -> float:
    """Closed-form flops of one public ``slate.<routine>`` call."""
    return ROUTINE_FLOPS[routine](n, nrhs)
