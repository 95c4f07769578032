"""Jaxpr traversal: equation sites with their sub-jaxpr path and the
mesh axes live at each point.

``jax.make_jaxpr`` output nests programs: a driver trace is a ``jit``
eqn wrapping a ``shard_map`` eqn wrapping ``scan``/``cond`` bodies.
:func:`walk` yields every equation of every sub-jaxpr depth-first as a
:class:`Site` carrying

* ``path`` — the label chain down to the eqn's own jaxpr
  (``jit:potrf/shard_map/scan``), stable enough for tests to pin a
  seeded violation to its exact equation;
* ``axis_sizes`` — the mesh axes bound by enclosing ``shard_map``
  eqns (name → size), the ground truth the collective analysis checks
  axis names and ``ppermute`` bijections against.

Sub-jaxprs are discovered *generically* — any ``Jaxpr``/``ClosedJaxpr``
value (or tuple/list of them) in an eqn's params — so new
higher-order primitives are traversed without a registry; only
``shard_map`` (axis binding) and ``cond`` (branch labels) get
special-cased labels.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Iterator

import jax
from jax.extend import core as jcore

_Jaxpr = jcore.Jaxpr
_ClosedJaxpr = jcore.ClosedJaxpr


def raw(jaxpr) -> _Jaxpr:
    """The underlying ``Jaxpr`` of either a closed or raw jaxpr."""
    return jaxpr.jaxpr if isinstance(jaxpr, _ClosedJaxpr) else jaxpr


@dataclass(frozen=True)
class Site:
    """One equation in one (sub-)jaxpr."""
    jaxpr: object           # the raw Jaxpr owning the eqn
    eqn: object             # jax JaxprEqn
    index: int              # position within jaxpr.eqns
    path: str               # label chain of the owning jaxpr
    axis_sizes: dict        # mesh axes bound here: {name: size}

    @property
    def primitive(self) -> str:
        return self.eqn.primitive.name


def _eqn_label(eqn) -> str:
    name = eqn.params.get("name")
    p = eqn.primitive.name
    return f"{p}:{name}" if isinstance(name, str) and name else p


def sub_jaxprs(eqn) -> Iterator[tuple[str, object]]:
    """(label, jaxpr) pairs for every sub-jaxpr in an eqn's params.

    ``cond`` branches get ``br{i}`` suffixes so the two arms of a
    divergent switch are distinguishable in finding paths.
    """
    base = _eqn_label(eqn)
    if eqn.primitive.name == "cond":
        for i, br in enumerate(eqn.params.get("branches", ())):
            yield f"{base}.br{i}", br
        return
    for key, val in sorted(eqn.params.items()):
        if isinstance(val, (_Jaxpr, _ClosedJaxpr)):
            # single sub-program (pjit/shard_map "jaxpr", scan "jaxpr",
            # while "cond_jaxpr"/"body_jaxpr", custom_* "call_jaxpr")
            label = base if key == "jaxpr" else f"{base}.{key}"
            yield label, val
        elif isinstance(val, (tuple, list)):
            for i, item in enumerate(val):
                if isinstance(item, (_Jaxpr, _ClosedJaxpr)):
                    yield f"{base}.{key}[{i}]", item


def bound_axes(eqn) -> dict:
    """Mesh axes an eqn's sub-programs run under (shard_map mesh)."""
    if eqn.primitive.name != "shard_map":
        return {}
    mesh = eqn.params.get("mesh")
    shape = getattr(mesh, "shape", None)
    if shape is None:
        return {}
    try:
        return {str(k): int(v) for k, v in dict(shape).items()}
    except Exception:
        return {}


def walk(jaxpr, path: str = "", axis_sizes: dict | None = None,
         _depth: int = 0) -> Iterator[Site]:
    """Depth-first over every eqn of ``jaxpr`` and its sub-jaxprs."""
    if _depth > 32:         # defensive: jaxprs never nest this deep
        return
    axis_sizes = dict(axis_sizes or {})
    jx = raw(jaxpr)
    for i, eqn in enumerate(jx.eqns):
        yield Site(jaxpr=jx, eqn=eqn, index=i, path=path or "<top>",
                   axis_sizes=axis_sizes)
        inner_axes = {**axis_sizes, **bound_axes(eqn)}
        for label, sub in sub_jaxprs(eqn):
            sub_path = f"{path}/{label}" if path else label
            yield from walk(sub, sub_path, inner_axes, _depth + 1)


def aval_bytes(aval) -> int:
    """Byte size of a shaped aval (0 when shape/dtype are absent)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        return math.prod(int(d) for d in shape) * dtype.itemsize
    except (TypeError, ValueError):
        return 0


def make_closed(fn, *args, **kwargs) -> _ClosedJaxpr:
    """``jax.make_jaxpr`` shim (kwargs supported in this jax)."""
    return jax.make_jaxpr(fn)(*args, **kwargs)
