"""slatescope roofline attribution: what *kind* of slow is this span?

Given a span's labels (routine + dims + platform/dtype/precision) and
its measured seconds, classify it against the machine's roofline the
way "Large Scale Distributed Linear Algebra With TPUs" attributes
every kernel before optimizing it:

* **arithmetic intensity** ``AI = flops / bytes`` (flops from the
  closed-form table or the captured XLA cost, bytes from the XLA
  ``bytes accessed`` when captured, else the minimum-traffic closed
  form);
* **classification** — ``compute`` when the compute-time term of the
  roofline dominates (AI above the ridge point), ``memory`` when the
  bandwidth term dominates, ``latency`` when the roofline expects the
  work to take well under the measured wall (dispatch/compile
  overheads own the span, not the device), ``host`` when the span
  carries no attributable routine at all;
* **expected vs measured** — ``expected_s = max(flops/peak,
  bytes/bw)`` and ``roofline_frac = expected_s / measured_s`` (1.0 =
  running at the roofline; the geqrf 8.9–11.0 TF/s compile-to-compile
  band shows up as this number moving while AI stays put).

The machine model is deliberately coarse — order-of-magnitude peaks
are enough to separate a 240-flops/byte ridge from a 0.5-AI solve —
and overridable per fleet: ``SLATE_TPU_PEAK_GFLOPS`` (via
:func:`flops.peak_gflops`) and ``SLATE_TPU_MEM_BW_GBS`` here.
"""

from __future__ import annotations

import os

from . import costmodel as _costmodel
from . import flops as _flops

# Nominal memory bandwidth per platform, GB/s.  The TPU number is the
# v5e HBM figure (819 GB/s) matching the bf16 peak flops.py pins; the
# cpu/gpu rows are order-of-magnitude attribution defaults, not
# measurements — override with SLATE_TPU_MEM_BW_GBS for a real SKU.
MEM_BW_GBS = {
    "tpu": 819.0,
    "cpu": 20.0,
    "gpu": 900.0,
}

# Compute-peak fallbacks for (platform, dtype) pairs flops.PEAK_GFLOPS
# doesn't carry (it only lists measured entries and must keep
# returning None for them — %peak never guesses; classification may).
# TPU f32/c64 default to the bf16_6x tier (6 MXU passes) — the
# repo-wide f32 accuracy contract — unless a precision= label picks a
# different rung via flops.peak_gflops.
DEFAULT_PEAK_GFLOPS = {
    ("tpu", "float32"): 197e3 / 6,
    ("tpu", "complex64"): 197e3 / 6,
    ("cpu", "float32"): 50.0,
    ("cpu", "float64"): 25.0,
    ("cpu", "complex64"): 50.0,
    ("cpu", "complex128"): 25.0,
    ("cpu", "bfloat16"): 50.0,
}

# Nominal per-link interconnect bandwidths, GB/s per direction.  The
# tpu ICI row is a v5e 2D-torus link figure; DCN is a 50 Gb/s NIC
# share.  The cpu rows stand in for a host "mesh" (shared memory /
# loopback) — attribution defaults, not measurements.  Override with
# SLATE_TPU_ICI_GBS / SLATE_TPU_DCN_GBS for a real fleet (the same
# env-wins contract as SLATE_TPU_MEM_BW_GBS above).
ICI_GBS = {
    "tpu": 90.0,
    "cpu": 10.0,
    "gpu": 50.0,
}
DCN_GBS = {
    "tpu": 6.25,
    "cpu": 1.25,
    "gpu": 6.25,
}

# a span is latency-bound when the roofline expects under this
# fraction of the measured wall — the device work cannot explain the
# time; dispatch/pipeline bubbles own it
LATENCY_FRACTION = 0.1

_DIM_KEYS = ("m", "n", "k", "nb", "b", "nrhs", "side")


def mem_bw_gbs(platform) -> float | None:
    """Nominal bandwidth for a platform; SLATE_TPU_MEM_BW_GBS wins."""
    env = os.environ.get("SLATE_TPU_MEM_BW_GBS", "")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    if platform is None:
        return None
    return MEM_BW_GBS.get(str(platform))


def link_bw_gbs(link: str, platform=None) -> float | None:
    """Nominal bandwidth of an interconnect link class ("ici" or
    "dcn"), GB/s.  ``SLATE_TPU_ICI_GBS`` / ``SLATE_TPU_DCN_GBS`` win;
    with no platform given the live jax backend is asked."""
    link = str(link).lower()
    env = os.environ.get(f"SLATE_TPU_{link.upper()}_GBS", "")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    if platform is None:
        try:
            import jax
            platform = jax.default_backend()
        except Exception:  # noqa: BLE001 — attribution never raises
            return None
    table = DCN_GBS if link == "dcn" else ICI_GBS
    return table.get(str(platform))


def compute_peak_gflops(platform, dtype, precision=None) -> float | None:
    """Attribution peak: the measured table first (env override
    included), then the classification defaults."""
    pk = _flops.peak_gflops(platform, dtype, precision)
    if pk is not None:
        return pk
    if platform is None or dtype is None:
        return None
    return DEFAULT_PEAK_GFLOPS.get((str(platform), str(dtype)))


def ridge_ai(platform, dtype, precision=None) -> float | None:
    """The roofline ridge point in flops/byte: AI above it is
    compute-bound territory."""
    pk = compute_peak_gflops(platform, dtype, precision)
    bw = mem_bw_gbs(platform)
    if not pk or not bw:
        return None
    return pk / bw


def attribute(labels: dict, seconds: float | None = None, *,
              span: str | None = None, cost: dict | None = None) -> dict:
    """Roofline attribution for one span.

    ``labels`` are ordinary span labels (routine, dims, platform,
    dtype, precision); ``seconds`` is the measured mean time (None =
    classification only, no expected-vs-measured); ``cost`` is a
    captured XLA cost dict (defaults to the costmodel registry entry
    for the routine).  Always returns a dict with ``flops``,
    ``bytes``, ``ai``, ``bound`` keys — an unattributable span gets
    ``bound="host"`` and null numerics rather than a blank row.
    """
    labels = labels or {}
    routine = labels.get("routine")
    out: dict = {"routine": routine, "flops": None, "bytes": None,
                 "ai": None, "bound": "host"}
    if span is not None:
        out["span"] = span
    if routine is None:
        return out
    if cost is None:
        cost = _costmodel.lookup_prefix(str(routine))
    if cost and cost.get("hlo"):
        # the optimized-HLO fingerprint slatecache stamped at compile
        # time — carries the "which compile was this" attribution
        # (the 32k compile lottery) into every roofline row
        out["hlo"] = cost["hlo"]
    dims = {k: labels[k] for k in _DIM_KEYS if k in labels}
    dtype = labels.get("dtype")

    fl = None
    if "flops" in labels:
        try:
            fl = float(labels["flops"])
        except (TypeError, ValueError):
            fl = None
    if fl is None:
        fl = _flops.flop_count(str(routine), **dims)
    if fl is None and cost:
        fl = cost.get("flops")
        if fl is not None:
            out["flops_source"] = "xla"

    nb = None
    if cost and cost.get("bytes_accessed") is not None:
        nb = float(cost["bytes_accessed"])
        out["bytes_source"] = "xla"
    if nb is None:
        nb = _costmodel.min_bytes(str(routine), dtype=dtype, **dims)
        if nb is not None:
            out["bytes_source"] = "model"

    out["flops"] = fl
    out["bytes"] = nb
    if not fl or not nb:
        return out
    out["ai"] = fl / nb

    platform = labels.get("platform")
    pk = compute_peak_gflops(platform, dtype, labels.get("precision"))
    bw = mem_bw_gbs(platform)
    if not pk or not bw:
        out["bound"] = "unknown"          # numerics present, no machine model
        return out
    t_compute = fl / (pk * 1e9)
    t_memory = nb / (bw * 1e9)
    expected = max(t_compute, t_memory)
    out["ridge_ai"] = pk / bw
    out["expected_s"] = expected
    if seconds and seconds > 0:
        out["measured_s"] = seconds
        out["roofline_frac"] = min(expected / seconds, 1.0)
        if expected < LATENCY_FRACTION * seconds:
            out["bound"] = "latency"
            return out
    out["bound"] = "compute" if t_compute >= t_memory else "memory"
    return out
