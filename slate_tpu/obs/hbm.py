"""slatescope device-memory telemetry: HBM live/peak gauges.

``jax`` devices expose allocator statistics via
``Device.memory_stats()`` (``bytes_in_use``, ``peak_bytes_in_use``,
``bytes_limit`` on TPU/GPU; ``None`` on CPU).  This module samples
them around interesting regions:

* :func:`sample` — one-shot gauges
  (``hbm.bytes_in_use{where=…}`` / ``hbm.peak_bytes{where=…}``);
* :func:`watch` — a context manager bracketing a region: gauges the
  live bytes at entry and exit plus the allocator peak, and when the
  region exits holding more live bytes than it entered with, counts
  the growth as ``hbm.leak_bytes{section=…}`` and drops an instant —
  a leak becomes a number instead of an OOM three regions later.

Degradation contract: a platform without ``memory_stats`` (CPU) makes
every entry point a cheap no-op returning ``None`` — telemetry must
never take down a solve, and tests inject a fake stats source via
:func:`set_stats_fn`.
"""

from __future__ import annotations

from . import metrics as _metrics
from . import tracing as _tracing

# live-bytes growth below this is allocator noise, not a leak
LEAK_THRESHOLD_BYTES = 16 * 1024 * 1024

_stats_fn = None       # test override (set_stats_fn)


def set_stats_fn(fn) -> None:
    """Install a ``() -> dict | None`` stats source (tests; ``None``
    restores the real device)."""
    global _stats_fn
    _stats_fn = fn


def device_memory_stats(device=None) -> dict | None:
    """Raw allocator stats for ``device`` (default: first local
    device), or ``None`` where the platform has none."""
    if _stats_fn is not None and device is None:
        try:
            return _stats_fn()
        except Exception:  # noqa: BLE001 — telemetry never raises
            return None
    try:
        import jax
        dev = device if device is not None else jax.local_devices()[0]
        return dev.memory_stats()
    except Exception:  # noqa: BLE001
        return None


def sample(where: str, device=None) -> dict | None:
    """Gauge the current live/peak bytes under a ``where=`` label.
    Returns ``{"bytes_in_use", "peak_bytes_in_use", ...}`` or None."""
    stats = device_memory_stats(device)
    if not stats:
        return None
    live = stats.get("bytes_in_use")
    peak = stats.get("peak_bytes_in_use")
    if live is not None:
        _metrics.set_gauge("hbm.bytes_in_use", float(live), where=where)
    if peak is not None:
        _metrics.set_gauge("hbm.peak_bytes", float(peak), where=where)
    limit = stats.get("bytes_limit")
    if limit is not None:
        _metrics.set_gauge("hbm.bytes_limit", float(limit), where=where)
    return stats


class watch:
    """Bracket a region with live/peak sampling and leak detection.

    After exit, ``self.stats`` is ``{"pre_live_bytes",
    "post_live_bytes", "peak_bytes", "delta_bytes"}`` (or ``None`` on
    a statless platform).
    """

    __slots__ = ("name", "device", "stats", "_pre")

    def __init__(self, name: str, device=None):
        self.name = name
        self.device = device
        self.stats: dict | None = None
        self._pre: dict | None = None

    def __enter__(self):
        self._pre = device_memory_stats(self.device)
        if self._pre and self._pre.get("bytes_in_use") is not None:
            _metrics.set_gauge("hbm.bytes_in_use",
                               float(self._pre["bytes_in_use"]),
                               section=self.name, edge="pre")
        return self

    def __exit__(self, *exc):
        post = device_memory_stats(self.device)
        if not (self._pre and post):
            return False
        pre_live = self._pre.get("bytes_in_use")
        post_live = post.get("bytes_in_use")
        peak = post.get("peak_bytes_in_use")
        if pre_live is None or post_live is None:
            return False
        _metrics.set_gauge("hbm.bytes_in_use", float(post_live),
                           section=self.name, edge="post")
        if peak is not None:
            _metrics.set_gauge("hbm.peak_bytes", float(peak),
                               section=self.name)
        delta = int(post_live) - int(pre_live)
        self.stats = {
            "pre_live_bytes": int(pre_live),
            "post_live_bytes": int(post_live),
            "delta_bytes": delta,
        }
        if peak is not None:
            self.stats["peak_bytes"] = int(peak)
        if delta > LEAK_THRESHOLD_BYTES:
            _metrics.inc("hbm.leak_bytes", float(delta),
                         section=self.name)
            _tracing.instant("hbm.leak_suspect", section=self.name,
                             delta_bytes=delta)
        return False
