"""slatecache — AOT executable cache + shape-bucket warmup.

SLATE's kernels are AOT-compiled binaries; a solver call costs only
the solve. This package closes the XLA port's compile-tax gap
(BASELINE.md: 240–747 s fresh compiles, a ±7 % compile lottery):

* :mod:`.jitcache` — ``cached_jit``, the single jit entry point the
  driver/runtime layers use (slatelint SL009 bans raw ``jax.jit`` in
  ``slate_tpu/linalg`` + ``simplified.py``);
* :mod:`.store` — the versioned on-disk store of serialized
  executables (fingerprint invalidation, corrupt-entry quarantine);
* :mod:`.buckets` — the canonical shape-bucket table with
  pad-and-crop dispatch (``bucketed_posv``/``bucketed_gesv``);
* ``python -m slate_tpu.cache warmup|stats|check|clear`` — the
  serving-side CLI (docs/performance.md "Warmup and the executable
  cache").

Arming: set ``SLATE_TPU_CACHE_DIR=/path`` (or call
:func:`set_cache_dir`); ``SLATE_TPU_CACHE=0`` disables the layer.
Unarmed, every ``cached_jit`` is a plain ``jax.jit`` passthrough.
"""

from __future__ import annotations

from .buckets import (bucket_for, bucket_table, bucketed_gesv,
                      bucketed_posv, default_nb, pad_embed, pad_rhs)
from .jitcache import CachedJit, cached_jit, clear_in_process
from .store import (ENV_CACHE, ENV_CACHE_DIR, cache_dir, clear,
                    enabled, fingerprint, fp_digest, reset_cache_dir,
                    set_cache_dir, stats)
from .xla_cache import place_jax_compile_cache

__all__ = [
    "CachedJit", "cached_jit", "clear_in_process",
    "bucket_for", "bucket_table", "bucketed_gesv", "bucketed_posv",
    "default_nb", "pad_embed", "pad_rhs",
    "ENV_CACHE", "ENV_CACHE_DIR", "cache_dir", "clear", "enabled",
    "fingerprint", "fp_digest", "reset_cache_dir", "set_cache_dir",
    "stats",
]
