"""Where JAX's own persistent compilation cache lives — the one rule
for the whole repo.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
nothing is set in code. Where it is not, the cache goes to
``<checkout>/.jax_cache``: a fixed path inside the checkout
(git-ignored), never a temporary name, pid or time — a directory that
moves is never found again.
Entry points call this once before their first compile
(``benchmarks/run.py``, the serve/cache/tune CLIs).
"""

from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ENV = "JAX_COMPILATION_CACHE_DIR"
OPTION = "jax_compilation_cache_dir"     # named here and nowhere else


def place_jax_compile_cache() -> str:
    """Apply the rule; returns the directory jax will use."""
    env = os.environ.get(ENV)
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update(OPTION, path)
    return path
