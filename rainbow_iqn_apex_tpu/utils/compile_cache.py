"""Where XLA's persistent compilation cache lives.

Every entry point that compiles (train_agent_apex.main, test_agent.main,
PolicyServer start-up, chip_smoke.py) calls
``enable_compile_cache()`` once before its first jit.  The directory is part
of the cache key, so it must not move between runs: either the operator
places it with ``JAX_COMPILATION_CACHE_DIR`` (JAX reads that variable itself
and this module then touches nothing), or it is the fixed
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Returns the cache directory in effect."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update(  # drift-ok: jax's config, not ours
        "jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
