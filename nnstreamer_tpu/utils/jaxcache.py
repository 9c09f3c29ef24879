"""Where JAX's persistent compilation cache lives for this checkout.

One rule, used by ``chip_smoke.py`` and ``benchmark/run.py``: when
``JAX_COMPILATION_CACHE_DIR`` is set the operator has placed the cache
and JAX reads the variable itself, so nothing is set in code; otherwise
the cache is ``<checkout>/.jax_cache`` (git-ignored).  The path is part
of how a cache is found again, so it is fixed — never a temp name, a
pid or a time.

This is JAX's own cache of compiled XLA programs.  The product's
serialized-executable cache (``runtime/compilecache.py``,
``NNS_TPU_COMPILE_CACHE_DIR``) is separate and stays opt-in.
"""

from __future__ import annotations

import os

#: JAX's own variable for the cache directory
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Arm the persistent compilation cache and return its directory.
    Call before the first compile: a program built earlier is neither
    looked up nor stored.  Every program is kept, however quickly it
    compiled (JAX's default skips those under a second), so that a warm
    run compiles nothing and "warm" can be checked per section."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    placed = os.environ.get(CACHE_ENV, "").strip()
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
