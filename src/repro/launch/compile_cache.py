"""Where the persistent XLA compilation cache lives.

One rule for every entry point (``launch/train.py``, ``launch/serve.py``,
``chip_smoke.py``): if ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it
itself and nothing is set in code; otherwise the cache goes to a fixed
directory inside the checkout (``<repo>/.jax_cache``, git-ignored). The
path is part of the cache key, so it is never built from a temp name, a
pid or the time — a directory that moves never hits.
"""
from __future__ import annotations

import os

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
