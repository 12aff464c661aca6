"""Where the entry points keep JAX's persistent compilation cache.

A cold start on the chip compiles every step function and kernel again;
the persistent cache lets a later run of the same checkout skip that. The
cache key includes the directory, so the default is one fixed path,
``<repo>/.jax_cache``. Call ``enable_compile_cache()`` from a ``main()``,
never at import or from tests.
"""
from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``DEFAULT_DIR`` unless
    ``JAX_COMPILATION_CACHE_DIR`` is set — JAX reads that variable itself,
    and then nothing is set here. Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
