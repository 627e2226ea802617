"""Where the persistent XLA compilation cache lives.

Every entry point (``chip_smoke.py``, ``launch/serve.py``,
``benchmarks/run.py``) calls :func:`enable_compile_cache` before its first
compile. ``JAX_COMPILATION_CACHE_DIR``, when set, places the cache: JAX reads
it itself and this module sets no other directory. Otherwise the cache goes
to the fixed path ``<checkout>/.jax_cache`` (gitignored). The path is part of
what a cached entry is found by, so it never depends on a temp name, a pid
or the time. ``JAX_ENABLE_COMPILATION_CACHE=false`` turns the cache off (the
test suite sets it).
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache")
)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
