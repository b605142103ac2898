"""JAX persistent compilation cache at one fixed place.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run`` and the examples) calls :func:`enable` before its
first compile, so a second run in the same checkout loads its programs
from disk instead of compiling them again.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
there and :func:`enable` leaves the directory alone. Otherwise the cache
lives in ``.jax_cache/`` at the root of the checkout (gitignored): a
fixed path, because the path is part of what a cache entry is found by.
"""
from __future__ import annotations

import contextlib
import os

import jax

DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable() -> str:
    """Turn the persistent cache on for every program; returns its
    directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program: the kernels compile in well under the default
    # one-second floor, and the counting programs are what a rerun saves
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@contextlib.contextmanager
def disabled():
    """Compile with the persistent cache off inside the block (compiles
    for a described, unattached chip can be written but never read)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
