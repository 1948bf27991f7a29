"""Persistent XLA compilation cache.

A cold start is dominated by compilation, not math: the Gauntlet's round
programs and the peer's train step are stable across runs (sticky pow2
buckets pin the shapes), so an on-disk cache lets a second process load
the executables instead of compiling them.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
module sets no directory. Otherwise the cache lives at a fixed path
inside the checkout (``.jax_cache/``): a directory that moves between
runs never hits.

The thresholds are floored to zero/-1 so even the small CPU-sized round
programs (sub-second compiles) are cached; the default jax thresholds
would skip exactly the programs ``benchmarks/compile_cache_check.py``
measures.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn jax's persistent compilation cache on and return its
    directory (``$JAX_COMPILATION_CACHE_DIR``, else ``DEFAULT_DIR``).
    Idempotent; call it before the first compile to cache that too."""
    import jax
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
    return path
