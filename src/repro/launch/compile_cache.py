"""Placement of JAX's persistent compilation cache for the entry points.

A decode of the paper's decoder compiles for tens of seconds per batch
bucket, so the launcher, the chip smoke test and the benchmarks keep
compiled executables across processes.  The cache's path is part of its
key, so it must never move between runs: ``JAX_COMPILATION_CACHE_DIR``
wins where it is set (JAX reads it itself; nothing is set in code), and
otherwise the cache lives at the fixed ``<repo>/.jax_cache``.

Entry points call :func:`enable_compile_cache` explicitly; importing
``repro`` never turns the cache on, so the test suite stays cache-free.
"""

from __future__ import annotations

import os

import jax

#: The repository root (``src/repro/launch`` -> three levels up).
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
