"""The persistent JAX compilation cache, placed from outside.

`enable_compile_cache()` is called first thing in the `main` of every
program that compiles at full size (launch/serve.py, launch/train.py,
benchmarks/run.py, chip_smoke.py) — never while a module is imported:

  * JAX_COMPILATION_CACHE_DIR set: JAX's own setting already points at
    it, and no other directory is set here.
  * not set: the cache goes to the fixed `<repo>/.jax_cache` (listed in
    .gitignore). The path is part of the cache key, so it never comes
    from a temporary name, a process id or the time.

The key includes each program's metadata (its name scopes, source
files and lines). Without it an executable loaded from the cache keeps
the metadata of whichever program first compiled to the same ops, and
a profile attributes device time by those stale scopes.
"""
from __future__ import annotations

import os

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.
    Every compile is cached, however quick: a cold start on the chip is
    the sum of many small kernel and step programs."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path
