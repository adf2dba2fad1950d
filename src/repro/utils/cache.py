"""Persistent XLA compilation cache wiring.

One call makes every jit in the process write/read compiled executables
from a directory on disk, so a fresh process (or a ``jax.clear_caches()``
restart) pays deserialization milliseconds instead of the multi-second
XLA compile for every program it has seen before. The thresholds are
dropped to zero so SMALL programs cache too — this repo's compile tax is
many medium programs, not one giant one.

Where the cache lives is decided outside the program:
``$JAX_COMPILATION_CACHE_DIR`` when it is set, otherwise the fixed
``<checkout>/.jax_cache`` (git-ignored), found from this package's own
location. The path is part of what a cache entry is found by, so it
never moves with the working directory or the home directory.

Called at start by ``chip_smoke.py``, ``launch.train``, ``launch.serve``
and the benchmark harness (``benchmarks.common``);
``scripts/check_warm_cache.py`` asserts the warm-start drop.
"""
from __future__ import annotations

import os
import pathlib

import jax
from jax.experimental.compilation_cache import compilation_cache as cc

_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``."""
    return os.environ.get(_ENV_DIR) or CHECKOUT_CACHE_DIR


def enable_compilation_cache() -> str:
    """Point jax's persistent compilation cache at ``cache_dir()`` and
    drop the size/time thresholds so every program is cached. Returns
    the directory used. Safe to call more than once."""
    path = cache_dir()
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    # jax latches a cache-used? decision at the FIRST compile of the
    # process; if anything compiled before this call, the latch says
    # "disabled" forever and the dir above is silently ignored.
    # reset_cache() clears the latch (and the in-memory handle) so
    # enabling mid-process actually takes effect.
    cc.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
