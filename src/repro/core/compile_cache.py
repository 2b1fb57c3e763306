"""Persistent jit compile cache wiring for repeated k-searches.

Shape bucketing (``repro.factorization.batching.bucket_batch``) caps the
number of distinct compiled ``(batch, k_pad)`` shapes *within* one search;
this module makes those few compilations survive *across* processes: with
``jax_compilation_cache_dir`` set, XLA executables are written to disk and
the next search over the same data shape deserializes instead of
recompiling — the dominant cold-start cost of every executor on a chip.

``resolve_compile_cache`` is the one place the directory is chosen:

  * ``JAX_COMPILATION_CACHE_DIR`` set — the cache lives in that
    directory, and no other directory is set in code.
  * unset — ``<checkout>/.jax_cache``, a fixed path found from this file
    (the path is part of what makes a later process find the entries, so
    it never depends on a temporary name, a process id or the time).

Either way JAX's persistence thresholds (tuned to skip compiles under a
second) are lowered to zero, so the second-long compiles of the wavefront
planes are cached too.

This is deliberately config-only — no jax device state is touched at
import time, so ``repro.core`` stays importable before XLA_FLAGS tricks
like ``--xla_force_host_platform_device_count``.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_persistent_cache(
    cache_dir: str,
    min_compile_time_secs: float = 0.0,
    min_entry_size_bytes: int = -1,
) -> bool:
    """Point jax's persistent compilation cache at ``cache_dir``.

    Call before the first jit dispatch; entries compiled earlier are not
    retro-cached. Returns True.
    """
    import jax

    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # persist everything: the default thresholds skip sub-second compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_compile_time_secs)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", min_entry_size_bytes)
    return True


def resolve_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in use.

    Call first, before anything compiles (see the module docstring for
    where the directory comes from).
    """
    # the variable's own directory is re-applied rather than trusted to
    # jax's import-time read, which misses a variable set after import
    cache_dir = os.environ.get(CACHE_ENV) or str(DEFAULT_CACHE_DIR)
    enable_persistent_cache(cache_dir)
    return cache_dir


def cache_entry_count(cache_dir: str) -> int:
    """Number of serialized executables currently in ``cache_dir``."""
    try:
        return sum(1 for e in os.scandir(cache_dir) if e.is_file())
    except FileNotFoundError:
        return 0


__all__ = [
    "CACHE_ENV",
    "DEFAULT_CACHE_DIR",
    "cache_entry_count",
    "enable_persistent_cache",
    "resolve_compile_cache",
]
