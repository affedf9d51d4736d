"""Persistent AOT warmup: the XLA compilation cache as a serving feature.

The engine's plan cache (``core.engine``) makes the SECOND call to a
geometry free — within one process.  A restarted service still pays the XLA
compile for every geometry its warmed set replays, which is exactly the
cold-start window a failover is trying to close.  This module threads
``jax``'s persistent compilation cache (``jax_compilation_cache_dir`` — the
maxtext cold-start idiom) through the serving stack as an opt-in:

    api.enable_compilation_cache("/ckpts/xla-cache")   # once, before traffic
    api.warmup(policy, m=512, n=768, rank=16)          # compiles -> disk

    # ... process dies; a fresh one restores:
    SvdFleet.restore("/ckpts/fleet", cache_dir="/ckpts/xla-cache")
    # warmed-set replay hits the disk cache: ZERO XLA recompiles

Every compile is persisted (the min-compile-time and min-entry-size gates
are zeroed), so "no new cache entries after restore" is an observable
zero-recompile proof — pinned by the fresh-process test in
tests/test_fleet.py.  The cache key includes the XLA build and flags, so a
stale cache is never wrong, only cold.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compilation_cache", "compilation_cache_entries"]


def enable_compilation_cache(cache_dir: str | Path) -> Path:
    """Opt this process into the persistent XLA compilation cache at
    ``cache_dir`` (created if missing).  Idempotent; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins over ``cache_dir``: the
    deployment chose where compiled executables live, and the code sets no
    other directory.

    Call it BEFORE the executables you want cached are built — in serving
    terms, before ``api.warmup`` / service ``restore`` replay the warmed
    geometry set.  Threaded through ``SvdService.restore(cache_dir=)`` and
    ``SvdFleet.restore(cache_dir=)`` so failover restores compile nothing
    that any previous process on this cache already compiled.
    """
    cache_dir = Path(os.environ.get("JAX_COMPILATION_CACHE_DIR") or cache_dir)
    cache_dir.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    # persist EVERY compile: the serving executables are small and the point
    # is a bitwise-observable "no new entries" zero-recompile contract
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def compilation_cache_entries(cache_dir: str | Path) -> int:
    """Number of persisted executables in a compilation cache directory
    (0 for a missing dir).  A warm restore adds none — the observable the
    zero-recompile test asserts on."""
    cache_dir = Path(cache_dir)
    if not cache_dir.is_dir():
        return 0
    return sum(1 for name in os.listdir(cache_dir)
               if not name.startswith("."))
