"""Persistent XLA compilation cache setup.

A compile costs seconds to minutes per program; the persistent cache hits
across processes, so a warmed cache directory makes later runs (serving
warmups, artifact builds, a second run of the same command) pay ~0
compile time. The directory is part of the cache key, so it never moves:
``JAX_COMPILATION_CACHE_DIR`` where the environment sets it, else
``.jax_cache`` at the root of the checkout.
"""

from __future__ import annotations

import os
import time
from pathlib import Path

# per-directory (walk_time, stats) — a warmed cache holds thousands of
# files, and /metrics scrapes two gauges off it; full rglob per scrape
# would put a directory walk on the monitoring hot path
_stats_cache: dict = {}

_CHECKOUT_CACHE = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def _resolve(cache_dir=None) -> str:
    """Where the cache lives: the environment's directory wins outright
    (whoever runs the process placed it; the code names no other), then
    an explicit ``cache_dir`` (tests isolating an arm), then a directory
    an earlier call already configured, then the fixed checkout path."""
    import jax
    return str(os.environ.get("JAX_COMPILATION_CACHE_DIR") or cache_dir
               or jax.config.jax_compilation_cache_dir or _CHECKOUT_CACHE)


def setup_compile_cache(cache_dir=None) -> str:
    """Point JAX at a persistent compilation cache directory (idempotent;
    resolution in ``_resolve``). Returns the directory used."""
    import jax
    d = _resolve(cache_dir)
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # scrapeable cache size: live callback gauges, evaluated only when
    # /metrics is actually pulled (a directory walk per scrape)
    from deeplearning4j_tpu.monitor.metrics import get_registry
    reg = get_registry()
    reg.gauge("dl4jtpu_compile_cache_entries",
              "Files in the persistent XLA compilation cache."
              ).set_function(lambda: cache_stats(d)["entries"])
    reg.gauge("dl4jtpu_compile_cache_bytes",
              "Total bytes of the persistent XLA compilation cache."
              ).set_function(lambda: cache_stats(d)["bytes"])
    return d


def cache_stats(cache_dir=None, ttl: float = 5.0) -> dict:
    """Entry count + total bytes of the persistent cache directory (the
    serving /stats surface: lets an operator confirm a warmed process will
    really serve its first request compile-free). Safe before setup — an
    absent directory reports zero entries.

    The walk is memoized for ``ttl`` seconds per directory so back-to-back
    /metrics scrapes of a large warmed cache don't each pay a full
    ``rglob``; ``ttl=0`` forces a fresh walk."""
    d = Path(_resolve(cache_dir))
    key = str(d)
    now = time.monotonic()
    hit = _stats_cache.get(key)
    if hit is not None and ttl > 0 and now - hit[0] < ttl:
        return dict(hit[1])
    entries = bytes_ = 0
    if d.is_dir():
        for p in d.rglob("*"):
            if p.is_file():
                entries += 1
                bytes_ += p.stat().st_size
    stats = {"dir": key, "entries": entries, "bytes": bytes_}
    _stats_cache[key] = (now, stats)
    return dict(stats)
