"""Persistent XLA compilation cache: one placement rule + hit/miss accounting.

JAX ships a content-addressed on-disk cache of compiled executables; with
it enabled, a repeat run of the same program skips XLA compilation — tens
of seconds per shape at GPT-small on a TPU. Every entry point that compiles
(`fit()`, main-serve.py, chip_smoke.py, the test harness) calls
`enable_compilation_cache`, so the recipes cache by default.

Where the cache lives — ONE rule (`enable_compilation_cache`):

  1. an explicit `--compilation_cache_dir` wins;
  2. else, if `JAX_COMPILATION_CACHE_DIR` is set, jax has already read it
     and tpukit sets NO directory in code — whoever runs the program places
     the cache from outside;
  3. else `<checkout>/.jax_cache`, resolved from this package's own path —
     never the cwd, a temp name, a pid or the time: the path is part of the
     cache's key, so a directory that moves never hits.

Counting: jax records `/jax/compilation_cache/compile_requests_use_cache`
once per cache-eligible compile and `/jax/compilation_cache/cache_hits`
once per hit, so `misses = requests - hits`; `compile_s` sums the wall
seconds jax spent compiling (or, on a hit, deserializing). One listener is
installed at most once per process; `enable_compilation_cache` returns a
stats handle that reports deltas since it was created, so nested scopes
(repeated fit calls, chip_smoke phases) each see their own
counts.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path

import jax
from jax.experimental.compilation_cache import compilation_cache as _cc

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
# wall seconds inside jax's compile-or-fetch-from-cache call: XLA's compile on
# a miss, the (much shorter) deserialization on a hit
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# Compiles cheaper than this are not worth a disk entry (jax's own default,
# 1 s, would skip most of the CPU test suite's programs).
MIN_COMPILE_SECS = 0.2

_lock = threading.Lock()
_counts = {"hits": 0, "requests": 0, "compile_s": 0.0}
_listener_installed = False


def _on_event(event: str, **kwargs) -> None:
    if event == _HIT_EVENT:
        _counts["hits"] += 1
    elif event == _REQUEST_EVENT:
        _counts["requests"] += 1


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == _COMPILE_EVENT:
        _counts["compile_s"] += duration


def _install_listener() -> None:
    global _listener_installed
    with _lock:
        if not _listener_installed:
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listener_installed = True


def default_cache_dir() -> str:
    """`<checkout>/.jax_cache`, from the package's own location."""
    return str(Path(__file__).resolve().parent.parent / ".jax_cache")


class CompileCacheStats:
    """Delta view of the cache counters since construction, plus the cache
    directory's entry count."""

    def __init__(self, cache_dir: str):
        self.cache_dir = cache_dir
        self._base = dict(_counts)
        self._entries0 = self._entry_count()

    def _entry_count(self) -> int:
        try:
            return sum(
                1 for name in os.listdir(self.cache_dir)
                if not name.startswith(".")
            )
        except OSError:
            return 0  # jax creates the directory at its first write

    def stats(self) -> dict:
        """JSONL-ready summary: requests/hits/misses and compile seconds
        observed since this handle was created, and on-disk entry growth."""
        entries = self._entry_count()
        requests = _counts["requests"] - self._base["requests"]
        hits = _counts["hits"] - self._base["hits"]
        return {
            "dir": self.cache_dir,
            "entries": entries,
            "new_entries": entries - self._entries0,
            "requests": requests,
            "hits": hits,
            "misses": requests - hits,
            "compile_s": round(_counts["compile_s"] - self._base["compile_s"], 3),
        }


def enable_compilation_cache(
    cache_dir: str = "", min_compile_time_secs: float = MIN_COMPILE_SECS
) -> CompileCacheStats:
    """Apply the placement rule of the module docstring and return a
    hit/miss stats handle. `cache_dir` is the explicit override ("" = none
    given)."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if cache_dir or not env_dir:
        cache_dir = (
            os.path.abspath(os.path.expanduser(cache_dir))
            if cache_dir
            else default_cache_dir()
        )
        if jax.config.jax_compilation_cache_dir != cache_dir:
            jax.config.update("jax_compilation_cache_dir", cache_dir)
            # jax initializes its cache object AT MOST ONCE per process, at
            # the first compile — if anything compiled before this call (or
            # an earlier call pointed elsewhere), the new dir silently never
            # takes effect. reset_cache() returns the module to its pristine
            # state so the next compile re-initializes against the dir set
            # above.
            _cc.reset_cache()
    else:
        cache_dir = env_dir  # jax read it itself; nothing to set
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_time_secs
    )
    _install_listener()
    return CompileCacheStats(cache_dir)
