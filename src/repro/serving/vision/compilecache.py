"""Persistent XLA compilation-cache wiring + process-wide compile counters.

Every serving process pays full jit compilation for the whole reachable
(model, bucket, device-group) layout set before it is servable — the main
obstacle to fast rolling restarts.  ``jax.experimental.compilation_cache``
persists compiled executables to disk keyed by the HLO + backend
fingerprint, so a restarted process that replays the same warmup set reads
executables back instead of recompiling.  This module is the one place
that turns the cache on and counts what it does:

* :func:`enable_compilation_cache` resolves the cache directory
  (``JAX_COMPILATION_CACHE_DIR`` where it is set — it always wins — else
  the caller's directory; entry points pass :data:`DEFAULT_CACHE_DIR`, a
  fixed directory inside the checkout, never a temporary name: the path
  is part of the cache key) and applies the jax config knobs serving
  needs — crucially the
  min-compile-time / min-entry-size floors are dropped to zero, because
  the smoke models' per-entry compiles are far below jax's default 1 s
  persistence threshold and would silently never be written.
* :func:`persistent_cache_counters` reads the process-wide hit/miss
  counters.  jax reports cache activity only through ``jax.monitoring``
  events (one ``cache_hits``/``cache_misses`` event per XLA compile
  request), so a listener is registered exactly once per process and
  accumulates into a thread-safe table.  A **miss is an actual XLA
  compile**; a hit is an executable deserialized from disk.  The
  cold/warm-restart CI gate and the ``serve_restart`` bench are built on
  the delta of these counters.

Counters are monotonic for the life of the process (jax gives no way to
unregister per-scope), so callers that want per-phase numbers snapshot
before/after and diff (:func:`counters_delta`).
"""
from __future__ import annotations

import os
import threading
from typing import Dict, Optional

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
# where entry points keep the cache when JAX_COMPILATION_CACHE_DIR is
# unset: <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))), ".jax_cache")

# jax.monitoring event names, as emitted by jax/_src/compiler.py and
# jax/_src/compilation_cache.py of the pinned jax (0.9.0).  They are
# internals: re-check them when the pin moves.
_EVENT_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_EVENT_HITS = "/jax/compilation_cache/cache_hits"
_EVENT_MISSES = "/jax/compilation_cache/cache_misses"
_EVENT_SAVED_SEC = "/jax/compilation_cache/compile_time_saved_sec"
_EVENT_RETRIEVAL_SEC = "/jax/compilation_cache/cache_retrieval_time_sec"

_lock = threading.Lock()
_counters: Dict[str, float] = {
    "requests": 0, "hits": 0, "misses": 0,
    "time_saved_s": 0.0, "retrieval_s": 0.0,
}
_installed = False


def _on_event(event: str, **kwargs) -> None:
    with _lock:
        if event == _EVENT_REQUESTS:
            _counters["requests"] += 1
        elif event == _EVENT_HITS:
            _counters["hits"] += 1
        elif event == _EVENT_MISSES:
            _counters["misses"] += 1


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    with _lock:
        if event == _EVENT_SAVED_SEC:
            _counters["time_saved_s"] += float(duration_secs)
        elif event == _EVENT_RETRIEVAL_SEC:
            _counters["retrieval_s"] += float(duration_secs)


def install_counters() -> None:
    """Register the (idempotent, process-wide) jax.monitoring listeners.

    Safe to call any number of times from any thread; the listeners are
    registered once.  Importing jax here is deliberate — callers that
    never enable the cache never pay for it.
    """
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    from jax._src import monitoring
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


def persistent_cache_counters() -> Dict[str, float]:
    """Snapshot of the process-wide persistent-cache counters.

    ``misses`` counts actual XLA compiles routed through the cache;
    ``hits`` counts executables deserialized from disk instead of
    compiled.  All zeros until :func:`enable_compilation_cache` ran and a
    jit executed (jax emits these events only when a cache dir is set).
    """
    with _lock:
        return dict(_counters)


def counters_delta(before: Dict[str, float],
                   after: Optional[Dict[str, float]] = None
                   ) -> Dict[str, float]:
    """``after - before`` per counter (``after`` defaults to now)."""
    if after is None:
        after = persistent_cache_counters()
    return {k: after.get(k, 0) - before.get(k, 0) for k in after}


def resolve_cache_dir(cache_dir: Optional[str] = None) -> Optional[str]:
    """``JAX_COMPILATION_CACHE_DIR`` where it is set, else ``cache_dir``,
    else None (cache off).  The variable wins so that whoever runs the
    program places the cache, and no code sets another directory."""
    resolved = os.environ.get(ENV_CACHE_DIR) or cache_dir or None
    if not resolved:
        return None
    return os.path.abspath(os.path.expanduser(str(resolved)))


def enable_compilation_cache(cache_dir: Optional[str] = None
                             ) -> Optional[str]:
    """Turn on jax's persistent compilation cache; returns the resolved
    directory (created if missing, see :func:`resolve_cache_dir`), or None
    when no directory was given and ``JAX_COMPILATION_CACHE_DIR`` is unset
    (cache stays off).

    Must run before the entries it should capture are compiled — in
    practice the registry calls it at construction, well before any jit.
    Idempotent: re-enabling with the same directory is a no-op; with a
    different one, the later call wins (jax re-reads the config per
    compile).
    """
    resolved = resolve_cache_dir(cache_dir)
    if not resolved:
        return None
    os.makedirs(resolved, exist_ok=True)
    import jax
    jax.config.update("jax_compilation_cache_dir", resolved)
    # serving entries are many small executables: jax's defaults
    # (>= 1 s compile time, entry-size floor) would skip exactly the
    # (model, bucket, group) kernels warm restarts need persisted
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    install_counters()
    return resolved
