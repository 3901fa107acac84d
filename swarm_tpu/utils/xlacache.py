"""Persistent XLA compilation cache.

The full-corpus match kernel takes tens of seconds to compile (device
word tables + q-gram prefilter + verify + regex lanes in one jit). The
reference worker had no analogous cost — its engines were prebuilt
binaries — so worker startup parity argues for caching: with JAX's
persistent compilation cache enabled, every worker restart (and every
fleet scale-up clone, server/fleet.py) after the first reuses the
serialized executable instead of recompiling.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path
from typing import Optional

#: where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is unset: one
#: fixed, gitignored directory inside the checkout. The path is part of
#: the cache key, so a directory that moved would never hit.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".xla_cache"
_active_dir: Optional[str] = None
_metrics_installed = False

#: jax.monitoring event names the persistent cache emits (jax/_src/
#: compiler.py + compilation_cache.py) — one listener maps them onto
#: the swarm counters.
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def _cache_counters():
    from swarm_tpu.telemetry import REGISTRY

    hit = REGISTRY.counter(
        "swarm_xla_cache_hit_total",
        "Persistent XLA compilation cache hits (executable deserialized "
        "instead of recompiled)",
    )
    miss = REGISTRY.counter(
        "swarm_xla_cache_miss_total",
        "Persistent XLA compilation cache misses (fresh compile written "
        "back to the cache)",
    )
    return hit, miss


def _cache_event_listener(event: str, **_kw) -> None:
    """jax.monitoring → telemetry bridge (module-level so tests can
    drive it with synthetic events)."""
    hit, miss = _cache_counters()
    if event == _HIT_EVENT:
        hit.inc()
    elif event == _MISS_EVENT:
        miss.inc()


def install_cache_metrics() -> bool:
    """Idempotently register the swarm_xla_cache_{hit,miss}_total
    counters on JAX's monitoring stream. Separate from
    :func:`enable_compilation_cache` so fleet code can re-assert the
    wiring; returns whether the listener is installed. Without these,
    a fleet restart can't tell whether the persistent cache actually
    served (the whole point of shipping it)."""
    global _metrics_installed
    if _metrics_installed:
        return True
    try:
        from jax import monitoring
    except ImportError:  # pragma: no cover - jax always present here
        return False
    _cache_counters()  # register the families even before any event
    monitoring.register_event_listener(_cache_event_listener)
    _metrics_installed = True
    return True


def enable_compilation_cache(cache_dir: Optional[str] = None) -> str:
    """Idempotently turn on JAX's persistent compilation cache. Returns
    the dir in effect ('' when disabled); once bound, later calls
    return the original binding.

    The rule: ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache —
    JAX reads it itself and this sets no other directory (empty
    disables). Otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`.
    ``cache_dir`` is for tests that keep their own directory. A dir
    that cannot be created degrades to no-cache rather than failing
    startup (the worker must run from a read-only checkout)."""
    global _active_dir
    if _active_dir is not None:
        return _active_dir
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if cache_dir is None and env is not None:
        raw = env  # JAX already bound it from the environment
    else:
        raw = str(DEFAULT_CACHE_DIR) if cache_dir is None else cache_dir
    if not raw:
        return ""
    path = Path(raw).expanduser()
    try:
        path.mkdir(parents=True, exist_ok=True)
    except OSError as e:
        # stderr: bench.py's stdout is a JSON-only metric stream
        print(f"xla cache disabled ({path}: {e})", file=sys.stderr)
        return ""
    if raw != env:
        raw = str(path)
        jax.config.update("jax_compilation_cache_dir", raw)
    # cache everything that took real compile time; tiny kernels
    # aren't worth the disk round-trip
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    install_cache_metrics()  # hit/miss counters ride every enable
    _active_dir = raw
    return _active_dir
