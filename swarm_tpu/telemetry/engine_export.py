"""Match-engine stats → metrics registry bridge.

``MatchEngine`` keeps its hot-path counters in a plain
:class:`~swarm_tpu.ops.engine.EngineStats` dataclass (mutating a real
metric per batch would tax the walk). This module registers ONE
scrape-time collector that aggregates the stats of every live engine in
the process into ``swarm_engine_*`` gauges — device seconds, host
confirm work, memo hit rate, batch fill — so the kernel layer shows up
on ``/metrics`` without touching engine hot paths.

Engines are held through a ``WeakSet``: telemetry must never extend an
engine's lifetime (tests construct hundreds).
"""

from __future__ import annotations

import dataclasses
import threading
import weakref

from swarm_tpu.telemetry.metrics import REGISTRY

_lock = threading.Lock()
_engines: "weakref.WeakSet" = weakref.WeakSet()  # guarded-by: _lock (reads)
_collector_added = False  # guarded-by: _lock (reads)

_G = {}


def _gauges() -> dict:
    if not _G:
        g = REGISTRY.gauge
        _G.update(
            engines=g("swarm_engine_instances", "Live MatchEngine instances"),
            rows=g("swarm_engine_rows", "Rows matched by all live engines"),
            batches=g("swarm_engine_batches", "Device batches dispatched"),
            device_seconds=g(
                "swarm_engine_device_seconds",
                "Seconds spent in device kernel dispatch",
            ),
            device_compile_seconds=g(
                "swarm_engine_device_compile_seconds",
                "Seconds spent compiling device match executables "
                "(new batch shapes)",
            ),
            host_confirm_seconds=g(
                "swarm_engine_host_confirm_seconds",
                "Seconds spent in the sparse host confirmation walk",
            ),
            host_confirm_pairs=g(
                "swarm_engine_host_confirm_pairs",
                "(row, matcher) pairs re-checked on the host",
            ),
            host_always_pairs=g(
                "swarm_engine_host_always_pairs",
                "(row, template) hits from the host-only template tail",
            ),
            overflow_rows=g(
                "swarm_engine_overflow_rows",
                "Rows re-run end to end on the host (overflow/truncation)",
            ),
            memo_rows=g(
                "swarm_engine_memo_rows",
                "Rows served by the cross-batch verdict memo",
            ),
            memo_hit_rate=g(
                "swarm_engine_memo_hit_rate",
                "Fraction of rows served by the verdict memo",
            ),
            batch_fill=g(
                "swarm_engine_batch_fill",
                "Mean fraction of batch capacity actually filled",
            ),
            degraded=g(
                "swarm_engine_degraded",
                "Engines currently running with an open device breaker "
                "(CPU-oracle fallback; results stay exact)",
            ),
            degraded_batches=g(
                "swarm_engine_degraded_batches",
                "Batches served by the CPU-oracle fallback after a "
                "device-path failure",
            ),
            device_faults=g(
                "swarm_engine_device_faults",
                "Device-path failures observed (compile/OOM/dispatch)",
            ),
            overlapped_batches=g(
                "swarm_engine_overlapped_batches",
                "Direct-path chunks walked while the next chunk's "
                "kernels were in flight",
            ),
        )
    return _G


def register_engine(engine) -> None:
    """Track a MatchEngine for the aggregate ``swarm_engine_*`` gauges."""
    global _collector_added
    with _lock:
        _engines.add(engine)
        if not _collector_added:
            REGISTRY.add_collector(_collect)
            _collector_added = True


def _collect() -> None:
    from swarm_tpu.telemetry import walk_export as we

    g = _gauges()
    with _lock:
        engines = list(_engines)
    rows = batches = confirm_pairs = always_pairs = overflow = memo = 0
    degraded = degraded_batches = device_faults = overlapped = 0
    dev_s = confirm_s = compile_s = 0.0
    capacity = 0
    walk_pairs = walk_rounds = walk_pool = 0
    walk_pre_s = 0.0
    phase_s = {"unc": 0.0, "ext": 0.0, "insert": 0.0, "fixup": 0.0}
    for eng in engines:
        s = eng.stats
        rows += s.rows
        batches += s.batches
        confirm_pairs += s.host_confirm_pairs
        always_pairs += s.host_always_pairs
        overflow += s.overflow_rows
        memo += s.memo_slots
        dev_s += s.device_seconds
        compile_s += getattr(s, "device_compile_seconds", 0.0)
        confirm_s += s.host_confirm_seconds
        capacity += s.batches * getattr(eng, "batch_rows", 0)
        degraded_batches += getattr(s, "degraded_batches", 0)
        device_faults += getattr(s, "device_faults", 0)
        overlapped += getattr(s, "overlapped_batches", 0)
        walk_pairs += getattr(s, "walk_batched_pairs", 0)
        walk_rounds += getattr(s, "walk_batch_rounds", 0)
        walk_pre_s += getattr(s, "walk_precompute_seconds", 0.0)
        walk_pool = max(walk_pool, getattr(s, "walk_pool_threads", 0))
        phase_s["unc"] += getattr(s, "unc_seconds", 0.0)
        phase_s["ext"] += getattr(s, "ext_seconds", 0.0)
        phase_s["insert"] += getattr(s, "insert_seconds", 0.0)
        phase_s["fixup"] += getattr(s, "fixup_seconds", 0.0)
        board = getattr(eng, "_device_breakers", None)
        if board is not None and board.any_open():
            degraded += 1
    we.WALK_POOL_THREADS.set(walk_pool)
    we.WALK_BATCHED_PAIRS.set(walk_pairs)
    we.WALK_BATCH_ROUNDS.set(walk_rounds)
    we.WALK_PRECOMPUTE_SECONDS.set(walk_pre_s)
    for ph, v in phase_s.items():
        we.WALK_PHASE_SECONDS.labels(phase=ph).set(v)
    g["engines"].set(len(engines))
    g["rows"].set(rows)
    g["batches"].set(batches)
    g["device_seconds"].set(dev_s)
    g["device_compile_seconds"].set(compile_s)
    g["host_confirm_seconds"].set(confirm_s)
    g["host_confirm_pairs"].set(confirm_pairs)
    g["host_always_pairs"].set(always_pairs)
    g["overflow_rows"].set(overflow)
    g["memo_rows"].set(memo)
    g["memo_hit_rate"].set(memo / rows if rows else 0.0)
    g["batch_fill"].set(rows / capacity if capacity else 0.0)
    g["degraded"].set(degraded)
    g["degraded_batches"].set(degraded_batches)
    g["device_faults"].set(device_faults)
    g["overlapped_batches"].set(overlapped)


def engine_stats_snapshot(engine) -> dict:
    """One engine's EngineStats as a JSON-able dict (bench attachments)."""
    return dataclasses.asdict(engine.stats)
