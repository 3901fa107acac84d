"""What a driver whose timed path runs a ``MatchEngine`` (ops/engine.py)
hands the runner: the engine's counters, and the encoded shapes its
matcher was dispatched with. The census driver and a template-scan
driver use it; the runner itself knows no engine."""

from __future__ import annotations


class EngineProbe:
    """Resolves the engine's backend, as its first call would, and notes
    each dispatch's encoded stream shapes and phase-B width."""

    def __init__(self, engine):
        engine._resolve_backend()
        self.engine = engine
        self.shapes: set = set()
        matcher = engine.sharded if engine.sharded is not None else engine.device
        fn = matcher.dispatch

        def dispatch(streams, lengths, status, *a, **kw):
            out = fn(streams, lengths, status, *a, **kw)
            key = tuple(sorted((k, tuple(v.shape)) for k, v in streams.items()))
            self.shapes.add((key, (getattr(matcher, "last_compact", None) or {}).get("verify_k")))
            return out

        matcher.dispatch = dispatch

    def stats(self) -> dict:
        """``EngineStats``' counters that the runner and the engine's
        metric readers read."""
        s = self.engine.stats
        return {
            "rows": s.rows, "memo_slots": s.memo_slots,
            "host_confirm_seconds": s.host_confirm_seconds,
            "device_compiles": s.device_compiles,
            "device_faults": s.device_faults,
            "degraded_batches": s.degraded_batches,
        }

    def notes(self) -> list:
        return ["encoded batch shapes (streams, phase-B width): "
                + "; ".join(f"{dict(s)} k={k}" for s, k in sorted(self.shapes, key=str))]
