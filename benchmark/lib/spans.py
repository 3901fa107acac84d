"""The program's own spans in a traced window, per call.

The program opens its spans with ``telemetry/tracing.py`` ``span()``,
which reaches the profiler trace as a host event of the same name, on
the clock of the device planes. Each reader names the spans it reads;
the spans a deployment opens are its driver's ``SPANS``. A program
without the named spans leaves the readers nothing to read: they
return None.
"""

from __future__ import annotations

from lib import trace as tr


def _clipped(run, names) -> list | None:
    """(start, end) of each host span named in ``names``, cut to the
    window; None where the trace holds no such span at all."""
    if run.trace is None or not run.calls:
        return None
    found = tr.host_spans(run.trace, names)
    if not found:
        return None
    t0, t1 = run.window_ns
    return tr.clip(((n, s, e - s) for n, s, e in found), t0, t1)


def _idle_gaps(run) -> list:
    """The window's idle gaps on each of the cell's devices in the trace."""
    t0, t1 = run.window_ns
    devices = [d for d in tr.device_ids(run.trace or []) if d in run.devices]
    return [tr.gaps(tr.busy(run.trace, d, t0, t1), t0, t1) for d in devices]


def overlap_ns(a: list, b: list) -> int:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _exposed_ns(gaps: list, ivs: list) -> float:
    """Idle time inside the union of ``ivs``, mean over the devices'
    ``gaps``."""
    held = tr.union(ivs)
    return sum(overlap_ns(g, held) for g in gaps) / len(gaps)


def span_ms(run, names) -> float | None:
    """Summed time of the spans named in ``names`` within the window,
    in ms per call."""
    ivs = _clipped(run, names)
    if ivs is None:
        return None
    return sum(b - a for a, b in ivs) / run.calls / 1e6


def exposed_ms(run, names) -> float | None:
    """Device idle time during which one of the named spans is open, in
    ms per call: each idle gap of the window intersected with the union
    of the spans (all of each gap they cover, not the span open at its
    middle), summed, mean over the cell's devices."""
    ivs = _clipped(run, names)
    gaps = _idle_gaps(run)
    if ivs is None or not gaps:
        return None
    return _exposed_ns(gaps, ivs) / run.calls / 1e6


def label(gap: tuple, found: list, default: str = "harness") -> str:
    """The name that holds most of ``gap``: each instant of it goes to
    the innermost (shortest) of the spans in ``found`` open then, or to
    ``default`` where none is, and the name with the most time wins."""
    lo, hi = gap
    inside = [(e - s, n, max(s, lo), min(e, hi)) for n, s, e in found if s < hi and e > lo]
    cuts = sorted({lo, hi} | {a for *_x, a, _b in inside} | {b for *_x, b in inside})
    held: dict = {}
    for a, b in zip(cuts, cuts[1:]):
        open_ = [(d, n) for d, n, s, e in inside if s <= a and b <= e]
        name = min(open_)[1] if open_ else default
        held[name] = held.get(name, 0) + b - a
    return max(held, key=held.get)


def cover(run, names) -> dict | None:
    """What the named spans say of the window, per call: the device's
    idle ms, the share of it inside the union of all the spans, the
    spans opened, and for each name its ms, the idle ms it covers and
    its count. Idle figures are None where the trace has no device of
    the cell; the whole is None where it holds none of the spans."""
    if not _clipped(run, names):
        return None
    gaps = _idle_gaps(run)
    calls = run.calls
    per_name, n_spans = {}, 0
    for name in names:
        ivs = _clipped(run, [name]) or []
        n_spans += len(ivs)
        per_name[name] = {
            "ms_per_call": sum(b - a for a, b in ivs) / calls / 1e6,
            "idle_ms_per_call": (_exposed_ns(gaps, ivs) / calls / 1e6
                                 if gaps else None),
            "count": len(ivs),
        }
    idle_ns = (sum(sum(b - a for a, b in g) for g in gaps) / len(gaps)
               if gaps else None)
    covered = _exposed_ns(gaps, _clipped(run, names)) if gaps else None
    return {
        "idle_ms_per_call": None if idle_ns is None else idle_ns / calls / 1e6,
        "covered_share": covered / idle_ns if idle_ns else None,
        "spans_per_call": n_spans / calls,
        "per_name": per_name,
    }
