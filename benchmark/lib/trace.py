"""Reduction from a profiler trace to device busy time, kernel time and gaps.

The reduction works on a neutral form, ``[(plane, line, [(name, start_ns,
dur_ns), ...]), ...]``, which :func:`from_profile_data` makes from
``jax.profiler.ProfileData`` and the tests make from a small recorded
trace. Device planes are ``/device:TPU:<n>``. A program's execution is an
event on the plane's ``XLA Modules`` line, and the device counts as busy
while one runs; an operation inside it is an event on the ``XLA Ops``
line, which is not read: the matcher's while loops put some 170,000 op
events a second on it. Host and device events share one clock. Host
spans (the program's own and the harness's ``bench.window``) are
``TraceAnnotation`` events, found by name on any host line.
"""

from __future__ import annotations

import re
from typing import Iterable

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE = "XLA Modules"


def from_profile_data(pd) -> list:
    """The neutral form: every host line, and each device plane's
    ``XLA Modules`` line."""
    out = []
    for plane in pd.planes:
        device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if device and line.name != MODULES_LINE:
                continue
            out.append((
                plane.name, line.name,
                [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events],
            ))
    return out


def device_ids(trace: list) -> list:
    return sorted({int(m.group(1)) for p, _l, _e in trace if (m := DEVICE_PLANE.match(p))})


def _events(trace: list, device: int, line: str) -> list:
    plane = f"/device:TPU:{device}"
    return [e for p, ln, evs in trace if p == plane and ln == line for e in evs]


def clip(events: Iterable, t0: int, t1: int) -> list:
    """(start, end) of each event, cut to [t0, t1]; empty ones dropped."""
    out = []
    for _n, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: list) -> list:
    merged: list = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy(trace: list, device: int, t0: int, t1: int) -> list:
    """The union of the device's program executions within [t0, t1]."""
    return union(clip(_events(trace, device, MODULES_LINE), t0, t1))


def busy_ns(trace: list, device: int, t0: int, t1: int) -> int:
    return sum(b - a for a, b in busy(trace, device, t0, t1))


def gaps(intervals: list, t0: int, t1: int) -> list:
    """The idle stretches of [t0, t1] between busy ``intervals``."""
    out, t = [], t0
    for a, b in intervals:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t1 > t:
        out.append((t, t1))
    return out


def time_ns(trace: list, device: int, line: str, pattern: str, t0: int, t1: int) -> int:
    """Summed device time of the events on ``line`` whose name matches."""
    rx = re.compile(pattern)
    return sum(
        b - a for a, b in clip(
            (e for e in _events(trace, device, line) if rx.search(e[0])), t0, t1
        )
    )


def top_names(trace: list, devices: list, line: str, t0: int, t1: int, k: int) -> list:
    """The ``k`` names on ``line`` with the most device time, in seconds,
    averaged over ``devices``."""
    tot: dict = {}
    for d in devices:
        for name, s, dur in _events(trace, d, line):
            a, b = max(s, t0), min(s + dur, t1)
            if b > a:
                tot[name] = tot.get(name, 0) + (b - a)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9 / len(devices)] for name, ns in ranked]


def host_spans(trace: list, names: Iterable[str]) -> list:
    """(name, start, end) of every host event named in ``names``."""
    want = set(names)
    return [
        (n, s, s + d)
        for p, _l, evs in trace if not DEVICE_PLANE.match(p)
        for n, s, d in evs if n in want
    ]
