"""The traced run's device line: busy and window seconds, and ``breakdown``,
whose idle gaps are named from the spans the cell's driver lists (``SPANS``)."""

from __future__ import annotations

from lib import spans as sp
from lib import trace as tr


def device_summary(run) -> tuple:
    t0, t1 = run.window_ns
    devices = [d for d in tr.device_ids(run.trace) if d in run.devices] or tr.device_ids(run.trace)
    window_s = (t1 - t0) / 1e9
    if not devices:
        return 0.0, window_s, {"device_ops": [], "idle_gaps": []}
    busy_s = sum(tr.busy_ns(run.trace, d, t0, t1) for d in devices) / len(devices) / 1e9
    ops = tr.top_names(run.trace, devices, tr.MODULES_LINE, t0, t1, 10)
    found = tr.host_spans(run.trace, run.program_spans)
    gaps = tr.gaps(tr.busy(run.trace, devices[0], t0, t1), t0, t1)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    idle = [[sp.label(g, found), (g[1] - g[0]) / 1e9] for g in longest]
    return busy_s, window_s, {"device_ops": ops, "idle_gaps": idle}
