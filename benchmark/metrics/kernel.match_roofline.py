"""The matcher's share of its bytes-bound floor: the least time the chip
could take to read each call's unpadded input bytes and write one
verdict bit per row and verdict (the driver's ``verdict_width``: the
census DB's match directives), at the device's peak HBM bandwidth
(lib/peaks.json), over the matcher's device time (kernel.match_ms). The
floor comes from the traffic and the reference alone; a reference
without ``verdict_width`` gives none."""

from pathlib import Path

from lib import spec


def read(run):
    kernel = spec.metric_module(Path(__file__).resolve().parents[2], "kernel.match_ms")
    s = kernel.kernel_s(run)
    bw = run.peaks.get("hbm_bytes_per_s")
    if s is None or not bw or not run.calls or run.verdict_width is None:
        return None
    rows_per_call = run.rows / run.calls
    floor_bytes = sum(run.call_bytes) + run.calls * rows_per_call * run.verdict_width / 8
    return floor_bytes / bw / s * 100.0
