"""The reduction on a trace recorded on the chip, as
``lib.trace.from_profile_data`` keeps it: a census-rescan ``--trace 1``
run on one v5e (program executions of TPU 0 and the host spans of that
time). That trace holds none of the program's own spans, so the naming
of gaps is checked on one census-fresh call laid out from the per-call
times of a traced census-fresh run on one v5e (PERF.md section 5): two
1,024-row chunks, chunk 0 walked under chunk 1's phase B."""

import json
from pathlib import Path

from drivers.service_census import SPANS
from lib import spans as sp
from lib import trace as tr

DATA = Path(__file__).resolve().parent / "data"


def _load(name):
    return [tuple(x) for x in json.loads((DATA / name).read_text())]


TRACE = _load("rescan_trace.json")
MS = 1_000_000


def _ms(*events):
    return [(n, int(a * MS), int((b - a) * MS)) for n, a, b in events]


FRESH = [
    ("/device:TPU:0", "XLA Modules", _ms(
        ("jit_kernel_a(1)", 16, 197), ("jit_kernel_b(2)", 199, 2549),
        ("jit_kernel_a(1)", 2551, 2732), ("jit_kernel_b(2)", 2751, 5101),
        ("jit_kernel_a(1)", 5666, 5847))),
    ("/host:CPU", "python3", _ms(
        ("bench.window", 0, 5700),
        ("classifier.classify", 0, 5650),
        ("engine.encode_wait", 0.5, 14),
        ("device.launch", 14, 200), ("device.phase_a_wait", 16, 197),
        ("device.launch", 200, 2751), ("device.phase_a_wait", 202, 2749),
        ("engine.confirm_walk", 2752, 2973), ("engine.assemble", 2973, 2980),
        ("device.result_wait", 2980, 5103),
        ("engine.confirm_walk", 5103, 5295), ("engine.assemble", 5295, 5302),
        ("classifier.veto_walk", 5310, 5641),
        ("classifier.classify", 5650.4, 11300),
        ("engine.encode_wait", 5651, 5665), ("device.launch", 5665, 5700))),
    ("/host:CPU", "python3", _ms(
        ("engine.encode_chunk", 1, 13.5), ("engine.encode_chunk", 20, 32),
        ("engine.encode_chunk", 5652, 5664))),
]


def _window(trace=TRACE):
    (_n, t0, t1), = tr.host_spans(trace, ["bench.window"])
    return t0, t1


def test_busy_is_the_union_of_program_executions():
    t0, t1 = _window()
    assert tr.device_ids(TRACE) == [0]
    busy = tr.busy(TRACE, 0, t0, t1)
    assert all(a < b <= c for (a, b), (c, _d) in zip(busy, busy[1:]))
    total = tr.busy_ns(TRACE, 0, t0, t1)
    kernels = tr.time_ns(TRACE, 0, tr.MODULES_LINE, r"^jit_kernel", t0, t1)
    # the matcher's two programs are nearly all the device does, and
    # never overlap each other on one device
    assert 0.95 * total <= kernels <= total
    assert 0 < total < t1 - t0


def test_gaps_fill_the_rest_of_the_window():
    for trace in (TRACE, FRESH):
        t0, t1 = _window(trace)
        gaps = tr.gaps(tr.busy(trace, 0, t0, t1), t0, t1)
        assert gaps
        assert sum(b - a for a, b in gaps) + tr.busy_ns(trace, 0, t0, t1) == t1 - t0


def test_gaps_are_named_after_the_programs_spans():
    """``breakdown``'s labels: each gap is named after the innermost
    program span that holds most of it."""
    t0, t1 = _window(FRESH)
    gaps = tr.gaps(tr.busy(FRESH, 0, t0, t1), t0, t1)
    found = tr.host_spans(FRESH, SPANS)
    named = {(round(a / MS), round(b / MS)): sp.label((a, b), found) for a, b in gaps}
    assert named == {
        (0, 16): "engine.encode_chunk",  # the helper encodes chunk 0
        (197, 199): "device.launch",  # phase A's scalar read, phase B's launch
        (2549, 2551): "device.phase_a_wait",
        (2732, 2751): "device.phase_a_wait",
        # 192 ms of chunk 1's confirm walk against 331 of veto walk
        (5101, 5666): "classifier.veto_walk",
    }
    # the 0.4 ms between the calls belongs to no span: the harness's
    assert sp.label((5650 * MS, 5650 * MS + 400_000), found) == "harness"
