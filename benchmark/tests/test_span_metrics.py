"""The readers of the program's own spans, on a small hand-built trace.

Device 0 runs three programs in a 100 ms window; the host lines hold the
program's spans. The 20-50 ms gap straddles the engine's walk and the
classifier's veto walk: the readers split it by intersection, and
``breakdown`` names it after the span that holds most of it.
"""

from pathlib import Path
from types import SimpleNamespace

import pytest

from drivers.service_census import SPANS
from lib import spec as speclib
from lib import spans
from lib import trace as tr

REPO = Path(__file__).resolve().parents[2]
MS = 1_000_000
BUSY = [("jit_kernel_b(2)", 10 * MS, 10 * MS), ("jit_kernel_b(2)", 50 * MS, 10 * MS),
        ("jit_kernel_b(2)", 90 * MS, 10 * MS)]
MAIN = [
    ("bench.window", 0, 100 * MS),
    ("classify", 0, 100 * MS),  # the harness's own span: never read here
    ("classifier.classify", 0, 100 * MS),
    ("engine.encode_wait", 0, 8 * MS),
    ("device.launch", 8 * MS, 2 * MS),
    ("device.phase_a_wait", 9 * MS, 1 * MS),
    ("device.result_wait", 12 * MS, 8 * MS),
    ("engine.confirm_walk", 20 * MS, 10 * MS),
    ("engine.assemble", 30 * MS, 5 * MS),
    ("classifier.veto_walk", 35 * MS, 13 * MS),
    ("device.launch", 48 * MS, 2 * MS),
    ("device.result_wait", 52 * MS, 8 * MS),
    ("engine.confirm_walk", 60 * MS, 15 * MS),
    ("classifier.veto_walk", 75 * MS, 15 * MS),
    # past the window's end: clipped away
    ("classifier.veto_walk", 100 * MS, 9 * MS),
]
HELPER = [("engine.encode_chunk", 1 * MS, 6 * MS)]
TRACE = [
    ("/device:TPU:0", "XLA Modules", BUSY),
    ("/host:CPU", "python", MAIN),
    ("/host:CPU", "python", HELPER),
]


def _run(trace=TRACE, devices=(0,)):
    return SimpleNamespace(trace=trace, window_ns=(0, 100 * MS), calls=2,
                           devices=list(devices))


def _read(name, run):
    return speclib.reader(REPO, name)(run)


def test_gaps_are_split_by_intersection_not_by_midpoint():
    run = _run()
    gaps = tr.gaps(tr.busy(TRACE, 0, 0, 100 * MS), 0, 100 * MS)
    assert gaps == [(0, 10 * MS), (20 * MS, 50 * MS), (60 * MS, 90 * MS)]
    # each gap named after the innermost span that holds most of it: 13
    # ms of veto walk against 10 of confirm walk in the first; in the
    # second 15 ms of each, and the first to come wins the tie
    found = tr.host_spans(TRACE, SPANS)
    assert [spans.label(g, found) for g in gaps] == [
        "engine.encode_chunk", "classifier.veto_walk", "engine.confirm_walk"]
    # intersection: 13 + 15 ms to the classifier, 8 + 15 + 15 to the engine
    assert _read("classifier.exposed_ms", run) == pytest.approx(14.0)
    assert _read("engine.exposed_ms", run) == pytest.approx(19.0)


def test_span_sums_per_call_inside_the_window():
    run = _run()
    assert _read("classifier.veto_walk_ms", run) == pytest.approx(14.0)
    # 1 ms phase-A read and 16 ms of result reads over two calls
    assert _read("device.host_wait_ms", run) == pytest.approx(8.5)


def test_exposed_time_is_the_mean_over_devices():
    # device 1 is busy the whole window: nothing exposed there
    two = TRACE + [("/device:TPU:1", "XLA Modules", [("jit_kernel_b(2)", 0, 100 * MS)])]
    run = _run(two, devices=(0, 1))
    assert _read("classifier.exposed_ms", run) == pytest.approx(7.0)
    assert _read("engine.exposed_ms", run) == pytest.approx(9.5)


@pytest.mark.parametrize("name", ["classifier.veto_walk_ms", "classifier.exposed_ms",
                                  "engine.exposed_ms", "device.host_wait_ms"])
def test_a_program_without_the_spans_reads_nothing(name):
    """The parent's program opens none of these spans: each reader
    returns None, and the traced run leaves the metric out."""
    bare = [("/device:TPU:0", "XLA Modules", BUSY),
            ("/host:CPU", "python", [("bench.window", 0, 100 * MS),
                                     ("classify", 0, 100 * MS)])]
    assert _read(name, _run(bare)) is None
    assert _read(name, SimpleNamespace(trace=None, window_ns=(0, 0), calls=0,
                                       devices=[0])) is None


def test_overlap_of_interval_lists():
    assert spans.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert spans.overlap_ns([(0, 10)], [(10, 20)]) == 0
    assert spans.overlap_ns([], [(0, 1)]) == 0


def test_cover_of_the_idle_time_by_the_programs_spans():
    run = _run()
    cov = spans.cover(run, SPANS)
    # 70 ms idle over two calls, all of it inside classifier.classify
    assert cov["idle_ms_per_call"] == pytest.approx(35.0)
    assert cov["covered_share"] == pytest.approx(1.0)
    # 13 spans inside the window (the third veto walk lies past its end)
    assert cov["spans_per_call"] == pytest.approx(6.5)
    launch = cov["per_name"]["device.launch"]
    assert (launch["ms_per_call"], launch["idle_ms_per_call"], launch["count"]) == (
        pytest.approx(2.0), pytest.approx(2.0), 2)
    # the result reads wait on busy stretches: they cover no idle time
    assert cov["per_name"]["device.result_wait"]["idle_ms_per_call"] == 0
    # the confirm walks alone cover 25 of the 70 idle ms
    walk = spans.cover(run, ["engine.confirm_walk"])
    assert walk["covered_share"] == pytest.approx(25 / 70)
    assert walk["spans_per_call"] == pytest.approx(1.0)


def test_cover_without_a_device_or_without_the_spans():
    host_only = [t for t in TRACE if not t[0].startswith("/device:")]
    cov = spans.cover(_run(host_only), SPANS)
    assert cov["idle_ms_per_call"] is None and cov["covered_share"] is None
    assert cov["spans_per_call"] == pytest.approx(6.5)
    assert cov["per_name"]["engine.assemble"]["idle_ms_per_call"] is None
    bare = [("/device:TPU:0", "XLA Modules", BUSY),
            ("/host:CPU", "python", [("bench.window", 0, 100 * MS)])]
    assert spans.cover(_run(bare), SPANS) is None
