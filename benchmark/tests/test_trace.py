"""The trace reduction on a small trace: busy union, idle share, kernel
sums and gap attribution."""

from lib import spans as sp
from lib import trace as tr

MS = 1_000_000
TRACE = [
    ("/device:TPU:0", "XLA Modules", [("jit_kernel_a(1)", 10 * MS, 5 * MS),
                                      ("jit_kernel_b(2)", 20 * MS, 10 * MS),
                                      ("jit_other(3)", 60 * MS, 5 * MS)]),
    ("/device:TPU:0", "XLA Ops", [("fusion.1", 10 * MS, 3 * MS), ("fusion.2", 12 * MS, 3 * MS),
                                  ("all-reduce.7", 20 * MS, 10 * MS),
                                  ("copy.3", 60 * MS, 5 * MS)]),
    ("/device:TPU:1", "XLA Modules", [("jit_kernel_b(2)", 0, 100 * MS)]),
    ("/host:CPU", "python", [("bench.window", 5 * MS, 90 * MS), ("classify", 5 * MS, 80 * MS),
                             ("engine.match", 8 * MS, 40 * MS)]),
]


def test_busy_union_and_idle_share():
    t0, t1 = 5 * MS, 95 * MS
    assert tr.device_ids(TRACE) == [0, 1]
    assert tr.busy(TRACE, 0, t0, t1) == [(10 * MS, 15 * MS), (20 * MS, 30 * MS), (60 * MS, 65 * MS)]
    assert tr.busy_ns(TRACE, 0, t0, t1) == 20 * MS
    assert tr.busy_ns(TRACE, 1, t0, t1) == 90 * MS  # clipped to the window


def test_kernel_sums():
    t0, t1 = 5 * MS, 95 * MS
    kernels = r"^jit_(kernel|kernel_a|kernel_b)\b"
    assert tr.time_ns(TRACE, 0, tr.MODULES_LINE, kernels, t0, t1) == 15 * MS
    top = tr.top_names(TRACE, [0], tr.MODULES_LINE, t0, t1, 2)
    assert top == [["jit_kernel_b(2)", 0.01], ["jit_kernel_a(1)", 0.005]]


def test_gaps_are_attributed_to_the_innermost_open_span():
    t0, t1 = 5 * MS, 95 * MS
    gaps = tr.gaps(tr.busy(TRACE, 0, t0, t1), t0, t1)
    assert gaps == [(5 * MS, 10 * MS), (15 * MS, 20 * MS), (30 * MS, 60 * MS), (65 * MS, 95 * MS)]
    spans = tr.host_spans(TRACE, ["classify", "engine.match"])
    labels = [sp.label(g, spans) for g in gaps]
    # (30, 60): 18 ms of engine.match, then 12 of classify alone
    assert labels == ["classify", "engine.match", "engine.match", "classify"]
    assert sp.label((90 * MS, 94 * MS), spans) == "harness"
    # (44, 100): 4 ms of engine.match, 37 of classify, 15 of neither
    assert sp.label((44 * MS, 100 * MS), spans) == "classify"
    assert sp.label((70 * MS, 110 * MS), spans, "loop") == "loop"
