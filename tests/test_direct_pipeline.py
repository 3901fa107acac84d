"""The direct path's one-deep pipeline (``MatchEngine.match``, ``pipeline="off"``).

A call of several chunks launches chunk i+1's kernels (``begin_packed``)
before it walks chunk i (``finish_packed``). Results, extractions and
the verdict memo's contents after the call equal the scheduler path's,
the CPU oracle's and those of the old serial order (``match_packed`` per
chunk) — on fresh rows, on rows repeated across chunks, with dead rows,
and with the device failing from the second chunk's launch on.
"""

from __future__ import annotations

import dataclasses
import random
from pathlib import Path

import numpy as np
import pytest

from swarm_tpu.fingerprints import load_corpus
from swarm_tpu.fingerprints.model import Response
from swarm_tpu.ops import cpu_ref
from swarm_tpu.ops.engine import MatchEngine
from swarm_tpu.resilience.faults import clear_plan, install_plan
from tests.test_match_parity import fuzz_rows

DATA = Path(__file__).parent / "data" / "templates"
ROWS = 16  # batch_rows: 40 live rows are three chunks


@pytest.fixture(scope="module")
def templates():
    templates, errors = load_corpus(DATA)
    assert not errors
    return templates


def _case_rows(templates, case: str) -> list:
    base = fuzz_rows(templates, random.Random(31), 40)
    if case == "repeated":
        # chunks 1 and 2 carry chunk 0's content again, under other hosts
        copies = [dataclasses.replace(r, host=f"rep{i}")
                  for i, r in enumerate(base[:ROWS])]
        return base[:ROWS] + copies + base[ROWS : ROWS + 8]
    if case == "dead":
        rows = list(base)
        for k in (0, 5, 17, 33, 39):
            rows.insert(k, Response(host=f"dead{k}", alive=False))
        return rows
    return base


def _oracle(templates, rows) -> list:
    return [
        sorted(t.id for t in templates if cpu_ref.match_template(t, r).matched)
        if r.alive else []
        for r in rows
    ]


def _serial(eng, rows) -> list:
    """The old direct order: match_packed chunk by chunk, no overlap."""
    live = [r for r in rows if r.alive]
    out = iter([
        m
        for s in range(0, len(live), eng.batch_rows)
        for m in eng.rowmatches_from_packed(
            eng.match_packed(live[s : s + eng.batch_rows]),
            len(live[s : s + eng.batch_rows]),
        )
    ])
    return [next(out) if r.alive else None for r in rows]


def _run(templates, rows, fault: bool, serial: bool = False):
    eng = MatchEngine(templates, mesh=None, batch_rows=ROWS)
    if fault:
        # every launch from the second chunk's on fails: both orders
        # degrade chunks 1 and 2 to the CPU oracle
        install_plan("device.dispatch:2-99")
    try:
        got = _serial(eng, rows) if serial else eng.match(rows)
    finally:
        clear_plan()
    return eng, got


@pytest.mark.parametrize("case", ["fresh", "repeated", "dead", "fault"])
def test_direct_pipeline_parity(templates, case):
    rows = _case_rows(templates, case)
    fault = case == "fault"
    sched = MatchEngine(templates, mesh=None, batch_rows=ROWS, pipeline="on")
    want = sched.match(rows)
    eng, got = _run(templates, rows, fault)
    ref, serial = _run(templates, rows, fault, serial=True)

    assert len(got) == len(rows)
    for i, (g, w, o) in enumerate(zip(got, want, _oracle(templates, rows))):
        assert g.template_ids == w.template_ids, i
        assert g.extractions == w.extractions, i
        assert sorted(g.template_ids) == o, i
        if serial[i] is not None:
            assert g.template_ids == serial[i].template_ids, i
            assert g.extractions == serial[i].extractions, i
    assert eng.stats.rows == ref.stats.rows == sum(r.alive for r in rows)

    # the memo after the call: what the serial order leaves, and what it
    # serves on a second pass equals the first answers
    mask = eng.memo_known_mask(rows)
    assert np.array_equal(mask, ref.memo_known_mask(rows))
    if fault:
        assert eng.stats.degraded_batches == ref.stats.degraded_batches == 2
    else:
        assert np.array_equal(mask, sched.memo_known_mask(rows))
        assert mask.tolist() == [int(r.alive) for r in rows]
    again = eng.match(rows)
    assert [(m.template_ids, m.extractions) for m in again] == [
        (m.template_ids, m.extractions) for m in got
    ]


def test_overlapped_batches_counts_chunks_minus_one(templates):
    eng = MatchEngine(templates, mesh=None, batch_rows=ROWS)
    rows = fuzz_rows(templates, random.Random(32), 3 * ROWS)
    eng.match(rows)  # three chunks
    assert eng.stats.overlapped_batches == 2
    eng.match(fuzz_rows(templates, random.Random(33), ROWS))  # one chunk
    assert eng.stats.overlapped_batches == 2
    eng.match(fuzz_rows(templates, random.Random(34), 2 * ROWS))  # two
    assert eng.stats.overlapped_batches == 3


def test_memo_only_chunks_launch_nothing_and_count_no_overlap(templates):
    eng = MatchEngine(templates, mesh=None, batch_rows=ROWS)
    rows = fuzz_rows(templates, random.Random(35), 3 * ROWS)
    first = eng.match(rows)
    n0, dev0 = eng.stats.overlapped_batches, eng.stats.device_seconds
    again = eng.match(rows)
    assert eng.stats.overlapped_batches == n0
    assert eng.stats.device_seconds == dev0
    assert [m.template_ids for m in again] == [m.template_ids for m in first]


def test_sharded_direct_pipeline_matches_single_device(templates):
    """A mesh matcher's dispatch is asynchronous too: the direct path
    overlaps there, with the answers of the single-device engine."""
    rows = fuzz_rows(templates, random.Random(36), 40)
    one = MatchEngine(templates, mesh=None, batch_rows=ROWS).match(rows)
    eng = MatchEngine(templates, mesh="auto", batch_rows=ROWS)
    got = eng.match(rows)
    assert eng.sharded is not None
    assert eng.stats.overlapped_batches == 2
    assert [(m.template_ids, m.extractions) for m in got] == [
        (m.template_ids, m.extractions) for m in one
    ]
