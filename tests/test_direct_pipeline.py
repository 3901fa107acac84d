"""The direct path's one-deep pipeline (``MatchEngine.match``, ``pipeline="off"``).

A call of several chunks is cut in width order (rows stable-sorted by
width class, widest first), and launches chunk i+1's kernels
(``begin_packed``) before it walks chunk i (``finish_packed``). Results,
extractions and the verdict memo's contents after the call equal the
scheduler path's, the CPU oracle's and those of the same chunks matched
serially (``match_packed`` per chunk) — on fresh rows, on rows repeated
across chunks, with dead rows, on short and long rows mixed, and with
the device failing from the second chunk's launch on.
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
from swarm_tpu.ops.encoding import row_width_class
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


def _sized(row: Response, n: int) -> Response:
    """``row`` with its body (its banner, for a banner row) cut or
    padded with page text to ``n`` bytes."""
    blob = row.body if row.banner is None else row.banner
    blob = (blob + b" <p>padding of a long page</p>" * (n // 30 + 1))[:n]
    if row.banner is None:
        return dataclasses.replace(row, body=blob)
    return dataclasses.replace(row, banner=blob)


def _mixed_rows(templates, seed: int, count: int, every=5) -> list:
    """Short rows (body class 512) with every ``every``-th row a long
    page (class 1536 or 2048; none where ``every`` is None): in the
    caller's order each chunk holds long rows and would encode at their
    width."""
    rows = fuzz_rows(templates, random.Random(seed), count)
    return [
        _sized(
            r,
            1100 + 300 * (i % 3) if every and i % every == 2
            else min(r.content_length, 500),
        )
        for i, r in enumerate(rows)
    ]


def _width_ordered(rows) -> list:
    """Indices of ``rows`` in the direct path's chunk order: stable by
    width class, widest first."""
    return sorted(
        range(len(rows)), key=lambda i: row_width_class(rows[i]),
        reverse=True,
    )


def _case_rows(templates, case: str) -> list:
    if case.startswith("mixed"):
        base = _mixed_rows(templates, 37, 40)
        if case == "mixed-dead":
            for k in (0, 2, 17, 33, 39):
                base.insert(k, Response(host=f"dead{k}", alive=False))
        return base
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
    """The direct path's chunks without the overlap: the live rows in
    width order, match_packed chunk by chunk, answers put back in the
    caller's positions."""
    live = [r for r in rows if r.alive]
    order = _width_ordered(live)
    ordered = [live[i] for i in order]
    matched = [
        m
        for s in range(0, len(ordered), eng.batch_rows)
        for m in eng.rowmatches_from_packed(
            eng.match_packed(ordered[s : s + eng.batch_rows]),
            len(ordered[s : s + eng.batch_rows]),
        )
    ]
    by_live: list = [None] * len(live)
    for pos, i in enumerate(order):
        by_live[i] = matched[pos]
    out = iter(by_live)
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


@pytest.mark.parametrize(
    "case",
    ["fresh", "repeated", "dead", "fault", "mixed", "mixed-dead",
     "mixed-fault"],
)
def test_direct_pipeline_parity(templates, case):
    rows = _case_rows(templates, case)
    fault = case.endswith("fault")
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


def _spied(eng) -> tuple:
    """(chunks, widths): the rows of each chunk the engine encodes, and
    the body width of each device dispatch, in order."""
    eng._resolve_backend()
    chunks: list = []
    widths: list = []
    encode, dispatch = eng._encode_for_backend, eng.device.dispatch

    def encode_spy(rows, *a, **kw):
        chunks.append(list(rows))
        return encode(rows, *a, **kw)

    def dispatch_spy(streams, *a, **kw):
        widths.append(int(streams["body"].shape[1]))
        return dispatch(streams, *a, **kw)

    eng._encode_for_backend = encode_spy
    eng.device.dispatch = dispatch_spy
    return chunks, widths


@pytest.mark.parametrize(
    "case, narrowed, want_widths",
    [
        # 8 long rows (classes 2048 and 1536) go first, with the first 8
        # short rows; the 24 others fill two chunks at width 512
        ("mixed", 2, [2048, 512, 512]),
        # every row in class 512: the caller's chunks
        ("one-class", 0, [512, 512, 512]),
        # 12 rows are one chunk: the caller's order, at its widest row
        ("one-chunk", 0, [2048]),
    ],
)
def test_width_order_narrows_chunks(templates, case, narrowed, want_widths):
    if case == "mixed":
        rows = _mixed_rows(templates, 38, 40)
    elif case == "one-class":
        rows = _mixed_rows(templates, 38, 40, every=None)
    else:
        rows = _mixed_rows(templates, 38, 12)
    eng = MatchEngine(templates, mesh=None, batch_rows=ROWS)
    chunks, widths = _spied(eng)
    got = eng.match(rows)

    order = _width_ordered(rows) if case == "mixed" else range(len(rows))
    ordered = [rows[i] for i in order]
    assert chunks == [
        ordered[s : s + ROWS] for s in range(0, len(rows), ROWS)
    ]
    assert widths == want_widths
    assert eng.stats.narrowed_chunks == narrowed
    assert [sorted(m.template_ids) for m in got] == _oracle(templates, rows)

    if case == "mixed":
        # in the caller's order every chunk holds a long row
        ref = MatchEngine(templates, mesh=None, batch_rows=ROWS)
        _c, caller_widths = _spied(ref)
        for s in range(0, len(rows), ROWS):
            ref.match_packed(rows[s : s + ROWS])
        assert caller_widths == [2048, 2048, 2048]
        # a second call of the same rows is memo-served: it encodes no
        # chunk, and still counts the chunks it cut narrower
        eng.match(rows)
        assert widths == want_widths
        assert eng.stats.narrowed_chunks == 2 * narrowed


def test_sharded_direct_pipeline_mixed_widths_match_single_device(templates):
    """On a mesh the width-ordered chunks give the single device's
    answers too, in the caller's order, with dead rows among them."""
    rows = _case_rows(templates, "mixed-dead")
    one = MatchEngine(templates, mesh=None, batch_rows=ROWS).match(rows)
    eng = MatchEngine(templates, mesh="auto", batch_rows=ROWS)
    got = eng.match(rows)
    assert eng.sharded is not None
    assert eng.stats.narrowed_chunks == 2
    assert eng.stats.overlapped_batches == 2
    assert [(m.template_ids, m.extractions) for m in got] == [
        (m.template_ids, m.extractions) for m in one
    ]
    assert [sorted(m.template_ids) for m in got] == _oracle(templates, rows)
