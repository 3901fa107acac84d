"""Continuous-batching scheduler (swarm_tpu/sched, docs/PIPELINE.md).

Three contracts pinned here:

1. **Bucket planning** — width-class choice, flush-at-target, partial
   final flush, fill-ratio accounting, occupancy reporting.
2. **Prefetch/backpressure bounds** — in-flight device batches never
   exceed the configured cap, queue depth bounds the encoded-batch
   buffer, and every row comes back exactly once (stub engine, so the
   bound is observed deterministically).
3. **End-to-end parity** — ``pipeline=on`` produces bit-identical
   verdicts AND extractions to ``pipeline=off`` on the test corpus:
   cold (fresh content), memo-warm, with dead rows interleaved, and
   through the decode-on-prefetch path.
"""

import threading

import numpy as np
import pytest

from swarm_tpu.fingerprints.model import Response
from swarm_tpu.ops.encoding import _width_for, width_class
from swarm_tpu.sched import (
    BatchScheduler,
    BucketPlanner,
    SchedulerConfig,
)

# ----------------------------------------------------------------------
# bucket planner
# ----------------------------------------------------------------------


def test_width_class_mirrors_encoder_rounding():
    assert width_class(0) == 512
    assert width_class(1) == 512
    assert width_class(512) == 512
    assert width_class(513) == 1024
    assert width_class(1500) == 1536  # _width_for's 512-multiple ladder
    assert width_class(99999) == 4096  # capped
    assert width_class(700, cap=1024) == 1024
    assert width_class(3000, multiple=512, cap=2048) == 2048
    # lockstep with the encoder: a bucket's encoded width is its class,
    # so each bucket pins exactly one compiled shape
    for n in (0, 1, 511, 512, 513, 1100, 1536, 2047, 4000, 9999):
        assert width_class(n) == _width_for(
            np.asarray([n]), cap=4096, multiple=512
        ), n


def _row(body_len: int, header_len: int = 10, banner: bool = False):
    blob = b"x" * body_len
    return Response(
        body=b"" if banner else blob,
        banner=blob if banner else None,
        header=b"h" * header_len,
        status=200,
    )


def test_bucket_choice_body_and_banner():
    p = BucketPlanner(rows_target=8, max_body=4096, max_header=1024)
    assert p.bucket_of(_row(100)) == (512, 512)
    assert p.bucket_of(_row(600)) == (1024, 512)
    assert p.bucket_of(_row(600, header_len=800)) == (1024, 1024)
    assert p.bucket_of(_row(1500)) == (1536, 512)
    # "body" is the banner when one is set (encoding part semantics)
    assert p.bucket_of(_row(2000, banner=True)) == (2048, 512)


def test_planner_flushes_at_target_and_keys_by_shape():
    p = BucketPlanner(rows_target=3)
    out = []
    for i in range(5):
        pb = p.add_fresh(i, _row(100))
        if pb:
            out.append(pb)
    # rows 0-2 flushed as one full bucket; 3-4 still pending
    assert len(out) == 1
    assert out[0].ids == [0, 1, 2]
    assert out[0].bucket == "w512h512"
    assert out[0].kind == "fresh" and not out[0].final
    # a different shape accumulates independently
    assert p.add_fresh(5, _row(1500)) is None
    assert p.occupancy() == {"w512h512": 2, "w1536h512": 1}
    finals = list(p.flush_all())
    assert {f.bucket for f in finals} == {"w512h512", "w1536h512"}
    assert all(f.final for f in finals)
    assert p.pending_rows == 0


def test_planner_memo_lane_and_fill_ratio():
    p = BucketPlanner(rows_target=4)
    outs = [p.add_known(i, _row(10)) for i in range(5)]
    full = [o for o in outs if o]
    assert len(full) == 1 and full[0].bucket == "memo"
    assert full[0].ids == [0, 1, 2, 3]
    (tail,) = list(p.flush_all())
    assert tail.ids == [4] and tail.kind == "memo"
    # fill ratio is against the engine's 256-row padding
    assert full[0].fill_rows == pytest.approx(4 / 256)
    assert tail.fill_rows == pytest.approx(1 / 256)


# ----------------------------------------------------------------------
# prefetch / backpressure bounds (stub engine: deterministic)
# ----------------------------------------------------------------------


class _StubDB:
    num_templates = 1
    template_ids = ["t"]


class _StubPacked:
    template_ids = ["t"]
    extractions: dict = {}
    host_always_matches: list = []
    confirms_per_row: dict = {}

    def __init__(self, n):
        self.bits = np.zeros((n, 1), dtype=np.uint8)


class _StubEngine:
    """Just the scheduler-facing surface. Tracks concurrency bounds."""

    batch_rows = 8
    max_body = 4096
    max_header = 1024
    db = _StubDB()

    def __init__(self):
        self.inflight = 0
        self.max_inflight = 0
        self.outstanding_encodes = 0
        self.max_outstanding_encodes = 0
        self.lock = threading.Lock()

    def _use_native_memo(self):
        return False

    def memo_known_mask(self, rows):
        return np.zeros(len(rows), dtype=np.uint8)

    def encode_packed(self, rows, reuse_buffers=False):
        with self.lock:
            self.outstanding_encodes += 1
            self.max_outstanding_encodes = max(
                self.max_outstanding_encodes, self.outstanding_encodes
            )
        return ("stub", list(rows))

    def begin_packed(self, rows, pre=None):
        with self.lock:
            self.inflight += 1
            self.max_inflight = max(self.max_inflight, self.inflight)
        return ("h", list(rows), pre)

    def finish_packed(self, handle):
        _tag, rows, _pre = handle
        with self.lock:
            self.inflight -= 1
            if _pre is not None:
                self.outstanding_encodes -= 1
        return _StubPacked(len(rows))

    def rowmatches_from_packed(self, packed, n):
        from swarm_tpu.ops.engine import RowMatches

        return [
            RowMatches(template_ids=[], extractions={}) for _ in range(n)
        ]


@pytest.mark.parametrize("prefetch", ["inline", "thread"])
@pytest.mark.parametrize("inflight", [1, 2, 3])
def test_inflight_never_exceeds_cap(prefetch, inflight):
    eng = _StubEngine()
    sched = BatchScheduler(
        eng,
        SchedulerConfig(
            rows_target=8, inflight=inflight, prefetch=prefetch
        ),
    )
    sched._overlap_helps = True  # exercise the configured depth
    chunks = [[_row(50) for _ in range(5)] for _ in range(20)]
    total = 0
    for res in sched.run(chunks):
        total += len(res)
    assert total == 100
    assert eng.inflight == 0
    assert eng.max_inflight <= inflight
    # backpressure: encoded-but-unwalked batches stay bounded by
    # queue + in-flight + the one being produced
    assert (
        eng.max_outstanding_encodes
        <= sched.config.queue_depth + inflight + 1
    )


def test_results_in_order_across_bucket_shapes():
    eng = _StubEngine()
    sched = BatchScheduler(eng, SchedulerConfig(rows_target=4))
    # alternating shapes so consecutive rows land in different buckets
    chunks = [
        [_row(100 if (i + j) % 2 else 1500) for j in range(6)]
        for i in range(4)
    ]
    out = list(sched.run(chunks))
    assert [len(c) for c in out] == [6, 6, 6, 6]
    assert sched.stats.fresh_rows == 24
    # every device batch carries a fill ratio <= 1
    assert 0 < sched.stats.fill_ratio <= 1


def test_dead_rows_resolve_without_engine_traffic():
    eng = _StubEngine()
    sched = BatchScheduler(eng, SchedulerConfig(rows_target=4))
    dead = Response(host="d", alive=False)
    chunks = [[dead, _row(10), dead]]
    (res,) = list(sched.run(chunks))
    assert len(res) == 3
    assert res[0].template_ids == [] and res[2].template_ids == []
    assert sched.stats.dead_rows == 2 and sched.stats.fresh_rows == 1


def test_accelerator_drives_inflight_ge2_with_walk_offload():
    """On an accelerator backend the submit thread must keep ≥2 device
    batches genuinely in flight WHILE the offloaded walk runs (the
    ISSUE-6 overlap acceptance), and the recycled-plane accounting
    stays closed: begun-but-unwalked batches never exceed the offload
    cap (3) plus the single offloaded walk."""
    eng = _StubEngine()
    sched = BatchScheduler(
        eng,
        SchedulerConfig(
            rows_target=8, inflight=4, walk_offload="on",
            prefetch="inline",
        ),
    )
    sched._overlap_helps = True  # accelerator backend
    chunks = [[_row(50) for _ in range(5)] for _ in range(30)]
    total = sum(len(r) for r in sched.run(chunks))
    assert total == 150
    assert eng.inflight == 0
    assert eng.max_inflight >= 2, "overlap must actually happen"
    assert eng.max_inflight <= 4  # cap 3 + the one offloaded walk
    assert sched.stats.offloaded_walks > 0


def test_cpu_fallback_still_collapses_inflight_to_1():
    """The CPU backend's XLA threads ARE the walk's cores: in-flight
    must still collapse to 1 there, whatever the configured depth."""
    eng = _StubEngine()
    sched = BatchScheduler(
        eng, SchedulerConfig(rows_target=8, inflight=4, prefetch="inline")
    )
    sched._overlap_helps = False  # CPU fallback
    chunks = [[_row(50) for _ in range(5)] for _ in range(10)]
    total = sum(len(r) for r in sched.run(chunks))
    assert total == 50
    assert eng.max_inflight <= 1


def test_producer_error_propagates():
    eng = _StubEngine()
    sched = BatchScheduler(
        eng, SchedulerConfig(rows_target=4, prefetch="thread")
    )

    def chunks():
        yield [_row(10)]
        raise RuntimeError("decode blew up")

    with pytest.raises(RuntimeError, match="decode blew up"):
        list(sched.run(chunks()))


# ----------------------------------------------------------------------
# end-to-end parity on the test corpus
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def engines():
    from swarm_tpu.fingerprints import load_corpus
    from swarm_tpu.ops.engine import MatchEngine

    templates, _errors = load_corpus("tests/data/templates")
    e_off = MatchEngine(templates, mesh=None, batch_rows=128)
    e_on = MatchEngine(
        templates, mesh=None, batch_rows=128, pipeline="on"
    )
    return e_off, e_on


def _scan_rows(n: int, seed: int = 7, salt: bool = False):
    rng = np.random.default_rng(seed)
    bodies = [
        b"<html><head><title>Welcome to nginx!</title></head></html>",
        b"<html><head><title>Grafana</title></head><body>"
        b"grafana v9.1.0</body></html>",
        b"<html>404 Not Found</html>",
        b"",
        b"A" * 900,  # crosses into the 1024 width class
        b"B" * 1800,  # 2048 class
    ]
    rows = []
    for i in range(n):
        body = bodies[i % len(bodies)]
        if salt:
            body = (
                b"<!-- %s -->" % bytes(
                    rng.integers(97, 123, size=24, dtype=np.uint8)
                )
            ) + body
        rows.append(
            Response(
                host=f"198.51.100.{i % 254}",
                port=(80, 443)[i % 2],
                status=(200, 404, 301)[i % 3],
                body=body,
                header=b"Server: nginx\r\nContent-Type: text/html",
            )
        )
    # interleave dead rows (match nothing by contract)
    for k in (3, 11, n - 2):
        if 0 <= k < n:
            rows[k] = Response(host=f"dead{k}", alive=False)
    return rows


def _assert_same(a, b):
    # EXACT id order: both assembly paths emit ascending template
    # index, then the host-always tail (confirmed_on_host is excluded —
    # confirm attribution follows each batch's dedup representative)
    assert len(a) == len(b)
    for i, (ra, rb) in enumerate(zip(a, b)):
        assert ra.template_ids == rb.template_ids, i
        assert ra.extractions == rb.extractions, i


def test_pipeline_parity_cold_and_memo_warm(engines):
    e_off, e_on = engines
    rows = _scan_rows(300, salt=True)
    r_off = e_off.match(rows)
    r_on = e_on.match(rows)
    _assert_same(r_off, r_on)
    # memo-warm second pass (content now resident in both engines):
    # the scheduler's memo split and steady-state speculation kick in
    clones = [
        Response(
            host=r.host, port=r.port, status=r.status,
            body=bytes(memoryview(r.body)),
            header=bytes(memoryview(r.header)),
            banner=None if r.banner is None else bytes(memoryview(r.banner)),
            alive=r.alive,
        )
        for r in rows
    ]
    _assert_same(e_off.match(clones), e_on.match(clones))
    assert e_on.scheduler().stats.memo_rows > 0


def test_pipeline_parity_through_run_with_decode(engines):
    e_off, e_on = engines
    rows = _scan_rows(120, seed=13, salt=True)
    chunks = [rows[i : i + 40] for i in range(0, len(rows), 40)]
    # decode runs on the prefetch stage: payloads are (index, rows)
    payloads = list(enumerate(chunks))
    seen_chunks = []

    def decode(payload):
        ci, chunk_rows = payload
        seen_chunks.append(ci)
        return chunk_rows

    out = []
    for res in e_on.scheduler().run(payloads, decode=decode):
        out.append(res)
    assert seen_chunks == [0, 1, 2]
    assert [len(c) for c in out] == [40, 40, 40]
    flat_on = [rm for c in out for rm in c]
    flat_off = e_off.match(rows)
    _assert_same(flat_off, flat_on)


def test_worker_runtime_tpu_pipeline_parity(tmp_path, monkeypatch):
    """The worker's response-lines tpu path (`_execute_tpu`) produces
    byte-identical output with `Config.pipeline="on"` (decode rides the
    scheduler's prefetch stage) vs the direct path."""
    import json

    # single-device engine: the virtual 8-device mesh is exercised by
    # test_sharding, not here (and this jax build lacks shard_map)
    import swarm_tpu.parallel.mesh as mesh_mod

    monkeypatch.setattr(mesh_mod, "make_mesh", lambda *a, **k: None)

    from swarm_tpu.config import Config
    from swarm_tpu.worker.modules import ModuleSpec
    from swarm_tpu.worker.runtime import JobProcessor

    module = ModuleSpec(
        "nuclei",
        {"backend": "tpu", "templates": "tests/data/templates"},
    )
    lines = []
    for i, r in enumerate(_scan_rows(90, seed=21, salt=True)):
        lines.append(
            json.dumps(
                {
                    "host": r.host,
                    "port": r.port,
                    "status": r.status,
                    "body": r.body.decode("latin-1"),
                    "header": r.header.decode("latin-1"),
                    "alive": r.alive,
                }
            )
        )
    data = ("\n".join(lines) + "\n").encode()
    outs = {}
    for mode in ("off", "on"):
        cfg = Config.load(
            server_url="http://127.0.0.1:1", api_key="k",
            worker_id="w", pipeline=mode,
        )
        proc = JobProcessor(
            cfg, client=object(), work_dir=str(tmp_path / mode)
        )
        outs[mode] = proc._execute_tpu(module, data)
        assert proc._engines["tests/data/templates"].pipeline == mode
    assert outs["on"] == outs["off"]


def test_scheduler_telemetry_families_present(engines):
    _e_off, e_on = engines
    from swarm_tpu.telemetry import REGISTRY

    e_on.match(_scan_rows(64, seed=99, salt=True))
    snap = REGISTRY.snapshot()
    for family in (
        "swarm_sched_batches_total",
        "swarm_sched_rows_total",
        "swarm_sched_fill_ratio",
        "swarm_sched_prefetch_stall_seconds_total",
        "swarm_sched_inflight_depth",
        "swarm_sched_bucket_rows",
    ):
        assert family in snap, family
