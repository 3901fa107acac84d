"""The census path's own spans (telemetry/tracing.py ``span()``).

Every span reaches a recording JAX profiler session under its exact
name, on the device trace's clock, and — with operator tracing on and
an attempt context bound — the attempt's waterfall with its real start.
With neither, nothing is recorded. The executables the benchmark's
kernel reader finds by name keep those names.
"""

from __future__ import annotations

import glob
import json
import re
import socketserver
import subprocess
import sys
import threading
import time
from pathlib import Path

import jax
import pytest

from swarm_tpu.fingerprints.model import Response
from swarm_tpu.ops.service import ServiceClassifier
from swarm_tpu.telemetry import tracing

REPO = Path(__file__).resolve().parent.parent
BUNDLED_DB = str(REPO / "swarm_tpu" / "data" / "service-probes.txt")

#: what the census path records per call (the veto walk and the call)
#: and per chunk (the rest)
SPANS = (
    "classifier.classify",
    "classifier.veto_walk",
    "engine.encode_wait",
    "engine.encode_chunk",
    "engine.confirm_walk",
    "engine.assemble",
    "device.launch",
    "device.phase_a_wait",
    "device.result_wait",
)
#: the benchmark harness's own span names, which these must not reuse
HARNESS = ("classify", "engine.match", "engine.encode", "device.dispatch",
           "device.collect")


@pytest.fixture(scope="module")
def classifier():
    # 64-row chunks: one call of ``_rows`` takes three, so the pre-encode
    # helper and its wait both run
    clf = ServiceClassifier(db_path=BUNDLED_DB, mesh=None, batch_rows=64)
    clf.classify(*_rows("warm"))  # compile outside the tests' windows
    return clf


def _rows(tag: str):
    """130 fresh banners (ssh, http, smtp) and their sent probes."""
    banners = (
        [b"SSH-2.0-OpenSSH_8.9p1 Ubuntu-3ubuntu0.%d %s\r\n" % (i, tag.encode())
         for i in range(40)]
        + [b"HTTP/1.1 200 OK\r\nServer: nginx/1.18.%d\r\nX-Id: %s\r\n\r\n"
           % (i, tag.encode()) for i in range(40)]
        + [b"220 mx%d.%s ESMTP Postfix\r\n" % (i, tag.encode()) for i in range(50)]
    )
    rows = [Response(host=f"10.0.0.{i % 250}", port=22, banner=b)
            for i, b in enumerate(banners)]
    return rows, [None] * len(rows)


def _host_events(profile_dir: str) -> list:
    """(name, start_ns, end_ns) of every host event the session wrote."""
    files = glob.glob(f"{profile_dir}/plugins/profile/*/*.xplane.pb")
    assert files, "the profiler wrote no trace"
    pd = jax.profiler.ProfileData.from_file(files[-1])
    return [
        (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
        for plane in pd.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
    ]


def test_classify_spans_reach_the_profiler_trace_nested_in_the_call(
    classifier, tmp_path
):
    rows, sent = _rows("traced")
    with jax.profiler.trace(str(tmp_path)):
        out = classifier.classify(rows, sent)
    assert [o.service for o in out[:1]] == ["ssh"]
    events = [e for e in _host_events(str(tmp_path)) if e[0] in SPANS]
    names = {n for n, _s, _e in events}
    assert names == set(SPANS)  # every span, verbatim
    assert not names & set(HARNESS)
    (_n, c0, c1), = [e for e in events if e[0] == "classifier.classify"]
    for name, s, e in events:
        assert c0 <= s <= e <= c1, name
    per_chunk = [n for n, _s, _e in events if n == "engine.confirm_walk"]
    assert len(per_chunk) == 3  # per chunk, never per row


def test_direct_path_launches_the_next_chunk_before_walking_this_one(
    classifier, tmp_path
):
    """Three chunks: chunk i+1's ``device.launch`` starts before chunk
    i's ``engine.confirm_walk`` on the profiler trace, and each chunk but
    the last counts in ``overlapped_batches``. One chunk runs the serial
    call: encoded inline, nothing counted."""
    stats = classifier.engine.stats
    rows, sent = _rows("overlap")
    n0 = stats.overlapped_batches
    with jax.profiler.trace(str(tmp_path / "three")):
        classifier.classify(rows, sent)
    assert stats.overlapped_batches == n0 + 2
    order = [n for _s, n in sorted(
        (s, n) for n, s, _e in _host_events(str(tmp_path / "three"))
        if n in ("device.launch", "engine.confirm_walk"))]
    launch, walk = "device.launch", "engine.confirm_walk"
    # L0 L1 W0 L2 W1 W2: launch chunk i+1, then walk chunk i
    assert order == [launch, launch, walk, launch, walk, walk]

    rows, sent = _rows("one")
    with jax.profiler.trace(str(tmp_path / "one")):
        classifier.classify(rows[:10], sent[:10])
    assert stats.overlapped_batches == n0 + 2
    names = [n for n, _s, _e in _host_events(str(tmp_path / "one"))]
    assert names.count("device.launch") == names.count("engine.confirm_walk") == 1
    assert "engine.encode_wait" in names and "engine.encode_chunk" not in names


def test_scheduler_path_records_the_same_spans(tmp_path):
    """``pipeline="on"``: begin_packed / finish_packed reach the same
    shared methods; the scheduler encodes ahead itself, so no
    ``engine.encode_wait``."""
    clf = ServiceClassifier(db_path=BUNDLED_DB, mesh=None, batch_rows=64,
                            pipeline="on")
    clf.classify(*_rows("warm-sched"))
    with jax.profiler.trace(str(tmp_path)):
        clf.classify(*_rows("sched"))
    events = [e for e in _host_events(str(tmp_path)) if e[0] in SPANS]
    assert {n for n, _s, _e in events} == set(SPANS) - {"engine.encode_wait"}
    (_n, c0, c1), = [e for e in events if e[0] == "classifier.classify"]
    for name, s, e in events:
        assert c0 <= s <= e <= c1, name


def test_operator_tracing_lands_the_same_spans_with_real_starts(classifier):
    rows, sent = _rows("operator")
    ctx = tracing.TraceContext("t-spans", job_id="j1")
    tracing.set_enabled(True)
    try:
        w0 = time.time()
        with tracing.activate(ctx), tracing.span("execute"):
            classifier.classify(rows, sent)
        w1 = time.time()
    finally:
        tracing.set_enabled(None)
    spans = ctx.finish()
    by_name: dict = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    # the helper thread that encodes binds no context: its spans reach
    # the profiler only
    assert set(SPANS) - {"engine.encode_chunk"} <= set(by_name)
    assert "engine.encode_chunk" not in by_name
    assert not {"device", "device.phase_a", "device.phase_b", "walk"} & set(by_name)
    assert not any("phase_a_s" in (s.get("attrs") or {}) for s in spans)
    for s in spans[1:]:
        assert w0 <= s["start"] <= s["start"] + s["duration_s"] <= w1 + 1e-3
    (call,) = by_name["classifier.classify"]
    (execute,) = by_name["execute"]
    assert call["parent_id"] == execute["span_id"]
    (veto,) = by_name["classifier.veto_walk"]
    assert veto["parent_id"] == call["span_id"]
    walks = sorted(by_name["engine.confirm_walk"], key=lambda s: s["start"])
    assert len(walks) == 3
    # real starts: each chunk's walk begins after the previous ends,
    # and the veto walk after the last
    for a, b in zip(walks, walks[1:]):
        assert a["start"] + a["duration_s"] <= b["start"] + 1e-3
    assert walks[-1]["start"] <= veto["start"]
    # the direct path launches chunk i+1 before it walks chunk i
    launches = sorted(s["start"] for s in by_name["device.launch"])
    assert len(launches) == 3
    for launch, walk in zip(launches[1:], walks):
        assert launch < walk["start"]
    assert not hasattr(classifier.engine.stats, "phase_a_seconds")


def test_offloaded_walk_lands_in_the_attempt_waterfall():
    """``pipeline="on"`` with the walk on the scheduler's walk worker:
    the worker enters the submitting thread's binding, so the walk's
    spans nest under the call that handed it off."""
    clf = ServiceClassifier(db_path=BUNDLED_DB, mesh=None, batch_rows=64,
                            pipeline="on")
    sched = clf.engine.scheduler()
    sched.config.walk_offload = "on"
    clf.classify(*_rows("warm-walk"))
    walks0 = sched.stats.offloaded_walks
    ctx = tracing.TraceContext("t-walk", job_id="j2")
    tracing.set_enabled(True)
    try:
        with tracing.activate(ctx), tracing.span("sched"):
            clf.classify(*_rows("walk"))
    finally:
        tracing.set_enabled(None)
    assert sched.stats.offloaded_walks > walks0
    spans = ctx.finish()
    by_id = {s["span_id"]: s for s in spans}
    (call,) = [s for s in spans if s["name"] == "classifier.classify"]
    assert by_id[call["parent_id"]]["name"] == "sched"
    assert by_id[by_id[call["parent_id"]]["parent_id"]]["name"] == "attempt"
    for name in ("device.result_wait", "engine.confirm_walk", "engine.assemble"):
        found = [s for s in spans if s["name"] == name]
        assert found, name
        for s in found:
            # device.result_wait sits inside the walk (finish_packed)
            assert s["parent_id"] == call["span_id"] or (
                by_id[s["parent_id"]]["parent_id"] == call["span_id"]), name


def test_nothing_is_recorded_with_tracing_off_and_no_session():
    from swarm_tpu.telemetry.trace_export import TRACE_SPANS

    def spans_total() -> float:
        return sum(s["value"] for s in TRACE_SPANS.snapshot()["samples"])

    ctx = tracing.TraceContext("t-off")
    flight0 = tracing.FLIGHT.snapshot()[-1:]
    total0 = spans_total()
    tracing.set_enabled(False)
    try:
        with tracing.activate(ctx):
            sp = tracing.span("classifier.classify", rows=3)
            with sp:
                pass
    finally:
        tracing.set_enabled(None)
    assert isinstance(sp, jax.profiler.TraceAnnotation)  # the hook alone
    assert ctx.span_count() == 0
    assert tracing.FLIGHT.snapshot()[-1:] == flight0
    assert spans_total() == total0


def test_tracing_imports_without_jax_and_keeps_the_no_op_span():
    code = (
        "import sys\n"
        "import swarm_tpu.telemetry.tracing as t\n"
        "import swarm_tpu.server.app\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
        "assert t.span('classifier.classify') is t._NULL_SPAN\n"
    )
    r = subprocess.run([sys.executable, "-c", code], cwd=str(REPO),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


class _BannerServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True


class _SSHBanner(socketserver.BaseRequestHandler):
    def handle(self):
        self.request.sendall(b"SSH-2.0-OpenSSH_9.6\r\n")


def test_worker_service_job_profile_carries_the_spans(tmp_path, monkeypatch):
    """``SWARM_PROFILE_DIR`` on the worker's service path: the job's
    profile holds the census spans, nested in ``execute``. One row is
    one chunk, so the encode runs inline (``engine.encode_wait``) and
    no helper thread encodes."""
    from swarm_tpu.client.cli import JobClient
    from swarm_tpu.config import Config
    from swarm_tpu.server.app import SwarmServer
    from swarm_tpu.worker.runtime import JobProcessor

    banner = _BannerServer(("127.0.0.1", 0), _SSHBanner)
    threading.Thread(target=banner.serve_forever, daemon=True).start()
    modules_dir = tmp_path / "modules"
    modules_dir.mkdir()
    (modules_dir / "svc.json").write_text(json.dumps({
        "backend": "service", "output_format": "nmap",
        "probe": {"connect_timeout_ms": 1500, "read_timeout_ms": 1500},
    }))
    cfg = Config(
        host="127.0.0.1", port=0, api_key="spkey",
        blob_root=str(tmp_path / "blobs"), doc_root=str(tmp_path / "docs"),
        modules_dir=str(modules_dir),
        poll_interval_idle_s=0.05, poll_interval_busy_s=0.01,
    )
    srv = SwarmServer(cfg)
    srv.start_background()
    cfg.server_url = f"http://127.0.0.1:{srv.port}"
    profile_dir = tmp_path / "profile"
    monkeypatch.setenv("SWARM_PROFILE_DIR", str(profile_dir))
    try:
        targets = tmp_path / "targets.txt"
        targets.write_text(f"127.0.0.1:{banner.server_address[1]}\n")
        client = JobClient(cfg.resolve_url(), cfg.api_key)
        code, _ = client.start_scan(str(targets), "svc", 0, 1, scan_id="svcprof_1")
        assert code == 200
        proc = JobProcessor(Config(**{**cfg.__dict__, "max_jobs": 1, "worker_id": "sp-w"}))
        proc.process_jobs()
        assert proc.jobs_done == 1
        (job,) = client.get_statuses()["jobs"].values()
        # one row is one chunk: nothing to walk under a next chunk
        assert job["perf"]["overlapped_batches"] == 0
    finally:
        srv.shutdown()
        banner.shutdown()
    (job_dir,) = profile_dir.iterdir()
    events = _host_events(str(job_dir))
    names = {n for n, _s, _e in events}
    assert set(SPANS) - {"engine.encode_chunk"} <= names
    assert "engine.encode_chunk" not in names
    (_n, x0, x1), = [e for e in events if e[0] == "execute"]
    for name, s, e in events:
        if name in SPANS:
            assert x0 <= s <= e <= x1, name


# ---------------------------------------------------------------------------
# executable names that benchmark/metrics/kernel.match_ms.py matches

TINY_DB = """
Probe TCP NULL q||
rarity 1
ports 1-65535
match ssh m|^SSH-([\\d.]+)-OpenSSH[_-]([^\\s\\r\\n]+)| p/OpenSSH/ v/$2/
softmatch ssh m|^SSH-[\\d.]+-|

Probe TCP GetRequest q|GET / HTTP/1.0\\r\\n\\r\\n|
rarity 1
ports 80
fallback NULL
match http m|^HTTP/1\\.[01] \\d\\d\\d.*Server: nginx/([^\\s\\r\\n]*)|s p/nginx/ v/$1/
"""


def _kernel_reader_pattern() -> re.Pattern:
    """The reader's ``MODULES`` pattern, read from its source (the
    reader imports the benchmark's ``lib``, which is not a package of
    this repo)."""
    text = (REPO / "benchmark" / "metrics" / "kernel.match_ms.py").read_text()
    (pattern,) = re.findall(r'^MODULES = r"(.+)"$', text, re.M)
    return re.compile(pattern)


@pytest.fixture(scope="module")
def lowered_names():
    """module name of every matcher executable, lowered on the CPU for a
    tiny corpus: DeviceDB's phases and fused twin, and the sharded
    steps on a 1x1x1 mesh (the reduction with and without streams)."""
    from swarm_tpu.fingerprints.compile import compile_corpus
    from swarm_tpu.fingerprints.model import Matcher, Operation, Template
    from swarm_tpu.fingerprints.nmap_probes import parse_probes
    from swarm_tpu.ops.encoding import encode_batch
    from swarm_tpu.ops.match import DeviceDB
    from swarm_tpu.parallel.mesh import make_mesh
    from swarm_tpu.parallel.sharded import ShardedMatcher

    def names(text: str) -> list:
        return re.findall(r"module @(\w+)", text)

    probes, _ = parse_probes(TINY_DB)
    clf = ServiceClassifier(probes=probes, mesh=None)
    dev = clf.engine.device
    rows = [Response(host="h", port=22, banner=b"SSH-2.0-OpenSSH_8.9 x")]
    kw = dict(max_body=clf.engine.max_body, max_header=clf.engine.max_header,
              pad_rows_to=8, width_multiple=512)
    b1 = encode_batch(rows, build_all=False, **kw)
    out = {}
    out["phases"] = names(dev.lowered_text(b1.streams, b1.lengths, b1.status))
    fused = DeviceDB(dev.db, compact=False)
    out["fused"] = names(fused.lowered_text(b1.streams, b1.lengths, b1.status))

    mesh = make_mesh((1, 1, 1), devices=jax.devices()[:1])
    b2 = encode_batch(rows, **kw)
    lnames = tuple(sorted(b2.lengths))
    for tag, db in (
        ("regex", dev.db),
        # no regex and no md5: the reduction takes no streams (step_r)
        ("words", compile_corpus([Template(id="w", protocol="network", operations=[
            Operation(matchers=[Matcher(type="word", part="body", words=["OpenSSH"])])
        ])])),
    ):
        sm = ShardedMatcher(db, mesh)
        s_j, l_j, st_j = sm._stage(b2.streams, b2.lengths, b2.status)
        fa = sm._build_phase_a(b2.streams, b2.lengths, False)
        cnt, ovf, _nmax = fa(sm._tab_j, s_j, l_j)
        fbp = sm._build_phase_b_probe(b2.streams, b2.lengths, 8, False)
        vb, ub = fbp(sm._tab_j, sm._rep_j, s_j, l_j, cnt)
        if sm._reduce_needs_streams(b2.streams):
            fr = sm._build_reduce(tuple(sorted(b2.streams)), lnames, True, False, False)
            red = fr.lower(sm._rep_j, s_j, l_j, st_j, vb, ub, ovf)
        else:
            fr = sm._build_reduce(None, lnames, True, False, False)
            red = fr.lower(sm._rep_j, l_j, st_j, vb, ub, ovf)
        ff = sm._build_fused(b2.streams, b2.lengths, True)
        out[f"sharded.{tag}"] = (
            names(fa.lower(sm._tab_j, s_j, l_j).as_text())
            + names(fbp.lower(sm._tab_j, sm._rep_j, s_j, l_j, cnt).as_text())
            + names(red.as_text())
            + names(ff.lower(sm._tab_j, sm._rep_j, s_j, l_j, st_j).as_text())
        )
    return out


@pytest.mark.parametrize("group,expected", [
    ("phases", ["jit_kernel_a", "jit_kernel_b"]),
    ("fused", ["jit_kernel"]),
    ("sharded.regex", ["jit_step_a", "jit_step_bp", "jit_finish", "jit_step"]),
    ("sharded.words", ["jit_step_a", "jit_step_bp", "jit_step_r", "jit_step"]),
])
def test_matcher_executable_names_are_pinned(lowered_names, group, expected):
    assert lowered_names[group] == expected
    pattern = _kernel_reader_pattern()
    for name in expected:
        assert pattern.search(name), name
