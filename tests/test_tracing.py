"""Tracing/profiling: phase timers, perf propagation, scan rollup, and
end-to-end trace-ID correlation.

The reference has zero observability beyond prints + two timestamps
(SURVEY.md §5); this framework reports per-job perf samples through the
same status-update path, aggregates them into the scan rollup, and
correlates every layer's structured events under one client-minted
trace ID (telemetry PR)."""

import json
import time
from pathlib import Path

import pytest

from swarm_tpu.datamodel import Job, JobStatus, rollup_scans
from swarm_tpu.utils.trace import PhaseTimer, maybe_device_profile


def test_phase_timer_accumulates():
    t = PhaseTimer()
    with t.phase("download"):
        time.sleep(0.01)
    with t.phase("download"):
        pass
    with t.phase("execute"):
        pass
    t.count("rows", 100)
    t.count("rows", 28)
    perf = t.perf()
    assert perf["download_s"] >= 0.01
    assert "execute_s" in perf
    assert perf["rows"] == 128


def test_device_profile_disabled_is_free(monkeypatch):
    monkeypatch.delenv("SWARM_PROFILE_DIR", raising=False)
    with maybe_device_profile("job_x") as active:
        assert active is False


def test_device_profile_writes_trace(tmp_path):
    import jax.numpy as jnp

    with maybe_device_profile("job_y", profile_dir=str(tmp_path)) as active:
        assert active is True
        jnp.ones((8, 8)).sum().block_until_ready()
    produced = list((tmp_path / "job_y").rglob("*"))
    assert produced, "profiler produced no files"


def test_job_perf_survives_wire_roundtrip():
    job = Job.create("mod_1700000000", 0, "mod")
    job.perf = {"execute_s": 1.5, "rows": 10}
    wire = job.to_wire()
    back = Job.from_wire(wire)
    assert back.perf == {"execute_s": 1.5, "rows": 10}


def test_rollup_aggregates_perf():
    jobs = {}
    for i in range(3):
        j = Job.create("m_1700000000", i, "m")
        j.status = JobStatus.COMPLETE
        j.completed_at = 1700000100.0 + i
        j.worker_id = "w1"
        j.perf = {"rows": 1000, "device_s": 0.5, "execute_s": 2.0}
        jobs[j.job_id] = j.to_wire()
    # one job without perf (e.g. a reference worker) must not break it
    j = Job.create("m_1700000000", 3, "m")
    j.status = JobStatus.COMPLETE
    jobs[j.job_id] = j.to_wire()

    scans = rollup_scans(jobs)
    assert len(scans) == 1
    s = scans[0]
    assert s["rows_processed"] == 3000
    assert s["device_seconds"] == 1.5
    assert s["execute_seconds"] == 6.0
    assert s["rows_per_second"] == 500.0


def test_rollup_no_perf_stays_none():
    j = Job.create("m_1700000000", 0, "m")
    j.status = JobStatus.COMPLETE
    scans = rollup_scans({j.job_id: j.to_wire()})
    assert scans[0]["rows_processed"] is None
    assert scans[0]["rows_per_second"] is None


def test_trace_id_propagates_end_to_end(tmp_path, monkeypatch):
    """One scan, one trace ID, observed at every layer: the client's
    submit event, the server's job record, the worker's completion
    event — with nonzero phase histograms for the job (the acceptance
    contract of the telemetry PR)."""
    from swarm_tpu.client.cli import JobClient
    from swarm_tpu.config import Config
    from swarm_tpu.server.app import SwarmServer
    from swarm_tpu.telemetry import REGISTRY, subscribe
    from swarm_tpu.worker.runtime import JobProcessor

    modules_dir = tmp_path / "modules"
    modules_dir.mkdir()
    (modules_dir / "echo.json").write_text(
        json.dumps({"command": "cat {input} > {output}"})
    )
    cfg = Config(
        host="127.0.0.1", port=0, api_key="tracekey",
        blob_root=str(tmp_path / "blobs"), doc_root=str(tmp_path / "docs"),
        modules_dir=str(modules_dir),
        poll_interval_idle_s=0.05, poll_interval_busy_s=0.01,
    )
    srv = SwarmServer(cfg)
    srv.start_background()
    cfg.server_url = f"http://127.0.0.1:{srv.port}"

    events = []
    unsubscribe = subscribe(events.append)
    try:
        scan_file = tmp_path / "targets.txt"
        scan_file.write_text("alpha\nbeta\n")
        client = JobClient(cfg.resolve_url(), cfg.api_key)
        code, _text = client.start_scan(str(scan_file), "echo", 0, 0)
        assert code == 200
        trace_id = client.last_trace_id
        assert trace_id

        wcfg = Config(**{**cfg.__dict__, "max_jobs": 1, "worker_id": "trace-w"})
        proc = JobProcessor(wcfg)
        proc.process_jobs()
        assert proc.jobs_done == 1

        # --- the same trace ID at all three layers ---
        by_event = {}
        for e in events:
            by_event.setdefault(e["event"], []).append(e)
        # 1. client submit event
        [submit] = by_event["scan.submit"]
        assert submit["trace_id"] == trace_id
        # 2. server job record (via the status API, like any operator)
        statuses = client.get_statuses()
        [job] = statuses["jobs"].values()
        assert job["trace_id"] == trace_id
        assert job["status"] == "complete"
        # 3. worker completion event, with the perf sample attached
        done = [
            e for e in by_event["job.worker_done"]
            if e["trace_id"] == trace_id and e["status"] == "complete"
        ]
        assert done and done[0]["job_id"] == job["job_id"]
        assert done[0]["perf"]["download_s"] >= 0
        # server-side terminal event carries it too
        assert any(
            e["trace_id"] == trace_id and e["status"] == "complete"
            for e in by_event["job.terminal"]
        )
        # queue-side lifecycle events under the same trace
        assert any(e["trace_id"] == trace_id for e in by_event["job.queued"])
        assert any(e["trace_id"] == trace_id for e in by_event["job.dispatch"])

        # --- nonzero phase histograms for that job on /metrics ---
        import requests as _requests

        text = _requests.get(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=10
        ).text
        from swarm_tpu.telemetry.metrics import parse_exposition

        samples = {
            (name, tuple(sorted(labels.items()))): value
            for name, labels, value in parse_exposition(text)
        }
        for family in ("swarm_worker_phase_seconds", "swarm_job_phase_seconds"):
            for phase in ("download", "execute", "upload"):
                key = (f"{family}_count", (("phase", phase),))
                assert samples.get(key, 0) >= 1, (family, phase)
        # worker outcome counter saw the completion
        assert (
            samples[("swarm_worker_jobs_total", (("outcome", "complete"),))] >= 1
        )
        # registry snapshot agrees (what `swarm metrics` renders)
        snap = REGISTRY.snapshot()
        assert snap["swarm_worker_phase_seconds"]["type"] == "histogram"
    finally:
        unsubscribe()
        srv.shutdown()


def test_compilation_cache_enable(tmp_path, monkeypatch):
    import jax

    from swarm_tpu.utils import xlacache

    monkeypatch.setattr(xlacache, "_active_dir", None)
    d = xlacache.enable_compilation_cache(str(tmp_path / "xc"))
    assert d == str(tmp_path / "xc")
    assert jax.config.jax_compilation_cache_dir == d
    # idempotent: second call with another dir keeps (and reports) the
    # original binding
    d2 = xlacache.enable_compilation_cache(str(tmp_path / "other"))
    assert jax.config.jax_compilation_cache_dir == d
    assert d2 == d
    assert not (tmp_path / "other").exists()
    # empty string disables; an uncreatable dir degrades to no-cache
    monkeypatch.setattr(xlacache, "_active_dir", None)
    assert xlacache.enable_compilation_cache("") == ""
    blocker = tmp_path / "blocker"
    blocker.write_text("")  # a file where a dir is needed
    assert xlacache.enable_compilation_cache(str(blocker / "sub")) == ""


# The compile-cache rule (utils/xlacache.py): JAX_COMPILATION_CACHE_DIR,
# when set, is the cache; otherwise one fixed gitignored directory in
# the checkout. Fresh processes: JAX reads the variable at import.
_CACHE_PROBE = (
    "import sys, jax; sys.path.insert(0, {repo!r}); {setup}; "
    "print(jax.config.jax_compilation_cache_dir)"
)


def _cache_dir_in_child(setup: str, env_dir=None) -> str:
    import os
    import subprocess
    import sys

    repo = str(Path(__file__).resolve().parent.parent)
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(repo=repo, setup=setup)],
        cwd=repo, env=env, capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.strip().splitlines()[-1]


@pytest.mark.parametrize("setup", [
    "from swarm_tpu.utils.xlacache import enable_compilation_cache; "
    "enable_compilation_cache()",
    "import bench; bench.resolve_device()",
])
def test_compilation_cache_env_var_wins(tmp_path, setup):
    """bench and the worker (both enable_compilation_cache) use the
    environment's directory and set no other."""
    want = str(tmp_path / "jcc")
    assert _cache_dir_in_child(setup, env_dir=want) == want


def test_compilation_cache_default_is_fixed_dir_in_checkout():
    from swarm_tpu.utils import xlacache

    got = _cache_dir_in_child(
        "from swarm_tpu.utils.xlacache import enable_compilation_cache; "
        "enable_compilation_cache()"
    )
    repo = Path(__file__).resolve().parent.parent
    assert got == str(xlacache.DEFAULT_CACHE_DIR) == str(repo / ".xla_cache")
    assert ".xla_cache/" in (repo / ".gitignore").read_text().splitlines()


def test_only_xlacache_sets_a_cache_directory():
    """No second name, no second setter outside the tests."""
    repo = Path(__file__).resolve().parent.parent
    files = [repo / "bench.py", repo / "chip_smoke.py", repo / "__graft_entry__.py"]
    files += sorted((repo / "swarm_tpu").rglob("*.py"))
    files += sorted((repo / "tools").rglob("*.py"))
    setters = [
        f.relative_to(repo).as_posix()
        for f in files
        if any(k in f.read_text() for k in (
            '"jax_compilation_cache_dir"', "set_cache_dir(", "SWARM_XLA_CACHE_DIR"
        ))
    ]
    assert setters == ["swarm_tpu/utils/xlacache.py"]
