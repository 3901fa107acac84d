"""Worker poll loop — reference ``worker/worker.py`` rebuilt.

Same observable protocol: poll ``/get-job``, walk the job through
``starting → downloading → executing → uploading → complete`` (or
``cmd failed`` / ``upload failed - *``) via ``/update-job``, with the
reference's cadence (0.8 s between jobs, 10 s when idle). Differences:

- chunk data moves over the server HTTP API by default (the reference
  requires AWS credentials on every worker); direct S3 remains possible
  via a custom transport.
- the ``tpu`` module backend executes the chunk as a device batch with
  the in-process MatchEngine instead of a subprocess.
- ``max_jobs`` actually works (the reference parsed and ignored it, and
  its post-loop thread spawn was dead code — SURVEY.md §2.1 defects).
"""

from __future__ import annotations

import json
import re
import subprocess
import tempfile
import time
from pathlib import Path
from typing import Optional

import requests

from swarm_tpu.config import Config
from swarm_tpu.datamodel import SCAN_ID_RE, JobStatus
from swarm_tpu.resilience.faults import (
    FaultInjected,
    fault_point,
    install_plan,
)
from swarm_tpu.resilience.heartbeat import LeaseHeartbeat
from swarm_tpu.resilience.spool import OutputSpool
from swarm_tpu.resilience.transport import (
    RetryingServerClient,
    TransportError,
)
from swarm_tpu.telemetry import REGISTRY, emit_event
from swarm_tpu.telemetry import tracing
from swarm_tpu.telemetry.fleet_export import (
    WORKER_DRAIN,
    WORKER_DRAIN_SECONDS,
)
from swarm_tpu.utils.trace import PhaseTimer, maybe_device_profile
from swarm_tpu.worker.modules import (
    ModuleRegistry,
    ModuleSpec,
    format_match_line,
    parse_response_line,
)

# Worker-side metric families (exposed on /metrics when the worker runs
# in-process with the server; remote workers' phase timings additionally
# reach the server via the job perf fields → swarm_job_phase_seconds)
_PHASE_SECONDS = REGISTRY.histogram(
    "swarm_worker_phase_seconds",
    "Per-phase job pipeline seconds measured on the worker",
    ("phase",),
)
_JOBS_PROCESSED = REGISTRY.counter(
    "swarm_worker_jobs_total",
    "Jobs processed by this worker, by outcome",
    ("outcome",),
)
_LAST_POLL = REGISTRY.gauge(
    "swarm_worker_last_poll_timestamp",
    "Unix time of this worker's most recent /get-job poll (heartbeat)",
)
_ROWS_PER_SEC = REGISTRY.gauge(
    "swarm_worker_rows_per_second",
    "Rows/sec of the most recently completed device job",
)
_ROWS_TOTAL = REGISTRY.counter(
    "swarm_worker_rows_total", "Device-engine rows processed by this worker"
)
_POLL_ERRORS = REGISTRY.counter(
    "swarm_worker_poll_errors_total",
    "Polls that failed with a transport error (server down ≠ idle queue)",
)
_SERVER_GENERATION = REGISTRY.gauge(
    "swarm_worker_server_generation",
    "Control-plane generation last observed on a successful poll",
)
_SERVER_RESTARTS = REGISTRY.counter(
    "swarm_worker_server_restarts_total",
    "Control-plane generation changes observed by this worker",
)

#: span batches up to this size ride the completed-job ``perf`` field;
#: larger batches (long scans) ship out of band via ``POST /spans``
_SPAN_INLINE_MAX = 256


class ServerClient:
    """HTTP client for the worker-facing server API.

    Failure typing (docs/RESILIENCE.md): connection failures and 5xx
    responses raise :class:`TransportError` so callers can tell "server
    down" from "queue empty" / contract rejections — previously a dead
    server looked exactly like an idle queue. Each operation declares a
    ``transport.*`` fault point for the injection harness.
    """

    def __init__(self, server_url: str, api_key: str, timeout: float = 30.0):
        self.base = server_url.rstrip("/")
        self.timeout = timeout
        self.session = requests.Session()
        self.session.headers["Authorization"] = f"Bearer {api_key}"
        #: control-plane generation from the most recent /get-job
        #: answer's X-Swarm-Generation header (None until the first
        #: successful poll, or against a pre-journal server). The poll
        #: loop watches it to detect server restarts
        #: (docs/DURABILITY.md).
        self.last_server_generation: Optional[int] = None
        #: drain order from the most recent /get-job answer's
        #: X-Swarm-Drain header (docs/RESILIENCE.md §Preemption): the
        #: reason string ("drain"/"preempted"/...) or None. The poll
        #: loop routes it into JobProcessor.request_drain.
        self.last_drain_reason: Optional[str] = None

    def _request(self, op: str, method: str, path: str, detail=None, **kw):
        fault_point(f"transport.{op}", detail=detail, exc=TransportError)
        try:
            resp = self.session.request(
                method, f"{self.base}{path}", timeout=self.timeout, **kw
            )
        except requests.RequestException as e:
            raise TransportError(f"{op}: {e}") from e
        if resp.status_code >= 500:
            raise TransportError(f"{op}: server error {resp.status_code}")
        return resp

    def get_job(self, worker_id: str) -> Optional[dict]:
        resp = self._request(
            "get_job", "GET", "/get-job", params={"worker_id": worker_id}
        )
        gen = resp.headers.get("X-Swarm-Generation")
        if gen is not None:
            try:
                self.last_server_generation = int(gen)
            except ValueError:
                pass
        self.last_drain_reason = resp.headers.get("X-Swarm-Drain")
        return resp.json() if resp.status_code == 200 else None

    def update_job(self, job_id: str, changes: dict, worker_id: Optional[str] = None) -> bool:
        if worker_id is not None:
            changes = {**changes, "worker_id": worker_id}  # fencing token
        resp = self._request(
            "update_job", "POST", f"/update-job/{job_id}", json=changes
        )
        return resp.status_code == 200

    def get_input_chunk(self, scan_id: str, chunk_index: int) -> Optional[bytes]:
        resp = self._request(
            "get_chunk", "GET", f"/get-input-chunk/{scan_id}/{chunk_index}"
        )
        return resp.content if resp.status_code == 200 else None

    def put_output_chunk(self, scan_id: str, chunk_index: int, data: bytes) -> bool:
        resp = self._request(
            "put_chunk", "POST", f"/put-output-chunk/{scan_id}/{chunk_index}",
            detail=f"{scan_id}_{chunk_index}", data=data,
        )
        return resp.status_code == 200

    def post_spans(self, scan_id: str, spans: list) -> bool:
        """Ship a span batch out of band (docs/OBSERVABILITY.md
        §Tracing) — used when an attempt's batch is too large to ride
        the completed-job perf field, or the attempt failed and there
        is no perf field to ride."""
        resp = self._request(
            "post_spans", "POST", "/spans", detail=scan_id,
            json={"scan_id": scan_id, "spans": spans},
        )
        return resp.status_code == 200

    def renew_lease(
        self, job_id: str, worker_id: str, saturation: Optional[float] = None
    ) -> bool:
        """Heartbeat one lease; False = the lease is no longer ours.
        ``saturation`` (0..1, optional) reports the scheduler's
        in-flight saturation so the gateway's admission pressure rises
        before the queue does (docs/GATEWAY.md)."""
        body = {"worker_id": worker_id}
        if saturation is not None:
            body["saturation"] = saturation
        resp = self._request(
            "renew_lease", "POST", f"/renew-lease/{job_id}",
            detail=job_id, json=body,
        )
        return resp.status_code == 200

    def deregister(self, worker_id: str) -> bool:
        """Tell the server this worker is exiting NOW: its leases hand
        back immediately and its saturation report is dropped — no
        grace-window wait (docs/RESILIENCE.md §Preemption)."""
        resp = self._request(
            "deregister", "POST", "/deregister",
            detail=worker_id, json={"worker_id": worker_id},
        )
        return resp.status_code == 200


class JobProcessor:
    def __init__(
        self,
        cfg: Config,
        client: Optional[ServerClient] = None,
        registry: Optional[ModuleRegistry] = None,
        work_dir: Optional[str] = None,
    ):
        self.cfg = cfg
        if cfg.fault_plan:
            install_plan(cfg.fault_plan)  # deterministic chaos (tests/soak)
        if client is None:
            # production default: retrying transport (jittered backoff +
            # per-operation breakers) over the raw HTTP client
            client = RetryingServerClient(
                ServerClient(cfg.resolve_url(), cfg.api_key),
                retries=cfg.transport_retries,
                backoff_s=cfg.transport_backoff_s,
                backoff_max_s=cfg.transport_backoff_max_s,
                breaker_threshold=cfg.transport_breaker_threshold,
                breaker_cooldown_s=cfg.transport_breaker_cooldown_s,
            )
        self.client = client
        self.registry = registry or ModuleRegistry(cfg.modules_dir)
        self.work_dir = Path(work_dir or tempfile.mkdtemp(prefix="swarm_worker_"))
        self.work_dir.mkdir(parents=True, exist_ok=True)
        self.spool = OutputSpool(cfg.spool_dir or self.work_dir / "spool")
        self._engines: dict[str, object] = {}  # templates_dir -> MatchEngine
        self._scan_perf_extra: dict = {}  # per-job scan counters (perf fields)
        self.jobs_done = 0
        #: cooperative shutdown for threaded workers (chaos soak test)
        self.stop_requested = False
        #: graceful-drain order (docs/RESILIENCE.md §Preemption): the
        #: reason string, set by SIGTERM, the X-Swarm-Drain poll
        #: header, or tests. The poll loop finishes its current lease,
        #: then runs :meth:`drain` and exits.
        self.drain_requested: Optional[str] = None
        #: outcome of the drain that ended process_jobs (None until
        #: then): "completed" | "spooled" | "idle" | "aborted"
        self.drain_outcome: Optional[str] = None
        #: True while a leased chunk is being processed — decides the
        #: drain outcome ("completed" vs "idle")
        self._job_in_flight = False
        #: True when the drain order arrived mid-chunk: the lease was
        #: finished first, so the drain outcome reports "completed"
        self._drained_mid_job = False
        self._last_heartbeat: Optional[LeaseHeartbeat] = None
        #: most recently observed scheduler in-flight saturation (0..1;
        #: None until a pipelined engine reports) — heartbeats carry it
        #: to the gateway's admission pressure signal
        self._last_saturation: Optional[float] = None
        #: control-plane generation seen on the last successful poll
        #: (None until the first; docs/DURABILITY.md)
        self._seen_generation: Optional[int] = None

    # ------------------------------------------------------------------
    def prewarm(self, module_name: str) -> bool:
        """Build a module's engine/scanner before the first job: an
        empty-input execution exercises exactly the construction path
        (corpus load + device compile), so with the persistent XLA
        cache the first real job runs at steady-state latency."""
        try:
            module = self.registry.load(module_name)
            dispatch = {
                "tpu": self._execute_tpu,
                "probe": self._execute_probe,
                "service": self._execute_service,
                "jarm": self._execute_jarm,
                "active": self._execute_active,
                "file": self._execute_file,
                "ssl": self._execute_ssl,
            }.get(module.backend)
            if dispatch is None:
                return False  # command modules have nothing to warm
            dispatch(module, b"")
            return True
        except Exception as e:
            print(f"prewarm {module_name} failed: {e}")
            return False

    # ------------------------------------------------------------------
    def request_drain(self, reason: str) -> None:
        """Ask the poll loop to drain: finish the current lease, flush
        or spool, deregister, exit. Callable from a signal handler or
        another thread — it only sets a flag. First reason wins."""
        if self.drain_requested is None:
            self.drain_requested = reason
            if self._job_in_flight:
                self._drained_mid_job = True

    def process_jobs(self) -> None:
        """The infinite poll loop (reference worker.py:113-126)."""
        while not self.stop_requested:
            if self.drain_requested is not None:
                # the current lease (if any) finished on the previous
                # iteration — process_chunk is synchronous, so reaching
                # this check means nothing is in flight
                self.drain(self.drain_requested)
                return
            try:
                _LAST_POLL.set(time.time())
                job = self.client.get_job(self.cfg.worker_id)
            except TransportError as e:
                # server down is NOT "queue empty": count it distinctly
                # (the retry layer already burned its backoff budget)
                _POLL_ERRORS.inc()
                print(f"server unreachable: {e}")
                time.sleep(self.cfg.poll_interval_idle_s)
                continue
            except Exception as e:
                print(f"error getting job: {e}")
                time.sleep(self.cfg.poll_interval_idle_s)
                continue
            # a successful poll re-registered this worker's WorkerInfo
            # server-side (next_job saves it on every poll); what's
            # left is OUR side of a control-plane restart
            self._note_server_generation()
            # drain order riding the poll answer (docs/RESILIENCE.md
            # §Preemption): the server stopped offering us jobs — loop
            # back to the drain check instead of sleeping out an idle
            # interval first
            drain = getattr(self.client, "last_drain_reason", None)
            if drain:
                self.request_drain(drain)
                continue
            # the poll proved the server reachable: flush any finished
            # chunks spooled while it was down (idempotent via fencing)
            self._replay_spool()
            try:
                if job:
                    self._job_in_flight = True
                    try:
                        self.process_chunk(job)
                    finally:
                        self._job_in_flight = False
                    # max_jobs bounds *attempts*: a failing job must not
                    # leave a --max-jobs worker polling forever
                    self.jobs_done += 1
                    if self.cfg.max_jobs and self.jobs_done >= self.cfg.max_jobs:
                        return
                else:
                    time.sleep(self.cfg.poll_interval_idle_s)
            except Exception as e:
                print(f"error processing job: {e}")
                time.sleep(self.cfg.poll_interval_idle_s)
            time.sleep(self.cfg.poll_interval_busy_s)

    def _note_server_generation(self) -> None:
        """Detect a control-plane restart (docs/DURABILITY.md): the
        X-Swarm-Generation header on the poll that just succeeded. On a
        change, this worker's WorkerInfo/status is ALREADY re-registered
        (the poll itself wrote it — /get-statuses is never stale past
        the first post-restart poll); locally we close the transport
        breakers the dead process earned, so the heartbeat and upload
        paths resume cleanly instead of waiting out a stale cooldown."""
        gen = getattr(self.client, "last_server_generation", None)
        if gen is None or gen == self._seen_generation:
            return
        prior = self._seen_generation
        self._seen_generation = gen
        _SERVER_GENERATION.set(gen)
        if prior is None:
            return  # first contact, not a restart
        _SERVER_RESTARTS.inc()
        breakers = getattr(self.client, "breakers", None)
        if breakers is not None:
            breakers.reset_all()
        emit_event(
            "worker.server_restarted",
            worker_id=self.cfg.worker_id,
            generation=gen,
            prior_generation=prior,
        )
        print(
            f"server restarted (generation {prior} -> {gen}); "
            "re-registered and reset transport breakers"
        )

    def _replay_spool(self) -> None:
        if not len(self.spool):
            return
        try:
            cleared = self.spool.replay(self.client)
        except Exception as e:
            print(f"spool replay failed: {e}")
            return
        if cleared:
            print(f"spool: replayed {cleared} finished chunk(s)")

    def drain(self, reason: str) -> str:
        """The graceful exit sequence (docs/RESILIENCE.md §Preemption).
        The current lease already finished — process_jobs only calls
        this between chunks — so what's left is flushing any spooled
        chunks while the server is still reachable, then deregistering
        (the server hands back leases and drops our saturation report
        immediately, no grace-window wait). The ``worker.drain`` fault
        point ABORTS the sequence when armed: the kill-after-grace
        case, where the node dies mid-drain and recovery rides the
        on-disk spool + fencing instead of this happy path."""
        t0 = time.monotonic()
        outcome = "completed" if self._drained_mid_job else "idle"
        try:
            fault_point("worker.drain", detail=self.cfg.worker_id)
            self._replay_spool()
            if len(self.spool):
                # replay couldn't clear everything (server gone again):
                # the chunks stay spooled on disk for the next process
                outcome = "spooled"
            try:
                self.client.deregister(self.cfg.worker_id)
            except Exception as e:
                print(f"drain: deregister undeliverable: {e}")
        except FaultInjected:
            # injected mid-drain death: no deregister, no replay — the
            # server's lease expiry and the on-disk spool own recovery
            outcome = "aborted"
        self.drain_outcome = outcome
        elapsed = time.monotonic() - t0
        WORKER_DRAIN.labels(outcome=outcome).inc()
        WORKER_DRAIN_SECONDS.labels().observe(elapsed)
        emit_event(
            "worker.stopped",
            worker_id=self.cfg.worker_id,
            reason=reason,
            outcome=outcome,
            jobs_done=self.jobs_done,
            drain_seconds=round(elapsed, 4),
        )
        print(f"worker drained ({reason}): {outcome} in {elapsed:.2f}s")
        return outcome

    # ------------------------------------------------------------------
    def process_chunk(self, job: dict) -> None:
        job_id = job.get("job_id") or f"{job['scan_id']}_{job['chunk_index']}"
        scan_id, chunk_index = job["scan_id"], int(job["chunk_index"])
        trace_id = job.get("trace_id")
        # defense in depth: the server validates scan ids, but these flow
        # into filesystem paths and {input}/{output} command substitution
        if not SCAN_ID_RE.match(str(scan_id)):
            self.client.update_job(job_id, {"status": JobStatus.CMD_FAILED})
            _JOBS_PROCESSED.labels(outcome=JobStatus.CMD_FAILED).inc()
            return
        timer = PhaseTimer()
        # per-attempt span collector (None when tracing is off or the
        # job carries no trace id — the completed-job wire payload is
        # then byte-identical to the untraced build)
        ctx = tracing.attempt_context(
            trace_id,
            job_id=job_id,
            attempt=job.get("attempts"),
            worker_id=self.cfg.worker_id,
            module=job.get("module"),
        )

        def _ship_spans(extra):
            """Close the attempt root; inline the batch on the perf
            field when small, else POST /spans (also the only path for
            failed attempts, which carry no perf)."""
            spans = ctx.finish()
            if not spans:
                return extra
            perf = extra.get("perf")
            if isinstance(perf, dict) and len(spans) <= _SPAN_INLINE_MAX:
                return {**extra, "perf": {**perf, "spans": spans}}
            try:
                self.client.post_spans(scan_id, spans)
            except Exception as e:
                print(f"span batch undeliverable: {e}")
            return extra

        def update(status, **extra):
            if status in JobStatus.TERMINAL and ctx is not None:
                extra = _ship_spans(extra)
            try:
                ok = self.client.update_job(
                    job_id,
                    {"status": status, **extra},
                    worker_id=self.cfg.worker_id,
                )
            except TransportError as e:
                # server unreachable mid-job: a lost phase update is
                # harmless (the lease covers us); a lost COMPLETE is
                # handled by the caller via the spool. None ≠ False —
                # False means the server actively rejected (fencing).
                print(f"update {status!r} undeliverable: {e}")
                ok = None
            if status not in JobStatus.TERMINAL:
                emit_event(
                    "job.phase",
                    trace_id=trace_id,
                    job_id=job_id,
                    worker_id=self.cfg.worker_id,
                    phase=status,
                )
            if status in JobStatus.TERMINAL:
                # one observation per phase per job: the job-level
                # latency distributions /metrics serves
                seconds, _counters = timer.snapshot()
                for phase, secs in seconds.items():
                    _PHASE_SECONDS.labels(phase=phase).observe(secs)
                _JOBS_PROCESSED.labels(outcome=status).inc()
                emit_event(
                    "job.worker_done",
                    trace_id=trace_id,
                    job_id=job_id,
                    worker_id=self.cfg.worker_id,
                    status=status,
                    perf=extra.get("perf"),
                )
            return ok

        self._engine_stats_mark = None
        self._scan_perf_extra = {}
        emit_event(
            "job.start",
            trace_id=trace_id,
            job_id=job_id,
            worker_id=self.cfg.worker_id,
            module=job.get("module"),
        )

        # lease heartbeat: renew from a background ticker while the
        # chunk runs so a long batch never races the server's
        # _requeue_expired into a double execution (docs/RESILIENCE.md)
        hb = LeaseHeartbeat(
            self.client,
            job_id,
            self.cfg.worker_id,
            self.cfg.heartbeat_interval_s or self.cfg.lease_seconds / 3.0,
            saturation_fn=lambda: self._last_saturation,
        )
        self._last_heartbeat = hb
        hb.start()
        try:
            with tracing.activate(ctx):
                self._run_chunk(
                    job, job_id, scan_id, chunk_index, timer, update
                )
        finally:
            hb.stop()

    def _run_chunk(
        self, job: dict, job_id: str, scan_id: str, chunk_index: int,
        timer: PhaseTimer, update,
    ) -> None:
        """Download → execute → upload under an active heartbeat."""
        update(JobStatus.STARTING)
        update(JobStatus.DOWNLOADING)
        with timer.phase("download"), tracing.span("download"):
            data = self.client.get_input_chunk(scan_id, chunk_index)
        if data is None:
            update(JobStatus.CMD_FAILED)
            return

        update(JobStatus.EXECUTING)
        try:
            module = self.registry.load(job["module"])
        except (OSError, ValueError) as e:
            print(f"module load failed: {e}")
            update(JobStatus.CMD_FAILED)
            return

        # the engine's own spans (classifier.*, engine.*, device.*) nest
        # under "execute", and reach the job's profile when one records
        try:
            with (
                timer.phase("execute"),
                maybe_device_profile(job_id),
                tracing.span("execute", module=job.get("module")),
            ):
                # chaos lever: fail (or delay) this chunk's execution —
                # detail carries the job id so a plan can poison one job
                fault_point("executor.run", detail=job_id)
                if module.backend == "tpu":
                    output = self._execute_tpu(
                        module, data, qos=job.get("qos")
                    )
                elif module.backend == "probe":
                    output = self._execute_probe(module, data)
                elif module.backend == "service":
                    output = self._execute_service(module, data)
                elif module.backend == "jarm":
                    output = self._execute_jarm(module, data)
                elif module.backend == "active":
                    output = self._execute_active(
                        module, data, chunk_index=chunk_index
                    )
                elif module.backend == "file":
                    output = self._execute_file(module, data)
                elif module.backend == "ssl":
                    output = self._execute_ssl(module, data)
                else:
                    output = self._execute_command(
                        module, scan_id, chunk_index, data
                    )
        except Exception as e:
            print(f"execution failed: {e}")
            update(JobStatus.CMD_FAILED)
            return
        if output is None:
            update(JobStatus.CMD_FAILED)
            return

        update(JobStatus.UPLOADING)
        unreachable = False
        with timer.phase("upload"), tracing.span("upload"):
            try:
                ok = self.client.put_output_chunk(scan_id, chunk_index, output)
            except TransportError:
                # server unreachable after the retry budget: the chunk's
                # compute is paid for — never lose it (spool below)
                ok = False
                unreachable = True
            except requests.RequestException:
                ok = False
        if ok or unreachable:
            perf = timer.perf()
            perf["input_bytes"] = len(data)
            perf["output_bytes"] = len(output)
            perf.update(self._engine_perf_delta())
            perf.update(self._scan_perf_extra)
            # this worker's non-closed breakers (transport + device)
            # ride the perf fields to the server, so /get-statuses
            # shows remote-fleet degradation the server-side /healthz
            # breaker board (process-local) cannot see
            from swarm_tpu.resilience.breaker import breaker_states

            open_breakers = {
                k: v for k, v in breaker_states().items() if v != "closed"
            }
            if open_breakers:
                perf["breakers_open"] = open_breakers
            rows = perf.get("rows")
            exec_s = perf.get("execute_s")
            import math

            if (
                isinstance(rows, (int, float))
                and math.isfinite(rows)
                and rows > 0
            ):
                _ROWS_TOTAL.inc(rows)
                if exec_s and math.isfinite(exec_s):
                    _ROWS_PER_SEC.set(rows / exec_s)
            done = True
            if ok:
                done = update(JobStatus.COMPLETE, perf=perf)
            if unreachable or done is None:
                # finished work outlives the outage: spool the output +
                # completion and replay on reconnect — idempotent, and
                # the fencing token discards it if the job was re-leased
                self._spool_finished(
                    job_id, scan_id, chunk_index, output, perf
                )
        else:
            update(JobStatus.UPLOAD_FAILED_UNKNOWN)

    def _spool_finished(
        self, job_id: str, scan_id: str, chunk_index: int,
        output: bytes, perf: dict,
    ) -> None:
        self.spool.put(
            job_id, scan_id, chunk_index, self.cfg.worker_id, output,
            perf=perf,
        )
        _JOBS_PROCESSED.labels(outcome="spooled").inc()
        emit_event(
            "job.spooled",
            job_id=job_id,
            worker_id=self.cfg.worker_id,
            scan_id=scan_id,
            chunk_index=chunk_index,
        )
        print(f"server unreachable; spooled finished chunk {job_id}")

    def _mark_engine_stats(self, engine) -> None:
        """Snapshot the cumulative engine counters at job start so
        :meth:`_engine_perf_delta` can report this job's delta."""
        ds = engine.stats
        self._engine_stats_mark = (
            engine,
            ds.rows,
            ds.device_seconds,
            ds.host_confirm_seconds,
            ds.device_compile_seconds,
            ds.device_faults,
            ds.degraded_batches,
            ds.overlapped_batches,
        )

    def _engine_perf_delta(self) -> dict:
        """Device-engine stats accumulated during this job (tpu backend
        caches engines across jobs, so report the delta since job start)."""
        mark = self._engine_stats_mark
        if mark is None:
            return {}
        engine, rows0, dev0, confirm0, comp0, faults0, degr0, ovl0 = mark
        ds = engine.stats
        out = {
            "rows": ds.rows - rows0,
            "device_s": round(ds.device_seconds - dev0, 6),
            "host_confirm_s": round(ds.host_confirm_seconds - confirm0, 6),
            "compile_s": round(ds.device_compile_seconds - comp0, 6),
            # device-degraded mode (docs/RESILIENCE.md): batches this
            # job served from the CPU oracle instead of the device
            "device_faults": ds.device_faults - faults0,
            "degraded_batches": ds.degraded_batches - degr0,
            # chunks whose walk ran under the next chunk's kernels
            "overlapped_batches": ds.overlapped_batches - ovl0,
            **device_perf(),
        }
        mesh = getattr(engine, "mesh", None)
        if mesh is not None:
            out["mesh"] = "x".join(
                f"{ax}{int(mesh.shape[ax])}" for ax in mesh.axis_names
            )
        # scheduler mode + feed health ride the job perf fields so
        # operators can see the A/B state per job (/get-statuses)
        out["pipeline"] = getattr(engine, "pipeline", "off")
        sched = getattr(engine, "_sched", None)
        if sched is not None:
            snap = sched.stats.snapshot()
            out["sched"] = snap
            # stall/wall = the fraction of scheduler wall time the
            # submit thread waited on a FULL in-flight window — the
            # honest "accelerator is saturated" scalar the gateway's
            # admission pressure consumes (perf here, heartbeats live)
            wall = snap.get("wall_seconds") or 0.0
            if wall > 0:
                saturation = min(1.0, snap.get("stall_seconds", 0.0) / wall)
                out["inflight_saturation"] = round(saturation, 4)
                self._last_saturation = saturation
        return out

    # ------------------------------------------------------------------
    def _execute_active(
        self, module: ModuleSpec, data: bytes, chunk_index: int = 0
    ) -> bytes:
        """Active template-request scanning (nuclei's execution mode):
        each template's own requests are issued per target, responses
        device-matched, hits attributed per request (worker/active.py)."""
        from swarm_tpu.fingerprints.model import Response
        from swarm_tpu.worker import formats
        from swarm_tpu.worker.active import ActiveScanner

        if not module.templates_dir:
            raise ValueError(f"active module {module.name} missing 'templates'")
        engine = self._engine_for(module.templates_dir)
        self._mark_engine_stats(engine)
        # keyed by probe spec + vars too: two modules sharing a
        # templates dir but differing in ports/timeouts/concurrency or
        # operator-supplied template vars must not alias
        user_vars = module.raw.get("vars") or None
        probe_key = json.dumps(
            [module.probe or {}, user_vars], sort_keys=True
        )
        key = f"active::{module.templates_dir}::{probe_key}"
        scanner = self._engines.get(key)
        if scanner is None:
            scanner = ActiveScanner(engine, module.probe, user_vars=user_vars)
            self._engines[key] = scanner
        target_lines = data.decode("utf-8", "surrogateescape").splitlines()
        hits, stats = scanner.run(target_lines)
        sev, proto = formats.severity_index(engine.templates)
        lines = []
        for h in hits:
            p = proto.get(h.template_id, "http")
            target = (
                formats.url_of(Response(host=h.host, port=h.port, tls=h.tls))
                + h.path
                if p == "http"
                else f"{h.host}:{h.port}"
            )
            extra = " [" + ",".join(h.extractions) + "]" if h.extractions else ""
            lines.append(
                f"[{h.template_id}] [{p}] [{sev.get(h.template_id, 'info')}] "
                f"{target}{extra}"
            )
        print(
            f"active scan: {stats['rows_probed']} requests over "
            f"{stats.get('live_targets', 0)} live targets, {len(lines)} hits"
        )
        # operator-visible scan counters in the job's perf fields
        # (/get-statuses -> swarm jobs): targets, probe volume, and OOB
        # activity so blind-class findings are explainable
        self._scan_perf_extra = {
            k: stats[k]
            for k in (
                "targets", "live_targets", "rows_probed",
                "oob_probes", "oob_interactions", "session_hits",
                "workflow_hits",
            )
            if k in stats
        }
        self._scan_perf_extra["hits"] = len(lines)
        # Scope honesty, once per scan (chunk 0 only — these are
        # per-scan facts; repeating them in every chunk would flood a
        # sharded scan's merged /raw with duplicates):
        if chunk_index == 0:
            # interactsh-referencing templates cannot fully evaluate
            # without an interaction server; their non-OOB requests (if
            # any) still run, so the marker scopes itself to the OOB part
            for tid in scanner.oob_limited:
                lines.append(
                    f"# [{tid}] [oob-skipped] interactsh-dependent "
                    "checks not evaluated (no out-of-band interaction "
                    "server)"
                )
            # headless templates outside the browserless JS-free
            # subset (worker/headless.py) need a real browser engine
            # (JS runtime, renderer, or selectors we don't emulate)
            for tid in scanner.plan.skipped.get("protocol-headless", []):
                lines.append(
                    f"# [{tid}] [headless-skipped] requires a browser "
                    "engine; not evaluated"
                )
            # compact coverage summary: one line per remaining skip
            # class (file/ssl run under their own modules; sessions
            # execute the chain classes, so those aren't listed here)
            for reason, ids in sorted(stats["skipped_templates"].items()):
                if reason.startswith("protocol-") or reason in (
                    "oob-interactsh",
                ):
                    continue  # surfaced above / handled elsewhere
                lines.append(
                    f"# [coverage] {reason}: {ids} templates not executed"
                )
        return ("\n".join(lines) + "\n").encode() if lines else b""

    # ------------------------------------------------------------------
    def _execute_file(self, module: ModuleSpec, data: bytes) -> bytes:
        """File-template scanning: input chunk lines are file/directory
        paths, matched against the corpus's ``file``-protocol templates
        (worker/filescan.py) in one exact device batch."""
        from swarm_tpu.fingerprints import load_corpus
        from swarm_tpu.worker.filescan import FileScanner, format_findings

        if not module.templates_dir:
            raise ValueError(f"file module {module.name} missing 'templates'")
        scan_root = module.raw.get("scan_root") or None
        key = f"file::{module.templates_dir}::{scan_root}"
        scanner = self._engines.get(key)
        if scanner is None:
            templates, _errors = load_corpus(module.templates_dir)
            scanner = FileScanner(templates, scan_root=scan_root)
            self._engines[key] = scanner
        findings, stats = scanner.scan_paths(
            data.decode("utf-8", "surrogateescape").splitlines()
        )
        print(
            f"file scan: {stats['files_scanned']} files x "
            f"{stats['templates']} templates, {stats['hits']} hits"
        )
        return format_findings(findings)

    # ------------------------------------------------------------------
    def _execute_ssl(self, module: ModuleSpec, data: bytes) -> bytes:
        """ssl-protocol template execution: version-pinned handshakes +
        matchers over the session/cert document (worker/sslscan.py)."""
        from swarm_tpu.fingerprints import load_corpus
        from swarm_tpu.worker.sslscan import SslScanner, format_findings

        if not module.templates_dir:
            raise ValueError(f"ssl module {module.name} missing 'templates'")
        probe = module.probe or {}
        key = (
            f"ssl::{module.templates_dir}::"
            f"{json.dumps(probe, sort_keys=True)}"
        )
        scanner = self._engines.get(key)
        if scanner is None:
            templates, _errors = load_corpus(module.templates_dir)
            scanner = SslScanner(
                templates,
                concurrency=int(probe.get("concurrency", 32)),
                timeout=float(probe.get("connect_timeout_ms", 4000)) / 1000.0,
            )
            self._engines[key] = scanner
        findings, stats = scanner.scan(
            data.decode("utf-8", "surrogateescape").splitlines()
        )
        print(
            f"ssl scan: {stats['targets']} targets x {stats['templates']} "
            f"templates, {stats['hits']} hits"
        )
        return format_findings(findings)

    # ------------------------------------------------------------------
    def _execute_jarm(self, module: ModuleSpec, data: bytes) -> bytes:
        """Active TLS fingerprinting (JARM + JA3S) with device-side
        density-peaks clustering of the resulting fingerprints
        (BASELINE.json config #5). Output: one line per target with its
        fingerprint, cluster label, and cluster size."""
        from swarm_tpu.ops import cluster as cl
        from swarm_tpu.worker.executor import ProbeExecutor

        fps = ProbeExecutor(module.probe).run_jarm(
            data.decode("utf-8", "surrogateescape").splitlines()
        )
        alive = [fp for fp in fps if fp.alive]
        lab: list[int] = []
        sizes: dict[int, int] = {}
        if alive:
            radius = float(module.raw.get("cluster_radius", 32.0))
            packed = cl.pack_strings([fp.jarmx for fp in alive])
            labels, _rho = cl.density_cluster(packed, radius)
            lab = [int(x) for x in labels]
            for label in lab:
                sizes[label] = sizes.get(label, 0) + 1
        lines = []
        alive_iter = iter(lab)
        for fp in fps:
            if fp.alive:
                label = next(alive_iter)
                lines.append(
                    f"{fp.line()} cluster={label} cluster_size={sizes[label]}"
                )
            else:
                lines.append(fp.line())
        return ("\n".join(lines) + "\n").encode() if lines else b""

    # ------------------------------------------------------------------
    def _execute_command(
        self, module: ModuleSpec, scan_id: str, chunk_index: int, data: bytes
    ) -> Optional[bytes]:
        """Subprocess path — behavior-parity with reference worker.py:79-90."""
        job_dir = self.work_dir / scan_id
        job_dir.mkdir(parents=True, exist_ok=True)
        input_file = job_dir / f"chunk_{chunk_index}.txt"
        output_file = job_dir / f"chunk_{chunk_index}.out.txt"
        input_file.write_bytes(data)
        command = module.command(str(input_file), str(output_file))
        proc = subprocess.run(
            command, shell=True, stdout=subprocess.PIPE, stderr=subprocess.PIPE
        )
        if proc.returncode != 0:
            print(f"Error executing command: {command}")
            print(proc.stderr.decode("utf-8", "replace"))
            return None
        return output_file.read_bytes() if output_file.is_file() else proc.stdout

    # ------------------------------------------------------------------
    def _engine_for(self, templates_dir: str):
        engine = self._engines.get(templates_dir)
        if engine is None:
            from swarm_tpu.fingerprints.dbcache import load_or_compile
            from swarm_tpu.ops.engine import MatchEngine
            from swarm_tpu.parallel.multihost import (
                maybe_initialize_distributed,
            )

            # multi-host engine bring-up (docs/SHARDING.md): join the
            # DCN process group BEFORE the engine's auto-mesh resolves,
            # so jax.devices() spans every host's chips and the mesh is
            # slice-wide. Idempotent and a no-op without the
            # SWARM_COORDINATOR/-NUM_PROCESSES/-PROCESS_ID triplet —
            # embedded workers (started without main()) get the same
            # bring-up as the CLI path.
            maybe_initialize_distributed()
            # disk-cached corpus compile (+ persistent XLA cache): a
            # warm worker builds the full-corpus engine in ~a second.
            # cfg.pipeline routes bulk matching through the continuous-
            # batching scheduler (swarm_tpu/sched) when "on".
            templates, db = load_or_compile(templates_dir)
            engine = MatchEngine(
                templates, db=db, pipeline=self.cfg.pipeline
            )
            # fleet-wide result tier (docs/CACHING.md): rows any worker
            # has ever resolved short-circuit before device dispatch.
            # SWARM_CACHE_BACKEND=off (default) skips this entirely; a
            # tier that can't be built must not kill engine bring-up —
            # the cache is an accelerator, never a dependency.
            from swarm_tpu.cache import build_result_cache

            try:
                client = build_result_cache(self.cfg)
                if client is not None:
                    engine.attach_result_cache(client)
            except Exception as e:
                print(f"result cache unavailable ({e}); running L1-only")
            # AOT executable cache (docs/AOT.md): a joining worker
            # FETCHES the fleet's published executables at bring-up
            # instead of compiling them per shape class — the cold-
            # start cliff becomes a one-time fleet-wide cost.
            # SWARM_AOT_BACKEND=off (default) skips this entirely; a
            # store that can't be built or prewarmed must not kill
            # engine bring-up (breaker-wrapped, never blocks).
            from swarm_tpu.aot import build_aot_client

            try:
                aot = build_aot_client(self.cfg)
                if aot is not None:
                    engine.attach_aot(aot)
                    if self.cfg.aot_prewarm:
                        import time as _time

                        t0 = _time.perf_counter()
                        n = engine.aot_prewarm()
                        if n:
                            print(
                                f"AOT prewarm: {n} executables loaded "
                                f"in {_time.perf_counter() - t0:.2f}s"
                            )
            except Exception as e:
                print(f"AOT executable cache unavailable ({e}); "
                      "compiling locally")
            self._engines[templates_dir] = engine
        return engine

    def _execute_tpu(
        self, module: ModuleSpec, data: bytes, qos: Optional[str] = None
    ) -> bytes:
        """Device-batch path: chunk rows → MatchEngine → JSONL hits.

        ``input_format: targets`` first runs the native probe front-end
        (resolve + connect + banner/HTTP fetch) to build the rows.
        ``qos`` is the job's latency class (docs/GATEWAY.md §QoS): on
        the pipelined path interactive chunks ride the scheduler's
        express buckets with the deadline flush armed."""
        if not module.templates_dir:
            raise ValueError(f"tpu module {module.name} missing 'templates'")
        engine = self._engine_for(module.templates_dir)
        self._mark_engine_stats(engine)
        text = data.decode("utf-8", "surrogateescape")
        if module.input_format == "targets":
            # double-buffered: probe wave i+1 while matching wave i
            from swarm_tpu.worker.streaming import stream_match

            rows, results, _stats = stream_match(
                engine,
                text.splitlines(),
                probe_spec=module.probe,
                wave_targets=int(module.raw.get("wave_targets", 1024)),
            )
        elif engine.pipeline == "on":
            # continuous-batching path (docs/PIPELINE.md): line decode
            # runs on the scheduler's prefetch thread — chunk i+1
            # parses while chunk i's batch rides the device — and rows
            # are re-binned into padding buckets with memo short-
            # circuiting. Results are bit-identical to the direct path.
            lines = text.splitlines()
            step = engine.batch_rows
            payloads = [
                (ci, lines[s : s + step])
                for ci, s in enumerate(range(0, len(lines), step))
            ] or [(0, [])]
            rows_by_chunk: dict = {}

            def decode(payload):
                ci, chunk_lines = payload
                out = []
                for line in chunk_lines:
                    row = parse_response_line(line)
                    if row is not None:
                        out.append(row)
                rows_by_chunk[ci] = out
                return out

            rows = []
            results = []
            sched = engine.scheduler()
            # operator deadline knobs reach the planner here (the
            # scheduler is engine-lazy; the engine ctor never sees cfg)
            sched.config.qos_deadline_ms = self.cfg.qos_deadline_ms
            sched.config.max_age_ms = self.cfg.sched_max_age_ms
            # "sched" = the continuous-batching drive window (planning,
            # coalescing, deadline flushes); the engine's device.* and
            # engine.* spans nest under it, the offloaded walk's included
            with tracing.span("sched", qos=qos, pipeline="on"):
                for ci, res in enumerate(
                    sched.run(payloads, decode=decode, qos=qos)
                ):
                    rows.extend(rows_by_chunk.pop(ci))
                    results.extend(res)
        else:
            rows = []
            for line in text.splitlines():
                row = parse_response_line(line)
                if row is not None:
                    rows.append(row)
            results = engine.match(rows)
        # workflow gating over the already-matched rows (ops/workflows):
        # one wf line per row where a trigger gated matching subtemplates
        wf_lines: list[str] = []
        if any(t.protocol == "workflow" for t in engine.templates):
            from swarm_tpu.ops.workflows import WorkflowRunner

            wkey = f"wfrunner::{module.templates_dir}"
            runner = self._engines.get(wkey)
            if runner is None:
                runner = WorkflowRunner(engine.templates, engine=engine)
                self._engines[wkey] = runner
            jsonl = module.output_format != "nuclei"
            for row, rm in zip(rows, results):
                if not rm.template_ids:
                    continue  # nothing matched: no workflow can trigger
                per = runner.evaluate_hits(
                    set(rm.template_ids), lambda _tid, _r=row: [_r]
                )
                for wid, sub_ids in sorted(per.items()):
                    if jsonl:  # keep the jsonl contract machine-readable
                        wf_lines.append(
                            json.dumps(
                                {
                                    "workflow": wid,
                                    "host": row.host,
                                    "port": row.port,
                                    "matches": sub_ids,
                                },
                                sort_keys=True,
                            )
                        )
                    else:
                        wf_lines.append(
                            f"[{wid}] [workflow] {row.host}:{row.port} "
                            f"[{','.join(sub_ids)}]"
                        )
        if module.output_format == "nuclei":
            from swarm_tpu.worker import formats

            sev, proto = formats.severity_index(engine.templates)
            out = formats.format_nuclei(rows, results, sev, proto)
            if wf_lines:
                out = out + "\n".join(wf_lines) + "\n"
            return out.encode()
        out_lines = [
            format_match_line(row, matches) for row, matches in zip(rows, results)
        ]
        out_lines += wf_lines
        return ("\n".join(out_lines) + "\n").encode() if out_lines else b""

    # ------------------------------------------------------------------
    def _execute_probe(self, module: ModuleSpec, data: bytes) -> bytes:
        """Native-I/O-only path (dnsx/httprobe/httpx/web module parity):
        probe the targets with the C++ front-end and emit the module's
        output format — no template matching involved."""
        from swarm_tpu.worker import formats
        from swarm_tpu.worker.executor import ProbeExecutor

        lines = data.decode("utf-8", "surrogateescape").splitlines()
        executor = ProbeExecutor(module.probe)
        if module.probe.get("type") == "dns":
            resolutions = executor.resolve(lines)
            return formats.format_dnsx(
                resolutions, with_a=bool(module.probe.get("with_a"))
            ).encode()
        rows = executor.run(lines)
        if module.output_format == "httprobe":
            return formats.format_httprobe(rows).encode()
        if module.output_format == "httpx_json":
            return formats.format_httpx_json(rows).encode()
        raise ValueError(
            f"module {module.name}: unknown output_format {module.output_format!r}"
        )

    # ------------------------------------------------------------------
    def _execute_service(self, module: ModuleSpec, data: bytes) -> bytes:
        """Service/version detection (the nmap -sV replacement): native
        banner probing with payloads from the probes DB, device-batched
        match prefilter, host version extraction."""
        from swarm_tpu.ops.service import ServiceClassifier
        from swarm_tpu.worker.executor import ProbeExecutor

        key = f"svc::{module.raw.get('probes_db') or ''}"
        classifier = self._engines.get(key)
        if classifier is None:
            classifier = ServiceClassifier(db_path=module.raw.get("probes_db"))
            self._engines[key] = classifier
        self._mark_engine_stats(classifier.engine)
        rows, sent = ProbeExecutor(module.probe).run_service(
            data.decode("utf-8", "surrogateescape").splitlines(), classifier
        )
        infos = classifier.classify(rows, sent)
        if module.output_format == "nmap":
            from swarm_tpu.worker import formats

            return formats.format_nmap_report(infos).encode()
        lines = [info.line() for info in infos if info.open]
        return ("\n".join(lines) + "\n").encode() if lines else b""


def device_info() -> dict:
    """The accelerator this worker runs on, as JAX reports it."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def device_perf() -> dict:
    """Job perf fields that say where the job ran: the device, the
    persistent compile cache's process-lifetime hit/miss counts, and
    the first device's peak memory (backends that report it)."""
    import jax

    from swarm_tpu.utils.xlacache import _cache_counters

    hit, miss = _cache_counters()
    out = {
        "device": device_info(),
        "xla_cache_hit": int(hit.labels().value),
        "xla_cache_miss": int(miss.labels().value),
    }
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        out["peak_bytes_in_use"] = int(stats["peak_bytes_in_use"])
    return out


def main(argv: Optional[list[str]] = None) -> None:
    import argparse

    parser = argparse.ArgumentParser(description="swarm_tpu worker")
    parser.add_argument("--server-url", default=None)
    parser.add_argument("--api-key", default=None)
    parser.add_argument("--worker-id", default=None)
    parser.add_argument("--modules-dir", default=None)
    parser.add_argument("--max-jobs", type=int, default=None)
    parser.add_argument("--config", default=None)
    args = parser.parse_args(argv)
    cfg = Config.load(
        path=args.config,
        server_url=args.server_url,
        api_key=args.api_key,
        worker_id=args.worker_id,
        modules_dir=args.modules_dir,
        max_jobs=args.max_jobs,
    )
    # multi-host worker: join the DCN process group when configured
    # (SWARM_COORDINATOR/-NUM_PROCESSES/-PROCESS_ID) so the tpu
    # backend's mesh spans every host's chips; no-op single-host
    from swarm_tpu.parallel.multihost import maybe_initialize_distributed
    from swarm_tpu.utils.xlacache import (
        enable_compilation_cache,
        install_cache_metrics,
    )

    enable_compilation_cache()  # warm restarts skip the corpus recompile
    # swarm_xla_cache_{hit,miss}_total: fleet restarts must show on
    # /metrics whether the persistent cache is actually serving —
    # installed even when the cache dir is disabled (counters then
    # simply stay dark, instead of silently missing from the scrape)
    install_cache_metrics()
    if maybe_initialize_distributed():
        print("multi-host: jax.distributed initialized")
    print("worker device: " + json.dumps(device_info()), flush=True)
    proc = JobProcessor(cfg)
    # SIGTERM routes through the DRAIN path, not a mid-upload death
    # (docs/RESILIENCE.md §Preemption): the handler only sets a flag,
    # the poll loop finishes its current lease, flushes or spools, and
    # deregisters before exiting. Best-effort install — embedded runs
    # off the main thread can't own signals.
    import signal

    try:
        signal.signal(
            signal.SIGTERM, lambda _sig, _frm: proc.request_drain("sigterm")
        )
    except ValueError:
        pass
    for name in filter(None, (n.strip() for n in cfg.prewarm_modules.split(","))):
        if proc.prewarm(name):
            print(f"prewarmed module {name}")
    proc.process_jobs()


if __name__ == "__main__":
    main()
