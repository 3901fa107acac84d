#!/usr/bin/env python3
"""Chip smoke: the service census end to end on one TPU chip.

Drives the system's main path through the entry points a user calls —
``python -m swarm_tpu.server``, exactly one ``python -m swarm_tpu.worker``
and ``python -m swarm_tpu.client scan``/``cat`` — over the production
probes DB (``swarm_tpu/data/service-probes-large.txt``: 487 probes,
12,331 match directives, nmap ``-sV`` scale). The targets are loopback
addresses served by a banner server in this process: each
``127.1.a.b:port`` target answers with one of the 1,386 labelled banners
of ``service-probes-large.recall.json`` (NULL-style banners on connect,
``GetRequest`` banners after the request arrives). Every target's line in
the nmap-style report must equal the host oracle's — ``ops/cpu_ref.py``
regex evaluation under the nmap first-match rule, run here on the same
banners — and the agreement share with the recall labels is printed for
both paths.

This process never imports JAX: the worker is the only process that
touches the chip. The last line of stdout is one JSON object; ``"ok":
true`` only when every phase passed on a TPU.

Options:
  --rehearsal   the bundled DB (service-probes.txt) and 256 targets —
                the CPU rehearsal a test runs under JAX_PLATFORMS=cpu
                (which ends "ok": false, since there is no TPU). With
                --four-chips: that path over the bundled DB, e.g. on
                XLA_FLAGS=--xla_force_host_platform_device_count=4.
  --four-chips  only the sharded path: the census rows through
                ServiceClassifier(mesh="auto") on 4 chips against
                mesh=None on one, in one process.
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import re
import shutil
import socket
import socketserver
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent
LARGE_DB = "swarm_tpu/data/service-probes-large.txt"
BUNDLED_DB = "swarm_tpu/data/service-probes.txt"
RECALL = REPO / "swarm_tpu/data/service-probes-large.recall.json"
MODULE = "servicescan-large"
API_KEY = "k"
DEADLINE_S = 1100.0  # the whole run, compile included (driver limit 1200)

_T0 = time.monotonic()
_phases: list[tuple[str, bool]] = []


def phase(name: str, ok: bool, detail: str = "") -> bool:
    _phases.append((name, ok))
    print(f"phase {name}: {'PASS' if ok else 'FAIL'} {detail}".rstrip(), flush=True)
    return ok


def remaining() -> float:
    return DEADLINE_S - (time.monotonic() - _T0)


def finish(device: dict | None, error: str = "") -> int:
    ok = (
        bool(_phases)
        and all(p_ok for _n, p_ok in _phases)
        and device is not None
        and device.get("platform") == "tpu"
        and not error
    )
    rec: dict = {"ok": ok, "device": device}
    if error:
        rec["error"] = error
    print(json.dumps(rec), flush=True)
    return 0 if ok else 1


# ----------------------------------------------------------------------
# banner server
# ----------------------------------------------------------------------
def target_ip(i: int) -> str:
    """Target i's loopback address (127.1.a.b, b in 1..250)."""
    return f"127.1.{i // 250}.{i % 250 + 1}"


def target_index(ip: str) -> int:
    _, _, a, b = ip.split(".")
    return int(a) * 250 + int(b) - 1


class _BannerHandler(socketserver.BaseRequestHandler):
    def handle(self):
        sock = self.request
        sock.settimeout(10)
        i = target_index(sock.getsockname()[0])
        banner, wait_request = self.server.banners[i]
        if wait_request:
            buf = b""
            while b"\r\n\r\n" not in buf:
                chunk = sock.recv(4096)
                if not chunk:
                    return
                buf += chunk
        sock.sendall(banner)


class BannerServer(socketserver.ThreadingTCPServer):
    """Listens on 0.0.0.0:port; the accepted socket's local address
    picks the target, so one socket answers every 127.1.a.b target."""

    daemon_threads = True
    allow_reuse_address = True
    request_queue_size = 4096

    def __init__(self, port: int, banners: dict, wait_request: bool):
        self.banners = {i: (b, wait_request) for i, b in banners.items()}
        super().__init__(("0.0.0.0", port), _BannerHandler)


def port_free(port: int) -> bool:
    with socket.socket() as s:
        try:
            s.bind(("0.0.0.0", port))
        except OSError:
            return False
    return True


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def pick_ports(probes) -> tuple[int, int]:
    """(listen-probe port, GetRequest port): ports the worker's probe
    selection maps to NULL and to GetRequest, and that are free here."""
    from swarm_tpu.fingerprints.nmap_probes import probe_for_port

    null_port = next(
        p for p in range(41000, 42000)
        if probe_for_port(probes, p).name == "NULL" and port_free(p)
    )
    get = next(p for p in probes if p.name == "GetRequest")
    get_port = next(
        p for lo, hi in get.ports for p in range(lo, hi + 1)
        if p >= 1024
        and probe_for_port(probes, p).name == "GetRequest"
        and port_free(p)
    )
    return null_port, get_port


# ----------------------------------------------------------------------
# host oracle (no JAX): cpu_ref regex evaluation, nmap first-match rule
# ----------------------------------------------------------------------
class Oracle:
    def __init__(self, probes):
        from swarm_tpu.fingerprints.model import Matcher
        from swarm_tpu.ops.service import _inline_flags

        self.probes = probes
        # probe name -> its matches in DB order (every probe that bears
        # the name), and the fallbacks of the last probe that bears it
        self.matches: dict = {}
        self.fallback: dict = {}
        for p in probes:
            for m in p.matches:
                matcher = Matcher(type="regex", part="body", regex=[_inline_flags(m)])
                self.matches.setdefault(p.name, []).append((m, matcher))
            self.fallback[p.name] = p.fallback
        self._memo: dict = {}

    def classify(self, host: str, port: int, banner: bytes, sent: str):
        from swarm_tpu.fingerprints.model import Response
        from swarm_tpu.fingerprints.nmap_probes import substitute_version
        from swarm_tpu.ops import cpu_ref
        from swarm_tpu.ops.service import ServiceInfo

        info = ServiceInfo(host=host, port=port, open=True)
        key = (banner, sent)
        if key not in self._memo:
            row = Response(host=host, port=port, banner=banner)
            order = [sent] + [f for f in self.fallback.get(sent, []) if f != sent]
            if "NULL" not in order:
                order.append("NULL")
            soft = None
            found = (None, None, None, None, [], False)
            for pname in order:
                for m, matcher in self.matches.get(pname, []):
                    if not cpu_ref.match_matcher(matcher, row):
                        continue
                    if m.soft:
                        soft = soft or m
                        continue
                    if soft is not None and m.service != soft.service:
                        continue
                    mo = m.compile().search(banner)
                    found = (
                        m.service,
                        substitute_version(m.product, mo),
                        substitute_version(m.version, mo),
                        substitute_version(m.info, mo),
                        [], False,
                    )
                    break
                if found[0] is not None:
                    break
            if found[0] is None and soft is not None:
                found = (soft.service, None, None, None, [], True)
            self._memo[key] = found
        (info.service, info.product, info.version, info.info,
         info.cpe, info.soft) = self._memo[key]
        return info


_LINE_RE = re.compile(r"^(\d+)/tcp\s+open\s+(\S+)\s*(.*)$")


def parse_report(text: str) -> dict:
    """nmap-style report -> {(host, port): (service column, version column)}."""
    out: dict = {}
    host = None
    for line in text.splitlines():
        if line.startswith("Nmap scan report for "):
            host = line[len("Nmap scan report for "):].strip()
            continue
        m = _LINE_RE.match(line)
        if m and host is not None:
            out[(host, int(m.group(1)))] = (m.group(2), m.group(3).strip())
    return out


def label_agrees(cols, label: dict) -> bool:
    svc, ver = cols
    want = " ".join(x for x in (label["product"], label["version"]) if x)
    return svc == label["service"] and (ver == want or ver.startswith(want + " ("))


# ----------------------------------------------------------------------
# the census through server -> worker -> client
# ----------------------------------------------------------------------
def http_json(url: str):
    req = urllib.request.Request(url, headers={"Authorization": f"Bearer {API_KEY}"})
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


def census(n_targets: int, db: str, procs: list, work: Path) -> dict | None:
    from swarm_tpu.datamodel import JobStatus
    from swarm_tpu.fingerprints.nmap_probes import load_probes, probe_for_port
    from swarm_tpu.worker import formats

    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env["SWARM_BLOB_ROOT"] = str(work / "blobs")
    env["SWARM_DOC_ROOT"] = str(work / "docs")
    modules_dir = "modules"
    if db != LARGE_DB:  # the rehearsal: the same spec over another DB
        spec = json.loads((REPO / "modules" / f"{MODULE}.json").read_text())
        spec["probes_db"] = db
        (work / "modules").mkdir()
        (work / "modules" / f"{MODULE}.json").write_text(json.dumps(spec))
        modules_dir = str(work / "modules")
    env["SWARM_MODULES_DIR"] = modules_dir

    # 1. native libraries from committed sources
    r = subprocess.run(
        ["make", "-B", "-C", "native", f"PY={sys.executable}"],
        cwd=REPO, capture_output=True, text=True,
    )
    if not phase("build", r.returncode == 0, "make -B -C native"):
        print(r.stdout[-2000:] + r.stderr[-2000:], file=sys.stderr)
        return None

    # 2. banner server
    recall = json.loads(RECALL.read_text())
    probes, _skipped = load_probes(REPO / db)
    null_port, get_port = pick_ports(probes)
    targets, by_port = [], {null_port: {}, get_port: {}}
    for i in range(n_targets):
        lab = recall[i % len(recall)]
        port = get_port if lab["probe"] == "GetRequest" else null_port
        by_port[port][i] = base64.b64decode(lab["banner"])
        targets.append((target_ip(i), port, lab))
    servers = [
        BannerServer(null_port, by_port[null_port], wait_request=False),
        BannerServer(get_port, by_port[get_port], wait_request=True),
    ]
    for s in servers:
        threading.Thread(target=s.serve_forever, daemon=True).start()
    procs.append(servers)
    phase(
        "banners", True,
        f"{n_targets} targets over {len(recall)} labelled banners "
        f"(listen port {null_port}, GetRequest port {get_port})",
    )

    # 3. server
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    with open(work / "server.log", "w") as log:
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "swarm_tpu.server", "--port", str(port),
             "--api-key", API_KEY],
            cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT,
        ))
    up = False
    for _ in range(600):
        try:
            urllib.request.urlopen(base + "/healthz", timeout=2).read()
            up = True
            break
        except OSError:
            time.sleep(0.1)
    if not phase("server", up, base):
        return None

    # 4. exactly one worker: the only process that touches JAX
    n_jobs = -(-n_targets // 2048)
    wlog_path = work / "worker.log"
    with open(wlog_path, "w") as wlog:
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "swarm_tpu.worker", "--server-url", base,
             "--api-key", API_KEY, "--worker-id", "smoke-w1",
             "--max-jobs", str(n_jobs)],
            cwd=REPO, env=env, stdout=wlog, stderr=subprocess.STDOUT,
        ))
    worker_device = None
    while worker_device is None and remaining() > 0:
        if procs[-1].poll() is not None:
            break
        for line in wlog_path.read_text(errors="replace").splitlines():
            if line.startswith("worker device: "):
                worker_device = json.loads(line[len("worker device: "):])
        time.sleep(0.2)
    if not phase("worker", worker_device is not None, json.dumps(worker_device)):
        return None
    if db == LARGE_DB and worker_device.get("platform") != "tpu":
        # the full-width census is for the chip: without one, stop here
        phase("tpu", False, "the worker found no TPU (--rehearsal runs on the CPU)")
        return worker_device

    # 5. submit through the client, wait, fetch the report
    tfile = work / "targets.txt"
    tfile.write_text("".join(f"{ip}:{p}\n" for ip, p, _lab in targets))
    client = [sys.executable, "-m", "swarm_tpu.client", "--server-url", base,
              "--api-key", API_KEY]
    t_submit = time.monotonic()
    r = subprocess.run(
        client + ["scan", "--module", MODULE, "--file", str(tfile),
                  "--batch-size", "2048"],
        cwd=REPO, env=env, capture_output=True, text=True,
    )
    if not phase("submit", r.returncode == 0, r.stdout.strip().replace("\n", "; ")):
        return None
    jobs: list = []
    while remaining() > 60:
        worker_gone = procs[-1].poll() is not None  # before the read: no race
        # a fresh server: every job is this scan's
        jobs = list(http_json(base + "/get-statuses")["jobs"].values())
        if worker_gone or (len(jobs) == n_jobs and all(
            j.get("status") in JobStatus.TERMINAL for j in jobs
        )):
            break
        time.sleep(0.5)
    wall = time.monotonic() - t_submit
    done = len(jobs) == n_jobs and all(
        j.get("status") == JobStatus.COMPLETE for j in jobs
    )
    if not phase("scan", done, f"{len(jobs)} jobs: {sorted({j.get('status') for j in jobs})}"):
        return None
    scan_id = jobs[0]["scan_id"]
    r = subprocess.run(
        client + ["cat", "--scan-id", scan_id],
        cwd=REPO, env=env, capture_output=True, text=True,
    )
    report = parse_report(r.stdout)

    # 6. answers: the host oracle on the same banners, and the labels
    oracle = Oracle(probes)
    t0 = time.perf_counter()
    infos = [
        oracle.classify(ip, p, base64.b64decode(lab["banner"]),
                        probe_for_port(probes, p).name)
        for ip, p, lab in targets
    ]
    oracle_s = time.perf_counter() - t0
    expected = parse_report(formats.format_nmap_report(infos))
    mismatch = [
        (k, report.get(k), v) for k, v in expected.items() if report.get(k) != v
    ]
    missing = len(targets) - len(report)
    phase(
        "oracle", not mismatch and missing == 0 and len(expected) == len(targets),
        f"{len(targets) - len(mismatch)}/{len(targets)} targets equal the "
        f"host oracle ({oracle_s:.1f} s on the host)",
    )
    for k, got, want in mismatch[:10]:
        print(f"  mismatch {k}: report {got} oracle {want}", flush=True)
    dev_lab = sum(
        label_agrees(report[(ip, p)], lab)
        for ip, p, lab in targets if (ip, p) in report
    )
    cpu_lab = sum(
        label_agrees(expected[(ip, p)], lab)
        for ip, p, lab in targets if (ip, p) in expected
    )
    print(
        f"label agreement: report {dev_lab}/{len(targets)} "
        f"({dev_lab / len(targets):.4f}), cpu path {cpu_lab}/{len(targets)} "
        f"({cpu_lab / len(targets):.4f})",
        flush=True,
    )

    # 7. what the worker reported
    perfs = [j.get("perf") or {} for j in jobs]
    dev = perfs[0].get("device") or worker_device
    rows = sum(p.get("rows", 0) for p in perfs)
    faults = sum(p.get("device_faults", 0) for p in perfs)
    degraded = sum(p.get("degraded_batches", 0) for p in perfs)
    print(f"device: platform={dev.get('platform')} kind={dev.get('kind')} "
          f"count={dev.get('count')}", flush=True)
    print(f"compile_s: {sum(p.get('compile_s', 0.0) for p in perfs)}", flush=True)
    print(f"rows_classified: {rows}", flush=True)
    print(f"wall_s: {wall} (informational, not a metric: "
          f"{n_targets / wall:.1f} targets/s incl. compile and probing)", flush=True)
    print(f"swarm_xla_cache_hit_total: {max(p.get('xla_cache_hit', 0) for p in perfs)} "
          f"swarm_xla_cache_miss_total: {max(p.get('xla_cache_miss', 0) for p in perfs)}",
          flush=True)
    print(f"device_faults: {faults} degraded_batches: {degraded}", flush=True)
    peak = [p["peak_bytes_in_use"] for p in perfs if "peak_bytes_in_use" in p]
    print(f"peak_bytes_in_use: {max(peak) if peak else 'not reported'}", flush=True)
    phase("rows", rows == n_targets, f"{rows} rows classified")
    phase("device_path", faults == 0 and degraded == 0,
          f"device_faults={faults} degraded_batches={degraded}")
    return dev


def stop(procs: list) -> None:
    for p in reversed(procs):
        if isinstance(p, list):
            for s in p:
                s.shutdown()
                s.server_close()
            continue
        if p.poll() is None:
            p.terminate()
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


# ----------------------------------------------------------------------
# --four-chips: the sharded path, one process
# ----------------------------------------------------------------------
def four_chips(db: str) -> int:
    import jax

    from swarm_tpu.fingerprints.model import Response
    from swarm_tpu.ops.service import ServiceClassifier
    from swarm_tpu.telemetry import REGISTRY

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: {json.dumps(device)}", flush=True)
    if not phase("devices", len(devs) == 4, f"{len(devs)} devices"):
        return finish(device)
    recall = json.loads(RECALL.read_text())
    # each distinct banner under the probe the census sends its target
    uniq = list(dict.fromkeys(
        (base64.b64decode(r["banner"]),
         "GetRequest" if r["probe"] == "GetRequest" else "NULL")
        for r in recall
    ))
    uniq = uniq[: len(uniq) - (1 - len(uniq) % 2)]  # an odd row count
    rows = [Response(host=target_ip(i), port=41000, banner=b)
            for i, (b, _sent) in enumerate(uniq)]
    sent = [s for _b, s in uniq]
    db = str(REPO / db)
    t0 = time.perf_counter()
    sharded = ServiceClassifier(db_path=db, mesh="auto")
    sharded.engine._resolve_backend()
    placed: set = set()
    stage = sharded.engine.sharded._stage

    def spy(streams, lengths, status):
        out = stage(streams, lengths, status)
        for a in out[0].values():
            placed.update(s.device for s in a.addressable_shards)
        return out

    sharded.engine.sharded._stage = spy
    got = sharded.classify(rows, sent)
    print(f"sharded mesh: {dict(sharded.engine.mesh.shape)} "
          f"({time.perf_counter() - t0:.1f} s incl. compile)", flush=True)
    t0 = time.perf_counter()
    single = ServiceClassifier(db_path=db, mesh=None)
    want = single.classify(rows, sent)
    print(f"single chip: {time.perf_counter() - t0:.1f} s incl. compile", flush=True)
    same = [g.line() for g in got] == [w.line() for w in want]
    phase("identical", same, f"{len(rows)} rows (odd), sharded vs mesh=None")
    dispatches = sum(
        s["value"] for s in REGISTRY.snapshot()
        .get("swarm_shard_dispatches_total", {}).get("samples", [])
    )
    phase("shard_dispatches", dispatches > 0,
          f"swarm_shard_dispatches_total={dispatches}")
    phase("placement", len(placed) == 4,
          f"batch shards on {len(placed)} distinct devices: "
          f"{sorted(str(d) for d in placed)}")
    for e in (sharded.engine, single.engine):
        phase("device_path", e.stats.device_faults == 0 and e.stats.degraded_batches == 0,
              f"device_faults={e.stats.device_faults} "
              f"degraded_batches={e.stats.degraded_batches}")
    return finish(device)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="bundled DB (and 256 targets): the CPU rehearsal")
    ap.add_argument("--four-chips", action="store_true",
                    help="only the sharded path on four chips")
    args = ap.parse_args(argv)
    if not (REPO / "swarm_tpu").is_dir():
        return finish(None, "no swarm_tpu package beside chip_smoke.py")
    sys.path.insert(0, str(REPO))
    db = BUNDLED_DB if args.rehearsal else LARGE_DB
    if args.four_chips:
        return four_chips(db)
    n_targets = 256 if args.rehearsal else 8192
    work = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    procs: list = []
    device = None
    try:
        device = census(n_targets, db, procs, work)
    except Exception as e:  # any phase that raised: report it, fail
        import traceback

        traceback.print_exc()
        phase("census", False, f"{type(e).__name__}: {e}")
    finally:
        stop(procs)
        if not all(ok for _n, ok in _phases):
            for name in ("worker.log", "server.log"):
                log = work / name
                if log.exists():
                    print(f"--- {name} (tail)", file=sys.stderr)
                    print(log.read_text(errors="replace")[-6000:], file=sys.stderr)
        shutil.rmtree(work, ignore_errors=True)
    return finish(device)


if __name__ == "__main__":
    sys.exit(main())
