"""Benchmarks: the framework's headline numbers on one chip.

Emits one JSON line per metric (the last line is the headline the
driver tails):

1. ``exact_fingerprints_per_sec_per_chip`` — END-TO-END
   ``MatchEngine.match_packed``: encode → device kernel (q-gram probe,
   byte verify, device regex verify, device md5, verdict lowering) →
   sparse host confirmation + extraction, over the full 3,989-template
   reference corpus on a realistic response mix. This includes the
   exactness contract's full cost (BASELINE.md's 100%-parity metric).
2. ``service_probe_classifications_per_sec`` — BASELINE config #4
   analog: banner stream → nmap-service-probes classifier
   (ops/service.py) end to end.
3. ``jarm_cluster_rows_per_sec`` — BASELINE config #5 analog: packed
   JARM fingerprints → density clustering (ops/cluster.py,
   Pallas/XLA MXU hamming kernels).
4. ``service_fingerprints_per_sec_per_chip`` — the device-only match
   step (the kernel ceiling; headline continuity with round 1).

vs_baseline divides by the north-star target 10M fingerprints/sec on a
v4-8 (4 chips) => 2.5M/sec/chip (BASELINE.json) for the exact/device
metrics; the auxiliary metrics divide by the per-config targets in
``BASELINES`` (documented in BASELINE.md §"Per-metric targets") so a
regression in ANY emitted line is driver-visible — no line carries
vs_baseline 0.0.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

import numpy as np

REFERENCE_CORPUS = Path("/root/reference/worker/artifacts/templates")
BUNDLED_CORPUS = Path(__file__).parent / "tests" / "data" / "templates"

TARGET_PER_CHIP = 10_000_000 / 4  # north star: 10M/s on a v4-8 (4 chips)

#: Per-metric baseline targets (BASELINE.md §"Per-metric targets").
#: Every emitted line divides by its target so the driver can detect a
#: regression in ANY metric, not just the headline (round-2 verdict:
#: no vs_baseline 0.0 lines).
BASELINES = {
    # BASELINE config #2: 10k-banner nmap-service-probes classify.
    "service_probe_classifications_per_sec": 50_000.0,
    # config #2 at production DB scale (487 probes / 12.3k signatures,
    # data/service-probes-large.txt) — nmap -sV's real signature count
    "service_full_db_classifications_per_sec": 20_000.0,
    # BASELINE config #4: masscan-style stream -> classifier, pipelined.
    "streamed_service_classifications_per_sec": 50_000.0,
    # BASELINE config #5: internet-wide JARM clustering (round-3 bar).
    "jarm_cluster_rows_per_sec": 20_000.0,
    # exact-engine speedup over the per-row CPU oracle (config #1 A/B).
    "device_vs_cpu_oracle_speedup": 10_000.0,
    # design-bound fresh-content host walk (round-3 bar: 10x the
    # round-2 measured 37k).
    "exact_fresh_content_host_walk_rows_per_sec": 400_000.0,
    # per-row CPU oracle over the full corpus (r2 measured ~12 rows/s);
    # input to the speedup ratio, but its standalone line must still
    # make a regression visible
    "cpu_oracle_rows_per_sec": 10.0,
    # continuous-batching scheduler A/B (docs/PIPELINE.md): pipeline=on
    # over pipeline=off on the same chunked fresh-content feed. Target
    # 1.0 = parity; the whole point is vs_baseline > 1.
    "pipeline_ab_fresh_speedup": 1.0,
    # row-parallel batched host walk A/B (docs/HOST_WALK.md): batched
    # walk over the serial reference on the same confirm-heavy fresh
    # feed (same-run paired comparison; 1.0 = parity).
    "walk_ab_fresh_speedup": 1.0,
    # TIME baselines (two-phase corpus-as-arguments kernel,
    # docs/DEVICE_MATCH.md): the PRE-change records — 124 s first-shape
    # compile (MULTICHIP_r05 slow_operation_alarm floor) and 14.2 s
    # END-TO-END per 2048-row fresh batch (143 rows/s/chip; that record
    # came through a remote accelerator connection and was deleted in
    # PR 21 — the figures stay as the baselines' definition only).
    # Lower is better, so these lines emit vs_baseline = baseline /
    # value (> 1 = improvement). The fresh line's VALUE is the total
    # per-batch wall (like-for-like with the 14.2 s record); the
    # device-only half rides in the line's extra fields so future
    # BENCH_* records can track it against itself.
    "device_compile_seconds": 124.0,
    "fresh_batch_device_ms": 14200.0,
    # pod-scale sharded serving (docs/SHARDING.md, ISSUE 8): data-axis
    # scaling efficiency of the mesh dispatch/collect path — rows/s at
    # mesh (R,1,1) vs the 1-device rate (per-chip on accelerators,
    # rate parity on the shared-silicon host-platform mesh). The
    # acceptance floor is ≥0.7 linear.
    "sharded_data_axis_efficiency": 0.7,
    # latency-tiered serving (docs/GATEWAY.md §QoS, ISSUE 15): the
    # bimodal open-loop A/B's gate — interactive p99 admission-to-
    # verdict latency on the express lane must be ≥5x lower than the
    # SAME probes riding the bulk lane, with bulk throughput retained
    # within 10% and verdicts bit-identical.
    "qos_interactive_p99_speedup": 5.0,
    # donated+compacted split-phase dispatch A/B (docs/DEVICE_MATCH.md,
    # ISSUE 6): the production dispatch (staging pool + donate_argnums
    # + survivor-compacted phase B) over the legacy fused arm on the
    # same fresh encoded batches, gated on bit-identical fused planes
    # every repeat (1.0 = parity; the tentpole's point is > 1).
    "fresh_dispatch_ab_speedup": 1.0,
    # fleet-replay dedup scenario (docs/CACHING.md, ISSUE 9): a second
    # engine LIFETIME (fresh L1) re-scanning tier-known content must be
    # ≥3x the tier-off lifetime with bit-identical verdicts, and ≥0.9
    # of its rows must be served by the shared tier.
    "dedup_warm_speedup": 3.0,
    "dedup_cache_hit_ratio": 0.9,
    # device workflow gating A/B (docs/WORKFLOWS.md, ISSUE 20): gate
    # planes decoded off the verdict tail vs the bit-identical host
    # twin on the same engine and workflow-heavy fresh fleet (1.0 =
    # parity; the tentpole's point is > 1, rc-gated on per-row result
    # equality every repeat).
    "workflow_device_speedup": 1.0,
}

ROWS = 2048
MAX_BODY = 2048
MAX_HEADER = 512
WARMUP = 2
ITERS = 20


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


#: the device every emitted line names (resolve_device); a parent that
#: never touches JAX emits its children's lines verbatim
_DEVICE: dict = {}


def emit(
    metric: str, value: float, unit: str, vs_baseline: float,
    extra: dict | None = None,
) -> None:
    rec = {
        "metric": metric,
        # 3 decimals, not int: sub-1.0 rates (the per-row CPU oracle)
        # must survive the child→parent JSON round trip
        "value": round(value, 3),
        "unit": unit,
        # significant figures, not decimals: a tiny-but-real ratio
        # (a CPU run's fresh floor ~0.0007) must never round to 0.0 —
        # that would read as a measured total collapse
        "vs_baseline": float(f"{vs_baseline:.3g}"),
    }
    if extra:
        rec.update(extra)
    if _DEVICE:
        rec.setdefault("device", _DEVICE)
    print(json.dumps(rec), flush=True)


def realistic_rows(n: int, seed: int = 7):
    """Internet-scan-shaped response mix: mostly default pages, 404s,
    redirects and bare replies; ~10% fingerprint-rich rows. Content
    repeats across hosts the way real scans do (default pages are
    byte-identical fleet-wide)."""
    from swarm_tpu.fingerprints.model import Response

    rng = np.random.default_rng(seed)
    servers = [
        b"nginx", b"nginx/1.18.0 (Ubuntu)", b"Apache/2.4.41 (Ubuntu)",
        b"Apache", b"cloudflare", b"Microsoft-IIS/10.0", b"openresty",
        b"LiteSpeed", b"AmazonS3", b"gws",
    ]
    rich = [
        b"<html><head><title>Grafana</title></head><body><script>window.grafanaBootData={settings:{buildInfo:{version:\"9.1.0\"}}}</script></body></html>",
        b"<html><head><title>Dashboard [Jenkins]</title></head><body>Jenkins</body></html>",
        b"<html><head><title>phpMyAdmin</title></head><body>phpMyAdmin</body></html>",
        b"<html><head><title>Sign in - GitLab</title></head><body class=gitlab>GitLab</body></html>",
        b"<meta name=\"generator\" content=\"WordPress 6.2\"><html><body>wp-content/themes</body></html>",
        b"<html><head><title>RouterOS router configuration page</title></head><body>mikrotik</body></html>",
    ]
    rows = []
    for i in range(n):
        r = rng.random()
        srv = servers[int(rng.integers(0, len(servers)))]
        if r < 0.35:
            body = b"<html><head><title>Welcome to nginx!</title></head><body><h1>Welcome to nginx!</h1></body></html>"
            status = 200
        elif r < 0.55:
            body = b"<html><head><title>404 Not Found</title></head><body><center><h1>404 Not Found</h1></center><hr><center>nginx</center></body></html>"
            status = 404
        elif r < 0.70:
            body = b""
            status = 301
        elif r < 0.80:
            body = b"<html><head><title>403 Forbidden</title></head><body><center><h1>403 Forbidden</h1></center></body></html>"
            status = 403
        elif r < 0.90:
            filler = bytes(
                rng.integers(97, 123, size=int(rng.integers(200, 1500)), dtype=np.uint8)
            )
            body = (
                b"<html><head><title>Home - Example Corp</title></head><body>"
                + filler + b"</body></html>"
            )
            status = 200
        else:
            body = rich[int(rng.integers(0, len(rich)))]
            status = 200
        hdr = (
            b"HTTP/1.1 %d X\r\nServer: %s\r\nContent-Type: text/html\r\n"
            b"Date: Tue, 29 Jul 2026 12:00:00 GMT" % (status, srv)
        )
        rows.append(
            Response(
                host=f"192.0.2.{i % 254}",
                port=(80, 443, 8080)[i % 3],
                status=status,
                body=body,
                header=hdr,
            )
        )
    return rows


def resolve_device():
    """The measurement's device. No TPU is a failure: a run lands on the
    CPU only when ``JAX_PLATFORMS=cpu`` selects it explicitly (--smoke,
    tools/preflight.sh), and then every line it emits names the CPU."""
    import jax

    from swarm_tpu.utils.xlacache import enable_compilation_cache

    enable_compilation_cache()
    devs = jax.devices()
    dev = devs[0]
    _DEVICE.update(
        platform=dev.platform, kind=dev.device_kind, count=len(devs)
    )
    log(f"bench device: {_DEVICE}")
    if dev.platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            f"bench: no TPU (JAX found {dev.platform}); set "
            "JAX_PLATFORMS=cpu to run on the CPU explicitly"
        )
    return dev


def _clone_rows(rows):
    """Content-equal copies through fresh byte objects — the
    production allocation pattern (every chunk parses new bytes), so
    memo full-compare costs are measured, not skipped via the
    same-object shortcut."""
    from swarm_tpu.fingerprints.model import Response as _R

    return [
        _R(
            host=r.host, port=r.port, status=r.status,
            body=bytes(memoryview(r.body)),
            header=bytes(memoryview(r.header)),
            banner=None if r.banner is None else bytes(memoryview(r.banner)),
        )
        for r in rows
    ]


def _verdicts_equal(a, b) -> bool:
    """Row-by-row identity of template_ids (EXACT order — both paths
    emit ascending template index then the host-always tail) and
    extraction values. ``confirmed_on_host`` is deliberately excluded:
    confirm attribution lands on each batch's dedup representative, so
    it legitimately differs when the batching differs."""
    if len(a) != len(b):
        return False
    for ra, rb in zip(a, b):
        if ra.template_ids != rb.template_ids:
            return False
        if ra.extractions != rb.extractions:
            return False
    return True


def bench_pipeline_ab(eng, chunk_rows: int = 0, n_chunks: int = 8) -> dict:
    """A/B the continuous-batching scheduler (swarm_tpu/sched,
    docs/PIPELINE.md) against the direct path on the SAME engine, same
    machine, same content — a chunk-shaped feed (chunks smaller than a
    device batch, the worker's real input shape, which is exactly
    where the direct path serializes decode/memo/dispatch). Steady
    state (memo-warm content) and fresh content (memos cleared, every
    row salted-unique) are measured per mode; verdict bit-identity
    between the modes is checked row by row — a perf mode that changed
    results would be a bug, not a result."""
    import time as _time

    # chunk floor 256: the worker's real chunks are hundreds to
    # thousands of rows (batch_size config; the reference shards by
    # file lines) — sub-100-row chunks are an artificial stress where
    # per-chunk interpreter floor dominates both modes
    chunk_rows = chunk_rows or max(ROWS // 4, 256)
    base = [
        realistic_rows(chunk_rows, seed=900 + i) for i in range(n_chunks)
    ]

    def salted(seed: int):
        rng = np.random.default_rng(seed)
        out = [_clone_rows(c) for c in base]
        for c in out:
            for r in c:
                salt = bytes(rng.integers(97, 123, size=48, dtype=np.uint8))
                r.body = b"<!-- %s -->" % salt + r.body
        return out

    prior_mode = eng.pipeline

    def run_mode(mode: str, chunks, fresh: bool):
        eng.pipeline = mode
        if fresh:
            eng.clear_content_memos()
        walk0 = eng.stats.host_confirm_seconds
        t0 = _time.perf_counter()
        out: list = []
        if mode == "on":
            # one scheduler run over the whole chunk stream: buckets
            # coalesce across chunk boundaries (continuous batching)
            for res in eng.scheduler().run(chunks):
                out.extend(res)
        else:
            for c in chunks:
                out.extend(eng.match(c))
        wall = _time.perf_counter() - t0
        walk = eng.stats.host_confirm_seconds - walk0
        n = sum(len(c) for c in chunks)
        return out, {
            "rows_per_sec": round(n / wall, 1) if wall > 0 else 0.0,
            "walk_rows_per_sec": round(n / walk, 1) if walk > 0 else 0.0,
        }

    try:
        # shape + memo warm per mode (untimed)
        run_mode("off", [_clone_rows(c) for c in base], fresh=False)
        run_mode("on", [_clone_rows(c) for c in base], fresh=False)
        # steady state is all-memo-served and finishes in tens of ms —
        # far below scheduler-jitter on a noisy host. Two stabilizers:
        # repeat the chunk stream so each timed run covers >= ~2k rows
        # (fixed per-run costs amortize for BOTH modes), and A/B on the
        # MEDIAN of interleaved repeats (interleaving cancels drift)
        def median_pair(pairs: list) -> tuple:
            """The (off, on) rep pair at the MEDIAN on/off ratio.

            Paired, not independent medians: each pair ran back to back,
            so shared-host drift (CPU steal on small VMs swings absolute
            rates ±50% between seconds) hits both sides of a pair alike
            and cancels in the ratio; independent medians can flip the
            comparison on drift alone."""
            pairs = sorted(
                pairs,
                key=lambda p: p[1]["rows_per_sec"]
                / max(p[0]["rows_per_sec"], 1e-9),
            )
            return pairs[len(pairs) // 2]

        steady_mult = max(1, -(-4096 // (chunk_rows * n_chunks)))
        steady_feed = base * steady_mult
        spairs: list = []
        out_off = out_on = None
        for _rep in range(5):
            out_off, so = run_mode(
                "off", [_clone_rows(c) for c in steady_feed], fresh=False
            )
            out_on, sn = run_mode(
                "on", [_clone_rows(c) for c in steady_feed], fresh=False
            )
            spairs.append((so, sn))
        steady_off, steady_on = median_pair(spairs)
        identical = _verdicts_equal(out_off, out_on)
        # fresh-mode shape warm with the SAME content the timed runs
        # use: the salt prefix shifts width classes and the bucket
        # tails' row padding varies with the length mix, so warming on
        # different content leaves shapes to XLA-compile INSIDE the
        # timed window (charged to whichever mode hits them first).
        # Timed fresh runs are interleaved medians too — one host stall
        # during a single-shot run would otherwise decide the A/B.
        fa = salted(13)
        run_mode("off", [_clone_rows(c) for c in fa], fresh=True)
        run_mode("on", [_clone_rows(c) for c in fa], fresh=True)
        fpairs: list = []
        out_foff = out_fon = None
        for _rep in range(3):
            out_foff, fo = run_mode(
                "off", [_clone_rows(c) for c in fa], fresh=True
            )
            out_fon, fn_ = run_mode(
                "on", [_clone_rows(c) for c in fa], fresh=True
            )
            fpairs.append((fo, fn_))
        fresh_off, fresh_on = median_pair(fpairs)
        identical = identical and _verdicts_equal(out_foff, out_fon)
        sched_snap = eng.scheduler().stats.snapshot()
    finally:
        eng.pipeline = prior_mode
    log(
        f"pipeline A/B ({n_chunks}x{chunk_rows} rows/chunk): steady "
        f"off {steady_off['rows_per_sec']:.0f} -> on "
        f"{steady_on['rows_per_sec']:.0f} rows/s; fresh off "
        f"{fresh_off['rows_per_sec']:.0f} -> on "
        f"{fresh_on['rows_per_sec']:.0f} rows/s (walk "
        f"{fresh_off['walk_rows_per_sec']:.0f} -> "
        f"{fresh_on['walk_rows_per_sec']:.0f}); verdicts "
        f"{'identical' if identical else 'MISMATCH'}"
    )
    return {
        "chunk_rows": chunk_rows,
        "n_chunks": n_chunks,
        "steady": {"off": steady_off, "on": steady_on},
        "fresh": {"off": fresh_off, "on": fresh_on},
        "verdicts_identical": bool(identical),
        "sched": sched_snap,  # bucket fill + prefetch stall counters
    }


def bench_dispatch_ab(db, n_batches: int = 3, reps: int = 3) -> dict:
    """Paired A/B of the production dispatch (staging pool + donated
    buffers + survivor-compacted phase B, docs/DEVICE_MATCH.md) against
    the legacy fused single-kernel arm — same corpus, same fresh
    encoded batches, device path only (no host walk), so the ratio
    isolates what the ISSUE-6 tentpole changed. Interleaved paired
    repeats with the median-ratio pair reported (host drift hits both
    sides of a pair alike and cancels); every repeat's fused planes are
    compared bit for bit — a dispatch variant that changed results
    would be a bug, not a speedup."""
    import time as _time

    from swarm_tpu.ops.encoding import encode_batch
    from swarm_tpu.ops.match import DeviceDB

    rows_n = min(ROWS, 512)
    rng = np.random.default_rng(777)
    batches = []
    for i in range(n_batches):
        rows = realistic_rows(rows_n, seed=500 + i)
        for r in rows:
            salt = bytes(rng.integers(97, 123, size=48, dtype=np.uint8))
            r.body = b"<!-- %s -->" % salt + r.body
        batches.append(
            encode_batch(
                rows, max_body=MAX_BODY, max_header=MAX_HEADER,
                pad_rows_to=rows_n,
            )
        )
    new = DeviceDB(db)  # compaction + donation (production defaults)
    old = DeviceDB(db, compact=False, donate=False)  # legacy fused arm

    def run(dev):
        t0 = _time.perf_counter()
        outs = [
            dev.match(b.streams, b.lengths, b.status, full=True)
            for b in batches
        ]
        return outs, (_time.perf_counter() - t0) * 1e3 / n_batches

    run(new)  # compile + warm both arms outside the timing
    run(old)
    identical = True
    pairs: list = []
    for _rep in range(reps):
        outs_o, ms_o = run(old)
        outs_n, ms_n = run(new)
        pairs.append((ms_o, ms_n))
        for po, pn in zip(outs_o, outs_n):
            for a, b in zip(po, pn):
                if not np.array_equal(np.asarray(a), np.asarray(b)):
                    identical = False
    pairs.sort(key=lambda p: p[0] / max(p[1], 1e-9))
    ms_o, ms_n = pairs[len(pairs) // 2]
    # the identity gate is REAL: a plane mismatch means the compacted
    # path is a correctness bug, so report no speedup at all (0.0 tanks
    # the vs_baseline ratio instead of celebrating broken output)
    speedup = ms_o / max(ms_n, 1e-9) if identical else 0.0
    lc = dict(new.last_compact)
    log(
        f"dispatch A/B ({n_batches}x{rows_n} rows): legacy "
        f"{ms_o:.1f} ms/batch -> compacted+donated {ms_n:.1f} ms/batch "
        f"({speedup:.2f}x; phase B at k={lc.get('verify_k')} of budget "
        f"{lc.get('budget')}); planes "
        f"{'identical' if identical else 'MISMATCH'}"
    )
    return {
        "rows": rows_n,
        "n_batches": n_batches,
        "legacy_ms_per_batch": round(ms_o, 3),
        "compacted_ms_per_batch": round(ms_n, 3),
        "speedup": round(speedup, 3),
        "identical": bool(identical),
        # the "phase B launches at survivor size" evidence
        "last_compact": lc,
    }


#: long enough to overflow the device's 64-byte exact-verify window
#: (fingerprints/compile.VERIFY_WIDTH) — hits are prefix-verified and
#: stay uncertain, exactly the reference corpus's long-word shape
_STRESS_LONG_A = "X" * 28 + "-acme-enterprise-stress-banner-edition-" + "Y" * 28
_STRESS_LONG_B = "Q" * 24 + "-community-stress-footer-build-string-" + "Z" * 24
_STRESS_CI = "sTreSs-CI-bRaNd-MaRkEr-" + "w" * 48


def walk_stress_templates() -> list:
    """Synthetic confirm-heavy templates modeled on the REAL corpus
    shapes that dominate the reference host walk (long prefix-verified
    words, case-insensitive words, multi-pattern regex matchers with
    extractors, negative regex, binary needles, a credentials-
    disclosure-shaped extractor-only op). The bundled demo corpus's
    words all fit the 64-byte device verify window, so on its own it
    produces ~zero uncertain pairs — these templates restore the
    uncertainty profile the fresh-content walk actually resolves, so
    the walk A/B measures the bottleneck the metric names."""
    from swarm_tpu.fingerprints.model import (
        Extractor, Matcher, Operation, Template,
    )

    return [
        Template(id="stress-long-word", protocol="http", operations=[
            Operation(matchers=[
                Matcher(type="word", part="body",
                        words=[_STRESS_LONG_A, _STRESS_LONG_B]),
            ]),
        ]),
        Template(id="stress-long-word-and", protocol="http", operations=[
            Operation(matchers=[
                Matcher(type="word", part="body",
                        words=[_STRESS_LONG_A, _STRESS_LONG_B],
                        condition="and"),
            ]),
        ]),
        Template(id="stress-ci-word", protocol="http", operations=[
            Operation(matchers=[
                Matcher(type="word", part="body", words=[_STRESS_CI],
                        case_insensitive=True),
            ]),
        ]),
        Template(id="stress-regex", protocol="http", operations=[
            Operation(
                matchers=[
                    Matcher(type="regex", part="body", regex=[
                        r"stress-version: (\d+\.\d+\.\d+)",
                        r"stress-edition: (enterprise|community)",
                    ]),
                ],
                extractors=[
                    Extractor(type="regex", part="body", group=1, regex=[
                        r"stress-version: (\d+\.\d+\.\d+)",
                    ]),
                ],
            ),
        ]),
        Template(id="stress-neg-regex", protocol="http", operations=[
            Operation(
                matchers_condition="and",
                matchers=[
                    Matcher(type="word", part="body",
                            words=["stress-edition"]),
                    Matcher(type="regex", part="body", negative=True,
                            regex=[r"stress-disabled:\s*true"]),
                ],
            ),
        ]),
        Template(id="stress-binary", protocol="http", operations=[
            Operation(matchers=[
                # 'stress-bin' with embedded whitespace (normalized by
                # the oracle's re.sub before unhexlify)
                Matcher(type="binary", part="body",
                        binary=["73747265 7373 2d62696e"]),
            ]),
        ]),
        Template(id="stress-tokens", protocol="http", operations=[
            # extractor-only op: verdict IS "any extraction non-empty"
            # (the credentials-disclosure shape — lowered as
            # per-pattern extraction prefilters)
            Operation(extractors=[
                Extractor(type="regex", part="body", group=0, regex=[
                    r"stress_key_[a-z0-9]{8}",
                    r"stress_tok_[A-Z]{4}\d{4}",
                    r"stress_secret=[0-9a-f]{12}",
                ] + [
                    # pattern population (the credentials family is
                    # ~689 patterns; a couple dozen keeps the smoke
                    # fast while the per-pattern prefilter shape holds)
                    rf"stress_cred_{tag}_[a-z0-9]{{10}}"
                    for tag in (
                        "aws", "gcp", "azure", "slack", "github",
                        "gitlab", "stripe", "twilio", "mailgun", "jwt",
                        "pgsql", "mysql", "redis", "mongo", "ftp",
                        "smtp",
                    )
                ]),
            ]),
        ]),
    ] + [
        # per-service detection family: each template is a long
        # prefix-verified word plus a versioned regex with extractor —
        # the tech-detection shape that fires on most fleet rows
        Template(id=f"stress-svc-{k}", protocol="http", operations=[
            Operation(
                matchers=[
                    Matcher(type="word", part="body", words=[
                        f"stress-service-{k}-" + "m" * 56,
                    ]),
                    Matcher(type="regex", part="body", regex=[
                        rf"stress-svc{k}/(\d+\.\d+)",
                        rf"stress-svc{k}-build-([a-f0-9]+)",
                    ]),
                ],
                extractors=[
                    Extractor(type="regex", part="body", group=1, regex=[
                        rf"stress-svc{k}/(\d+\.\d+)",
                    ]),
                ],
            ),
        ])
        for k in range(8)
    ]


def walk_stress_rows(n: int, seed: int = 7) -> list:
    """Realistic response mix with the walk-stress markers embedded on
    a fixed cycle (plus a per-row salt so every row is fresh content):
    roughly half the rows fire at least one stress template, the rest
    are ordinary fleet filler."""
    rows = realistic_rows(n, seed=seed)
    rng = np.random.default_rng(seed * 31 + 5)
    for i, r in enumerate(rows):
        salt = bytes(rng.integers(97, 123, size=48, dtype=np.uint8))
        parts = []
        k = i % 8
        if k in (0, 1):
            parts.append(_STRESS_LONG_A.encode())
            if k == 1:
                parts.append(_STRESS_LONG_B.encode())
        elif k == 2:
            # random-case CI hit (bytes.lower() on both sides decides)
            cased = "".join(
                c.upper() if rng.integers(0, 2) else c.lower()
                for c in _STRESS_CI
            )
            parts.append(cased.encode())
        elif k == 3:
            parts.append(
                b"stress-version: 4.%d.1 stress-edition: enterprise"
                % (i % 30)
            )
        elif k == 4:
            parts.append(b"stress-bin blob stress-edition: community")
        elif k == 5:
            parts.append(
                b"stress_key_ab12cd34 stress_tok_ABCD1234 "
                b"stress_secret=0123456789ab stress_cred_aws_q1w2e3r4t5 "
                b"stress_cred_github_a1b2c3d4e5"
            )
        # most rows also look like a detected service (the fleet-wide
        # tech-detection shape): 2-3 per-service families fire per row
        if k != 6:
            for k2 in range(i % 3 + 1):
                svc = (i + k2 * 3) % 8
                parts.append(
                    b"stress-service-%d-" % svc + b"m" * 56
                    + b" stress-svc%d/%d.%d stress-svc%d-build-%x"
                    % (svc, i % 9, i % 7, svc, 0xA0 + i % 60)
                )
        # k == 6: plain fleet filler (no stress content)
        filler = bytes(rng.integers(97, 123, size=384, dtype=np.uint8))
        # clamp under the bench's max_body: a clipped row would take the
        # whole-row oracle redo (a different, slower walk path) and
        # swamp the confirm phase this workload exists to exercise
        r.body = (b"<!-- %s --><!-- %s -->%s" % (
            salt, filler, b" ".join(parts)
        ) + r.body)[:2000]
    return rows


def bench_walk_ab(
    base_templates, n_rows: int = 0, n_batches: int = 3, reps: int = 3,
    threads=None,
) -> dict:
    """Paired A/B of the fresh-content host walk: the serial reference
    walk (``walk_threads=0``) vs the row-parallel batched walk
    (docs/HOST_WALK.md), SAME engine, same content, interleaved
    repeats with the median-ratio pair reported (the pipeline A/B's
    drift-cancelling scheme). Verdicts, extraction values AND
    host-confirm accounting must be identical on every repeat — a walk
    mode that changed any of them would be a bug, not a result. The
    feed is the corpus plus the walk-stress templates, so the confirm
    load matches what the 400k rows/s bar actually measures."""
    import time as _time

    from swarm_tpu.ops.engine import MatchEngine

    n_rows = n_rows or min(ROWS, 512)
    templates = list(base_templates) + walk_stress_templates()
    eng = MatchEngine(
        templates, mesh=None, batch_rows=n_rows, max_body=MAX_BODY,
        max_header=MAX_HEADER, walk_threads=threads,
    )
    threads_eff = eng.walk_threads
    batches = [
        walk_stress_rows(n_rows, seed=7000 + i) for i in range(n_batches)
    ]
    eng.match_packed(batches[0])  # warm the jit shapes outside timing

    def run(mode_threads):
        eng.configure_walk(mode_threads)
        eng.clear_content_memos()
        h0 = eng.stats.host_confirm_seconds
        c0 = eng.stats.host_confirm_pairs
        outs = []
        for b in batches:
            p = eng.match_packed(b)
            # bits may alias the recycled verdict-plane pool: snapshot
            # before the next encode can overwrite it
            outs.append((p.bits.copy(), dict(p.extractions),
                         list(p.host_always_matches)))
        walk = eng.stats.host_confirm_seconds - h0
        pairs = eng.stats.host_confirm_pairs - c0
        rate = n_rows * n_batches / walk if walk > 0 else 0.0
        return outs, {"walk_rows_per_sec": round(rate, 1),
                      "confirm_pairs": pairs}

    def identical(a, b) -> bool:
        return all(
            np.array_equal(xa[0], xb[0]) and xa[1] == xb[1]
            and xa[2] == xb[2]
            for xa, xb in zip(a, b)
        )

    pairs_list = []
    ok = True
    for _rep in range(reps):
        out_s, rs = run(0)
        out_b, rb = run(threads)
        ok = ok and identical(out_s, out_b)
        ok = ok and rs["confirm_pairs"] == rb["confirm_pairs"]
        pairs_list.append((rs, rb))
    eng.configure_walk(threads)
    pairs_list.sort(
        key=lambda p: p[1]["walk_rows_per_sec"]
        / max(p[0]["walk_rows_per_sec"], 1e-9)
    )
    # lower median on even rep counts: picking len//2 would report the
    # HIGHER of two ratios (best-of-N, not a median) — the smoke runs
    # reps=2 and its recorded trend metric must not inflate on noise
    serial, batched = pairs_list[(len(pairs_list) - 1) // 2]
    speedup = batched["walk_rows_per_sec"] / max(
        serial["walk_rows_per_sec"], 1e-9
    )
    stats = eng.stats
    log(
        f"walk A/B ({n_batches}x{n_rows} rows, {threads_eff} threads): "
        f"serial {serial['walk_rows_per_sec']:.0f} -> batched "
        f"{batched['walk_rows_per_sec']:.0f} rows/s ({speedup:.2f}x, "
        f"{serial['confirm_pairs']} confirm pairs/run); results "
        f"{'identical' if ok else 'MISMATCH'}"
    )
    return {
        "rows": n_rows,
        "n_batches": n_batches,
        "walk_threads": threads_eff,
        "serial": serial,
        "batched": batched,
        "speedup": round(speedup, 3),
        "identical": bool(ok),
        "walk_batched_pairs": stats.walk_batched_pairs,
        "walk_batch_rounds": stats.walk_batch_rounds,
    }


_WF_BENCH_N = 24


def workflow_stress_templates(n_workflows: int = _WF_BENCH_N) -> list:
    """Synthetic workflow-heavy corpus slice: every workflow is the
    reference shape (a tech-detection trigger with NAMED matchers, a
    tag-selected and a path-selected subtemplate behind the gates), so
    the lowering exercises WFC_MATCHER conds, tag expansion and path
    refs at fleet scale — the bundled demo corpus carries exactly ONE
    workflow, which would measure dispatch overhead, not gating."""
    from swarm_tpu.fingerprints.model import Matcher, Operation, Template

    out = []
    for k in range(n_workflows):
        out.append(Template(
            id=f"wfb-tech-{k}", protocol="http",
            source_path=f"http/wfb-tech-{k}.yaml", tags=["wfbtech"],
            operations=[Operation(matchers_condition="or", matchers=[
                Matcher(type="word", part="body", name=f"wfb-cms-{k}",
                        words=[f"powered by WfBench{k} engine"]),
                Matcher(type="regex", part="header", name=f"wfb-hdr-{k}",
                        regex=[rf"X-WfBench{k}: [0-9]+\.[0-9]+"]),
            ])],
        ))
        out.append(Template(
            id=f"wfb-vuln-{k}", protocol="http",
            source_path=f"http/wfb-vuln-{k}.yaml", tags=[f"wfb{k}"],
            operations=[Operation(matchers_condition="and", matchers=[
                Matcher(type="word", part="body",
                        words=[f"powered by WfBench{k} engine"]),
                Matcher(type="word", part="body",
                        words=["wfb-debug-build"]),
            ])],
        ))
        out.append(Template(
            id=f"wfb-panel-{k}", protocol="http",
            source_path=f"http/wfb-panel-{k}.yaml", tags=[f"wfb{k}"],
            operations=[Operation(matchers=[
                Matcher(type="word", part="body",
                        words=[f"WfBench{k} admin console"]),
            ])],
        ))
        out.append(Template(
            id=f"wfb-flow-{k}", protocol="workflow",
            source_path=f"workflows/wfb-flow-{k}.yaml",
            extra={"workflows": [{
                "template": f"http/wfb-tech-{k}.yaml",
                "matchers": [
                    {"name": f"wfb-cms-{k}",
                     "subtemplates": [{"tags": f"wfb{k}"}]},
                    {"name": f"wfb-hdr-{k}",
                     "subtemplates": [
                         {"template": f"http/wfb-vuln-{k}.yaml"},
                     ]},
                ],
            }]},
        ))
    return out


def workflow_stress_rows(
    n: int, n_workflows: int = _WF_BENCH_N, seed: int = 7
) -> list:
    """Fleet mix where most rows carry one workflow's trigger content
    (the body OR the header named-matcher alternative) and many also
    carry subtemplate markers, plus plain filler — every row salted so
    the feed is fresh content, the case the gate planes serve."""
    rows = realistic_rows(n, seed=seed)
    rng = np.random.default_rng(seed * 17 + 3)
    for i, r in enumerate(rows):
        salt = bytes(rng.integers(97, 123, size=40, dtype=np.uint8))
        k = i % n_workflows
        shape = i % 5
        parts = []
        if shape in (0, 1, 2):  # body-trigger rows
            parts.append(b"powered by WfBench%d engine" % k)
            if shape != 2:
                parts.append(b"wfb-debug-build")  # the vuln sub fires
            if shape == 1:
                parts.append(b"WfBench%d admin console" % k)
        elif shape == 3:  # header-trigger alternative
            r.header = (r.header or b"") + (
                b"\r\nX-WfBench%d: %d.%d" % (k, i % 9, i % 7)
            )
            parts.append(b"wfb-debug-build")
        # shape 4: plain fleet filler — no trigger fires
        r.body = (
            b"<!-- %s -->%s " % (salt, b" ".join(parts)) + r.body
        )[:2000]
    return rows


def bench_workflow_ab(
    base_templates, n_rows: int = 0, n_batches: int = 3, reps: int = 3,
    n_workflows: int = _WF_BENCH_N,
) -> dict:
    """Paired interleaved A/B of workflow gating (docs/WORKFLOWS.md):
    the host-twin reference (``device=False``) vs device gate planes
    (``device=True``) sharing ONE engine over the same workflow-heavy
    fresh fleet. Per-row result dicts must be equal on EVERY repeat —
    the rc gate; the median-ratio pair is reported (the pipeline/walk
    A/Bs' drift-cancelling scheme). Runner L1 memos and engine content
    memos are cleared before every arm so both arms pay the identical
    fresh-dispatch cost and the measured delta is the gating stage."""
    import time as _time

    from swarm_tpu.ops.engine import MatchEngine
    from swarm_tpu.ops.workflows import WorkflowRunner

    n_rows = n_rows or min(ROWS, 512)
    templates = list(base_templates) + workflow_stress_templates(n_workflows)
    eng = MatchEngine(
        templates, mesh=None, batch_rows=n_rows, max_body=MAX_BODY,
        max_header=MAX_HEADER,
    )
    dev = WorkflowRunner(templates, engine=eng, device=True)
    twin = WorkflowRunner(templates, engine=eng, device=False)
    if dev.plan is None or not dev.device:
        raise RuntimeError("workflow A/B: no lowered gate planes")
    batches = [
        workflow_stress_rows(n_rows, n_workflows, seed=9100 + i)
        for i in range(n_batches)
    ]
    dev.run(batches[0])  # warm the jit shapes outside timing

    def run(runner):
        eng.clear_content_memos()
        with runner._memo_lock:
            runner._wf_memo.clear()
        t0 = _time.perf_counter()
        outs = [runner.run(b) for b in batches]
        dt = _time.perf_counter() - t0
        return outs, (n_rows * n_batches / dt if dt > 0 else 0.0)

    pairs = []
    ok = True
    fired_rows = 0
    for _rep in range(reps):
        out_t, rate_t = run(twin)
        out_d, rate_d = run(dev)
        ok = ok and out_t == out_d  # per-row dict equality, every repeat
        fired_rows = sum(1 for b in out_d for per in b if per)
        pairs.append((rate_t, rate_d))
    # lower median on even rep counts (see bench_walk_ab): never report
    # best-of-N as the trend metric
    pairs.sort(key=lambda p: p[1] / max(p[0], 1e-9))
    rate_t, rate_d = pairs[(len(pairs) - 1) // 2]
    speedup = rate_d / max(rate_t, 1e-9)
    log(
        f"workflow A/B ({n_batches}x{n_rows} rows, "
        f"{len(dev.workflows)} workflows, {int(dev.plan.num_terms)} "
        f"lowered terms): twin {rate_t:.0f} -> device {rate_d:.0f} "
        f"rows/s ({speedup:.2f}x, {fired_rows} workflow-firing rows); "
        f"results {'identical' if ok else 'MISMATCH'}"
    )
    return {
        "rows": n_rows,
        "n_batches": n_batches,
        "workflows": len(dev.workflows),
        "host_only_workflows": len(dev.plan.host_only_ids),
        "lowered_terms": int(dev.plan.num_terms),
        "workflow_firing_rows": fired_rows,
        "twin_rows_per_sec": round(rate_t, 1),
        "device_rows_per_sec": round(rate_d, 1),
        "speedup": round(speedup, 3),
        "identical": bool(ok),
    }


def bench_dedup_fleet(
    templates, db=None, n_rows: int = 0, overlap: float = 0.94,
    reps: int = 3,
) -> dict:
    """Fleet-replay dedup scenario (docs/CACHING.md): two SEQUENTIAL
    engine lifetimes — a fresh ``MatchEngine`` per lifetime, so the L1
    verdict memo dies with each one exactly like a worker restart —
    scanning overlapping content through the shared content-addressed
    result tier. Lifetime 1 populates the tier; lifetime 2 (L1 cold,
    tier warm) re-scans ``overlap`` of the same contents plus a
    never-seen tail, paired against an IDENTICAL lifetime without the
    tier on clone rows. Every row's content is salted unique WITHIN a
    lifetime, so neither in-batch dedup nor the L1 can help — the
    measured win is the shared tier's alone (the internet-scan shape:
    thousands of hosts serving pages some other worker already
    resolved). Warm-lifetime clients are read-only (``writeback=off``)
    so every repeat sees the same tier state and the hit ratio stays
    the scenario's, not an artifact of earlier repeats. Interleaved
    paired repeats, median-ratio pair reported, verdict identity
    asserted on every repeat AND on the seeding lifetime — a mismatch
    zeroes the speedup (a perf mode that changed results is a bug,
    not a result)."""
    import time as _time

    from swarm_tpu.cache import ResultCacheClient, SharedResultTier
    from swarm_tpu.ops.engine import MatchEngine
    from swarm_tpu.stores import MemoryBlobStore, MemoryStateStore

    n_rows = n_rows or max(ROWS, 256)
    rng = np.random.default_rng(4242)

    def salt(rows, tag):
        """Unique-content rows in ONE width class: bodies are capped
        so every batch — including the warm arm's miss-subset batch,
        whose width is the max over only the rows the tier did NOT
        serve — compiles to the same shape. Without the cap, a
        data-dependent narrower subset batch would XLA-compile inside
        the timed window and the measurement would be a compile, not
        the tier."""
        for i, r in enumerate(rows):
            s = bytes(rng.integers(97, 123, size=40, dtype=np.uint8))
            r.body = (
                b"<!-- %s-%d %s -->" % (tag, i, s) + r.body
            )[:448]
        return rows

    def resalt(rows):
        """Fresh-content clones at EXACTLY the original lengths: the
        40-byte salt region is overwritten in place, so the warmup
        exercises the identical width classes and batch shapes the
        timed feed will use (nothing left to compile inside the timed
        window) while every content digest is new."""
        out = _clone_rows(rows)
        for r in out:
            s = bytes(rng.integers(97, 123, size=40, dtype=np.uint8))
            r.body = r.body[:5] + s + r.body[45:]
        return out

    base = salt(realistic_rows(n_rows, seed=77), b"host")
    n_over = max(1, int(n_rows * overlap))
    tail = salt(realistic_rows(n_rows - n_over, seed=99), b"fresh")
    feed2 = base[:n_over] + tail
    # chunked feed (the worker's real input shape): a fleet-known
    # chunk short-circuits its WHOLE device batch, so the tier's win
    # scales with the dedup fraction instead of disappearing into one
    # batch's fixed dispatch cost
    batch_rows = max(64, n_rows // 8)

    def lifetime(rows, client):
        """One engine lifetime: fresh engine (cold L1), untimed
        same-shape warmup on re-salted clone content (trace/compile
        and first-touch costs excluded from BOTH arms — the scenario
        measures steady serving, and the persistent XLA cache makes a
        production restart's compile near-free anyway), then the timed
        scan. Returns (results, wall, counters-delta-fn)."""
        eng = MatchEngine(
            templates, mesh=None, batch_rows=batch_rows,
            max_body=MAX_BODY, max_header=MAX_HEADER, db=db,
        )
        if client is not None:
            eng.attach_result_cache(client)
        eng.match(resalt(rows))
        c0 = client.counters() if client is not None else None
        rows = _clone_rows(rows)
        t0 = _time.perf_counter()
        out = eng.match(rows)
        wall = _time.perf_counter() - t0
        delta = None
        if client is not None:
            c1 = client.counters()
            # VERDICT-family outcomes only: the gated ratio is "rows
            # served by the tier", and confirm-part digests from the
            # fresh tail's walk would dilute the denominator
            delta = {
                k: c1[k] - c0[k]
                for k in ("verdict_hits", "verdict_misses")
            }
        return out, wall, delta

    out_base, _w, _d = lifetime(base, None)

    tier = SharedResultTier(MemoryStateStore(), MemoryBlobStore())
    out_seed, seed_wall, _d = lifetime(
        base, ResultCacheClient(tier, worker_id="bench-seed")
    )
    identical = _verdicts_equal(out_seed, out_base)

    pairs: list = []
    hit_ratio = 0.0
    for rep in range(reps):
        out_off, wall_off, _d = lifetime(feed2, None)
        client = ResultCacheClient(
            tier, worker_id=f"bench-warm-{rep}", writeback=False
        )
        out_on, wall_on, delta = lifetime(feed2, client)
        total = delta["verdict_hits"] + delta["verdict_misses"]
        hit_ratio = delta["verdict_hits"] / total if total else 0.0
        identical = identical and _verdicts_equal(out_off, out_on)
        pairs.append((wall_off, wall_on))
    pairs.sort(key=lambda p: p[0] / max(p[1], 1e-9))
    # lower-middle on even rep counts: never report the lucky rep
    wall_off, wall_on = pairs[(len(pairs) - 1) // 2]
    speedup = wall_off / max(wall_on, 1e-9) if identical else 0.0
    log(
        f"dedup fleet replay ({n_rows} rows, overlap {overlap:.0%}): "
        f"lifetime-2 tier-off {wall_off * 1e3:.1f} ms -> tier-on "
        f"{wall_on * 1e3:.1f} ms ({speedup:.2f}x), shared hit ratio "
        f"{hit_ratio:.3f}; verdicts "
        f"{'identical' if identical else 'MISMATCH'}"
    )
    return {
        "rows": n_rows,
        "overlap": overlap,
        "lifetime1_wall_s": round(seed_wall, 4),
        "cold_wall_s": round(wall_off, 4),
        "warm_wall_s": round(wall_on, 4),
        "speedup": round(speedup, 3),
        "hit_ratio": round(hit_ratio, 4),
        "identical": bool(identical),
    }


def bench_exact_engine(templates, db=None) -> tuple:
    # → (steady_rows_per_sec, fresh_floor_rows_per_sec,
    #    fresh_host_walk_rows_per_sec, MatchEngine, engine_stats_snapshot,
    #    device_record)  — device_record carries the two-phase kernel's
    #    headline times: first-shape compile seconds and per-fresh-batch
    #    device ms (ISSUE 3 BENCH trajectory metrics)
    from swarm_tpu.ops.engine import MatchEngine

    eng = MatchEngine(
        templates,
        mesh=None,
        batch_rows=ROWS,
        max_body=MAX_BODY,
        max_header=MAX_HEADER,
        db=db,
    )
    nb = 4 if ROWS >= 1024 else 2  # fewer distinct batches at smoke size
    warm = [realistic_rows(ROWS, seed=s) for s in range(nb)]
    t0 = time.time()
    eng.match_packed(warm[0])
    first_batch_s = time.time() - t0
    # compile attribution from the DeviceDB spy: wall time of dispatches
    # that built a new executable (first width bucket = the cold cost a
    # worker pays per corpus; the args kernel makes it corpus-free)
    compile_s = getattr(eng.device, "compile_seconds", 0.0) or first_batch_s
    log(
        f"engine compile+first batch: {first_batch_s:.1f}s "
        f"(device compile {compile_s:.1f}s, "
        f"{getattr(eng.device, 'compile_count', 0)} executables)"
    )
    for b in warm:
        eng.match_packed(b)  # warm every shape/content path
    # the timed batches repeat the warm CONTENT through fresh objects —
    # the production pattern (every chunk parses new bytes), so the
    # memo's full-compare cost is measured, not skipped via the
    # same-object shortcut
    from swarm_tpu.fingerprints.model import Response as _R

    batches = [
        [
            _R(
                host=r.host, port=r.port, status=r.status,
                body=bytes(memoryview(r.body)),
                header=bytes(memoryview(r.header)),
                banner=None if r.banner is None
                else bytes(memoryview(r.banner)),
            )
            for r in b
        ]
        for b in warm
    ]
    # pipelined feed (the production shape): encode batch i+1 on a
    # helper thread while the device matches batch i — the host encode
    # is the end-to-end ceiling at device rates
    from concurrent.futures import ThreadPoolExecutor

    t0 = time.perf_counter()
    n = 0
    with ThreadPoolExecutor(max_workers=1) as pool:
        # reuse_buffers: the 1-deep pipeline is exactly the recycled-
        # pool-safe pattern (each pre is matched before the next encode)
        fut = pool.submit(eng.encode_packed, batches[0], True)
        for i in range(ITERS):
            pre = fut.result()
            if i + 1 < ITERS:  # no unconsumed encode inside the timing
                fut = pool.submit(
                    eng.encode_packed, batches[(i + 1) % len(batches)], True
                )
            eng.match_packed(batches[i % len(batches)], pre=pre)
            n += ROWS
    dt = time.perf_counter() - t0
    s = eng.stats
    log(
        f"exact engine: {n} rows in {dt:.2f}s "
        f"(host confirms {s.host_confirm_pairs}, "
        f"host {s.host_confirm_seconds:.2f}s, device {s.device_seconds:.2f}s)"
    )

    # fresh-content floor: every ROW is unique content (per-row random
    # filler defeats in-batch dedup AND the cross-batch memos, which
    # are also cleared first) — the adversarial bound the steady-state
    # number amortizes from as fleet content recurs
    import numpy as _np

    fresh_iters = max(ITERS // 4, 2)
    rng = _np.random.default_rng(4242)
    fresh = []
    for i in range(fresh_iters + 1):  # +1: warm batch outside the timing
        batch_rows = realistic_rows(ROWS, seed=1000 + i)
        for r in batch_rows:
            salt = bytes(
                rng.integers(97, 123, size=48, dtype=_np.uint8)
            )
            r.body = b"<!-- %s -->" % salt + r.body
        fresh.append(batch_rows)
    eng.clear_content_memos()
    eng.match_packed(fresh[0])  # warm any new jit width bucket
    h0 = eng.stats.host_confirm_seconds
    d0 = eng.stats.device_seconds
    t0 = time.perf_counter()
    for b in fresh[1:]:
        tb = time.perf_counter()
        eng.match_packed(b)
        log(f"  fresh batch: {(time.perf_counter() - tb) * 1e3:.1f} ms")
    fresh_wall = time.perf_counter() - t0
    fresh_rate = fresh_iters * ROWS / fresh_wall
    log(f"fresh-content floor: {fresh_rate:.0f} rows/s")
    # per-fresh-batch times: TOTAL wall (like-for-like with the
    # pre-change 14.2 s baseline) and the device half (dispatch +
    # blocking fused read — the milliseconds the two-phase kernel is
    # accountable for; tracked against itself across BENCH_* records)
    fresh_batch_ms = fresh_wall / fresh_iters * 1e3
    fresh_device_ms = (
        (eng.stats.device_seconds - d0) / fresh_iters * 1e3
    )
    log(
        f"fresh batch: {fresh_batch_ms:.1f} ms total, "
        f"{fresh_device_ms:.1f} ms device"
    )
    # the floor's host-walk component, reported on its own so the
    # walk's share of the fresh rate is separable from the device's
    walk_s = eng.stats.host_confirm_seconds - h0
    fresh_walk_rate = fresh_iters * ROWS / walk_s if walk_s > 0 else 0.0
    log(f"fresh-content host walk: {fresh_walk_rate:.0f} rows/s")
    # per-phase attribution of one fresh-shaped batch → the headline's
    # device_phase_ms map (BENCH_* records show which phase a device
    # change moved — the ISSUE-6 attribution requirement)
    from swarm_tpu.ops.encoding import encode_batch as _encode_batch

    prof_n = min(ROWS, 256)
    pb = _encode_batch(
        fresh[-1][:prof_n], max_body=MAX_BODY, max_header=MAX_HEADER,
        pad_rows_to=prof_n,
    )
    phases = eng.device.profile_phases(pb.streams, pb.lengths, pb.status)
    log(
        "device phase ms: "
        + "  ".join(f"{k}={v:.2f}" for k, v in phases.items())
    )
    # kernel-counter snapshot riding along in the emitted JSON: BENCH_*
    # files carry device/host/memo counters from now on (telemetry PR)
    from swarm_tpu.telemetry.engine_export import engine_stats_snapshot

    stats_snap = engine_stats_snapshot(eng)
    # re-read at record time: the warm/fresh loops may have compiled
    # further width buckets after the first-batch snapshot — seconds
    # and count must cover the same set of executables
    compile_s = getattr(eng.device, "compile_seconds", 0.0) or compile_s
    device_record = {
        "device_compile_seconds": round(compile_s, 3),
        "device_compile_count": int(
            getattr(eng.device, "compile_count", 0)
        ),
        "fresh_batch_ms": round(fresh_batch_ms, 3),
        "fresh_batch_device_ms": round(fresh_device_ms, 3),
        "fresh_batch_rows": ROWS,
        "device_phase_ms": {k: round(v, 3) for k, v in phases.items()},
        # survivor-compaction evidence from the profiled batch: phase B
        # launched at verify_k of budget (docs/DEVICE_MATCH.md ladder)
        "last_compact": dict(
            getattr(eng.device, "last_compact", {}) or {}
        ),
    }
    return n / dt, fresh_rate, fresh_walk_rate, eng, stats_snap, device_record


def bench_service_classifier(db_path: str = "") -> float:
    from swarm_tpu.fingerprints.model import Response
    from swarm_tpu.ops.service import ServiceClassifier

    cl = ServiceClassifier(db_path=db_path or None)
    banners = [
        b"HTTP/1.1 200 OK\r\nServer: nginx/1.18.0\r\n\r\n<html>",
        b"SSH-2.0-OpenSSH_8.9p1 Ubuntu-3ubuntu0.1\r\n",
        b"220 mail.example.com ESMTP Postfix (Ubuntu)\r\n",
        b"HTTP/1.1 404 Not Found\r\nServer: Apache/2.4.41\r\n\r\n",
        b"+OK Dovecot ready.\r\n",
        b"220 (vsFTPd 3.0.3)\r\n",
        b"MySQL\x00\x00\x00\x0a8.0.31",
        b"", b"\x00\x00\x00\x00", b"HTTP/1.0 400 Bad Request\r\n\r\n",
    ]
    rows = [
        Response(
            host=f"198.51.100.{i % 254}",
            port=(80, 22, 25, 443, 110, 21, 3306, 8080)[i % 8],
            banner=banners[i % len(banners)],
        )
        for i in range(ROWS)
    ]
    cl.classify(rows)  # warm
    t0 = time.perf_counter()
    n = 0
    for _ in range(max(ITERS // 4, 3)):
        cl.classify(rows)
        n += ROWS
    dt = time.perf_counter() - t0
    log(f"service classifier: {n} banners in {dt:.2f}s")
    return n / dt


def bench_oracle_ab(templates) -> float:
    """BASELINE config #1's A/B shape: the same response rows through
    the pure-CPU oracle (reference-semantics module path, per-row
    Python) vs the device engine — the CPU side of the speedup ratio.
    Returns oracle rows/sec over a bounded sample."""
    from swarm_tpu.ops import cpu_ref

    rows = realistic_rows(32, seed=11)
    t0 = time.perf_counter()
    cpu_ref.match_corpus(templates, rows)
    dt = time.perf_counter() - t0
    log(f"cpu oracle: {len(rows)} rows x {len(templates)} templates in {dt:.1f}s")
    return len(rows) / dt


def bench_streaming_classifier() -> float:
    """BASELINE config #4's shape on one chip: a masscan-style banner
    stream flows through the double-buffered StreamingPipeline into the
    service classifier — producer (banner generation standing in for
    the native epoll front-end, which releases the GIL identically)
    overlaps device classification. Sustained rows/sec end to end."""
    from swarm_tpu.fingerprints.model import Response
    from swarm_tpu.ops.service import ServiceClassifier
    from swarm_tpu.worker.streaming import StreamingPipeline

    cl = ServiceClassifier()
    banners = [
        b"HTTP/1.1 200 OK\r\nServer: nginx/1.18.0\r\n\r\n<html>",
        b"SSH-2.0-OpenSSH_8.9p1 Ubuntu-3ubuntu0.1\r\n",
        b"220 mail.example.com ESMTP Postfix (Ubuntu)\r\n",
        b"@RSYNCD: 31.0\n",
        b"RFB 003.008\n",
        b"", b"\x03\x00\x00\x0b", b"HTTP/1.0 400 Bad Request\r\n\r\n",
    ]

    def probe(wave):
        # stands in for ProbeExecutor.run: wave of target lines -> rows
        return [
            Response(
                host=line,
                port=(80, 22, 25, 873, 5900, 9, 3389, 8080)[i % 8],
                banner=banners[i % len(banners)],
            )
            for i, line in enumerate(wave)
        ]

    total = ROWS * 8
    lines = [f"198.51.{i >> 8 & 255}.{i & 255}" for i in range(total)]
    wave = 4096
    pipe = StreamingPipeline(
        probe=probe, consume=cl.classify, wave_targets=wave
    )
    pipe.run(lines[:wave])  # warm the jit caches
    pipe = StreamingPipeline(
        probe=probe, consume=cl.classify, wave_targets=wave
    )
    t0 = time.perf_counter()
    out = pipe.run(lines)
    dt = time.perf_counter() - t0
    n = sum(len(w) for w in out)
    st = pipe.stats
    log(
        f"streaming classify: {n} rows in {dt:.2f}s "
        f"(probe {st.probe_seconds:.2f}s, match {st.match_seconds:.2f}s, "
        f"overlap {st.overlap_seconds:.2f}s)"
    )
    return n / dt


def bench_jarm_cluster() -> float:
    from swarm_tpu.ops import cluster

    rng = np.random.default_rng(5)
    # internet-wide framing (BASELINE config #5): batch large — the
    # per-dispatch cost amortizes over N while the O(N^2) tile kernel
    # stays device-resident
    n = 8192 if ROWS >= 1024 else 1024
    # synthetic JARM-style fingerprints: 64 base TLS stacks + per-host
    # jitter, the shape real fleet clustering sees
    alphabet = np.frombuffer(b"0123456789abcdef", dtype=np.uint8)
    base = alphabet[rng.integers(0, 16, size=(64, 62))]
    picks = base[rng.integers(0, 64, size=n)].copy()
    jitter = rng.integers(0, 62, size=n)
    picks[np.arange(n), jitter] = alphabet[rng.integers(0, 16, size=n)]
    packed = cluster.pack_strings([bytes(r) for r in picks])
    cluster.density_cluster(packed, radius=40.0)  # warm
    t0 = time.perf_counter()
    reps = max(ITERS // 4, 3)
    for _ in range(reps):
        cluster.density_cluster(packed, radius=40.0)
    dt = time.perf_counter() - t0
    log(f"jarm cluster: {reps}x{n} fingerprints in {dt:.2f}s")
    return reps * n / dt


def bench_device_only(db, dev) -> float:
    import jax

    from swarm_tpu.ops.encoding import encode_batch
    from swarm_tpu.ops.match import DeviceDB

    log(
        f"corpus: {db.stats['templates_in']} templates -> "
        f"{db.num_templates} device templates, {db.num_slots} word slots, "
        f"{db.stats['rx_matchers']} device-regex matchers, "
        f"{len(db.host_always)} host-tail"
    )
    rows = realistic_rows(ROWS, seed=11)
    batch = encode_batch(rows, max_body=MAX_BODY, max_header=MAX_HEADER)
    streams = {k: jax.device_put(v, dev) for k, v in batch.streams.items()}
    lengths = {k: jax.device_put(v, dev) for k, v in batch.lengths.items()}
    status = jax.device_put(batch.status, dev)

    # the production two-phase kernel (corpus arrays as device-resident
    # arguments — docs/DEVICE_MATCH.md), full-mode fused output
    matcher = DeviceDB(db)
    t0 = time.time()
    out = matcher.dispatch(streams, lengths, status)
    jax.block_until_ready(out)
    log(
        f"device compile+first call: {time.time() - t0:.1f}s "
        f"(compile {matcher.compile_seconds:.1f}s)"
    )
    for _ in range(WARMUP):
        out = matcher.dispatch(streams, lengths, status)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        out = matcher.dispatch(streams, lengths, status)
    jax.block_until_ready(out)
    per_batch = (time.perf_counter() - t0) / ITERS
    log(f"device steady state: {per_batch * 1e3:.2f} ms / {ROWS} rows")
    # per-phase attribution of one batch → stderr table + telemetry
    phases = matcher.profile_phases(streams, lengths, status)
    log(
        "device phase ms: "
        + "  ".join(f"{k}={v:.2f}" for k, v in phases.items())
    )
    return ROWS / per_batch


def _shard_shapes(n_dev: int) -> list:
    """Mesh shapes the sharded phase measures: the data-axis ladder
    (2, 4, … up to every device) plus one 3-axis factorization when
    the slice is big enough — the (2,2,2)/(8,1,1) pair the parity
    suite pins (tests/test_shard_serving.py)."""
    shapes = []
    r = 2
    while r <= n_dev:
        if n_dev % r == 0:
            shapes.append((r, 1, 1))
        r *= 2
    if n_dev >= 8 and n_dev % 8 == 0:
        shapes.append((2, 2, 2))
    return shapes


def bench_sharded_serving(db) -> dict:
    """Per-mesh-shape serving throughput on the mesh path
    (docs/SHARDING.md): the split-phase compacted ``ShardedMatcher``
    dispatch/collect split at in-flight depth 2, identity-gated
    against the single-device ``DeviceDB`` planes every shape. The
    data-axis scaling-efficiency figure compares rows/s at mesh
    (R,1,1) against the 1-device rate: on a real accelerator slice
    that is per-chip scaling (rate_R / (R·rate_1)); on the
    host-platform CPU mesh all "devices" share the same silicon, so
    the figure is rate_R / rate_1 — 1.0 means sharding costs nothing,
    and the ≥0.7 acceptance bounds the psum/placement overhead."""
    import jax

    from swarm_tpu.ops.encoding import encode_batch
    from swarm_tpu.ops.match import DeviceDB
    from swarm_tpu.parallel.mesh import make_mesh
    from swarm_tpu.parallel.sharded import (
        ShardedMatcher,
        max_entry_len,
        pad_streams_for_seq,
    )

    n_dev = len(jax.devices())
    platform = jax.devices()[0].platform
    record: dict = {
        "platform": platform,
        "n_devices": n_dev,
        "rows": ROWS,
        "templates": db.num_templates,
        "ok": True,
        "skipped": False,
        "per_mesh": {},
    }
    if n_dev < 2:
        log("!!! sharded phase: <2 devices visible; recording skip")
        record.update(ok=False, skipped=True, reason="<2 devices")
        return record

    rows = realistic_rows(ROWS, seed=23)
    batch = encode_batch(
        rows, max_body=MAX_BODY, max_header=MAX_HEADER, pad_rows_to=ROWS,
        width_multiple=512,
    )

    def serve_rate(matcher, streams, lengths, status):
        """Steady-state rows/s through dispatch/collect at in-flight
        depth 2 — the scheduler's serving pattern (batch i's collect
        overlaps batch i+1's dispatch)."""
        matcher.collect(
            matcher.dispatch(streams, lengths, status, full=True)
        )  # compile + warm
        for _ in range(WARMUP):
            matcher.collect(
                matcher.dispatch(streams, lengths, status, full=True)
            )
        t0 = time.perf_counter()
        pending = matcher.dispatch(streams, lengths, status, full=True)
        for _ in range(ITERS - 1):
            nxt = matcher.dispatch(streams, lengths, status, full=True)
            matcher.collect(pending)
            pending = nxt
        matcher.collect(pending)
        return ROWS * ITERS / (time.perf_counter() - t0)

    single = DeviceDB(db)
    rate_1 = serve_rate(single, batch.streams, batch.lengths, batch.status)
    want = single.match(batch.streams, batch.lengths, batch.status, full=True)
    record["single_device_rows_per_sec"] = round(rate_1, 1)
    log(f"sharded phase: 1-device serve {rate_1:.0f} rows/s")

    identical = True
    best_data = None
    for shape in _shard_shapes(n_dev):
        mesh = make_mesh(shape)
        matcher = ShardedMatcher(db, mesh)
        streams = dict(batch.streams)
        if shape[2] > 1:
            streams = {k: v.copy() for k, v in streams.items()}
            pad_streams_for_seq(streams, shape[2], max_entry_len(db))
        got = matcher.collect(
            matcher.dispatch(streams, batch.lengths, batch.status, full=True)
        )
        # identity gate: value planes bit-equal; overflow exact on
        # data-only meshes, safe-direction when the candidate space is
        # model/seq-sharded (per-rank k can only overflow less)
        shape_ok = all(
            np.array_equal(np.asarray(a), np.asarray(w))
            for a, w in zip(got[:5], want[:5])
        )
        ovf_g, ovf_w = np.asarray(got[5]), np.asarray(want[5])
        if shape[1] > 1 or shape[2] > 1:
            shape_ok = shape_ok and np.array_equal(ovf_g | ovf_w, ovf_w)
        else:
            shape_ok = shape_ok and np.array_equal(ovf_g, ovf_w)
        rate = serve_rate(matcher, streams, batch.lengths, batch.status)
        key = "x".join(str(d) for d in shape)
        record["per_mesh"][key] = {
            "rows_per_sec": round(rate, 1),
            "vs_single_device": round(rate / max(rate_1, 1e-9), 3),
            "identity": "bit-equal" if shape_ok else "MISMATCH",
            "survivor_max": matcher.last_compact.get("survivor_max"),
            "verify_k": matcher.last_compact.get("verify_k"),
            "compile_seconds": round(matcher.compile_seconds, 2),
        }
        log(
            f"sharded phase: mesh {key} serve {rate:.0f} rows/s "
            f"({rate / max(rate_1, 1e-9):.2f}x 1-device); planes "
            f"{'identical' if shape_ok else 'MISMATCH'}"
        )
        identical = identical and shape_ok
        if shape[1] == 1 and shape[2] == 1:
            if best_data is None or rate > best_data[1]:
                best_data = (shape[0], rate)

    # weak scaling: FIXED rows per data rank, growing R — the
    # strong-scaling ladder above holds total rows constant so
    # per-rank batches shrink with R, which conflates sharding
    # overhead with small-batch inefficiency; this sweep keeps every
    # rank's batch at the per-rank sweet spot, so any falloff is
    # attributable to collectives/placement alone and regressions
    # show on the host-platform mesh before TPU time is spent. The
    # per-shape table below is what tools/shard_floor.json pins.
    rows_per_rank = max(256, ROWS // 4)
    weak: dict = {"rows_per_rank": rows_per_rank, "per_mesh": {}}
    base_rows = realistic_rows(rows_per_rank, seed=29)
    base_batch = encode_batch(
        base_rows, max_body=MAX_BODY, max_header=MAX_HEADER,
        pad_rows_to=rows_per_rank, width_multiple=512,
    )
    # serve_rate counts ROWS per iteration; rescale to each sweep
    # batch's real row count
    rate_1w = (
        serve_rate(
            single, base_batch.streams, base_batch.lengths,
            base_batch.status,
        )
        * rows_per_rank
        / ROWS
    )
    weak["single_device_rows_per_sec"] = round(rate_1w, 1)
    basis = ""
    for shape in _shard_shapes(n_dev):
        # rows scale with the DATA axis only: model/seq ranks partition
        # the candidate space / stream width, not the batch, so fixed
        # rows-per-data-rank is the weak-scaling contract on every
        # shape — the (2,2,2) entry isolates the halo+psum cost the
        # fused single-round exchange is supposed to keep flat
        R = shape[0]
        wrows = realistic_rows(rows_per_rank * R, seed=29)
        wbatch = encode_batch(
            wrows, max_body=MAX_BODY, max_header=MAX_HEADER,
            pad_rows_to=rows_per_rank * R, width_multiple=512,
        )
        wstreams = dict(wbatch.streams)
        if shape[2] > 1:
            wstreams = {k: v.copy() for k, v in wstreams.items()}
            pad_streams_for_seq(wstreams, shape[2], max_entry_len(db))
        matcher = ShardedMatcher(db, make_mesh(shape))
        wrate = (
            serve_rate(
                matcher, wstreams, wbatch.lengths, wbatch.status
            )
            * (rows_per_rank * R)
            / ROWS
        )
        n_chips = shape[0] * shape[1] * shape[2]
        if platform == "cpu":
            # shared silicon: R ranks x fixed work per rank is R x the
            # total work, so rate parity with 1 device is ideal — the
            # figure isolates collective/placement overhead
            eff = wrate / max(rate_1w, 1e-9)
            basis = "host-platform (rate_R / rate_1)"
        else:
            eff = wrate / max(n_chips * rate_1w, 1e-9)
            basis = "per-chip (rate_R / (n_chips*rate_1))"
        key = "x".join(str(d) for d in shape)
        weak["per_mesh"][key] = {
            "rows": rows_per_rank * R,
            "rows_per_sec": round(wrate, 1),
            "efficiency": round(eff, 3),
        }
        log(
            f"sharded phase: weak-scaling mesh {key} "
            f"({rows_per_rank}/rank) {wrate:.0f} rows/s "
            f"(eff {eff:.3f})"
        )
    weak["basis"] = basis if weak["per_mesh"] else ""
    record["weak_scaling"] = weak

    record["ok"] = identical
    if best_data is not None:
        R, rate_r = best_data
        if platform == "cpu":
            # host-platform mesh: every virtual device is the same
            # silicon, so linear scaling is rate parity — the figure
            # measures pure sharding overhead
            eff = rate_r / max(rate_1, 1e-9)
            basis = "host-platform (rate_R / rate_1)"
        else:
            eff = rate_r / max(R * rate_1, 1e-9)
            basis = "per-chip (rate_R / (R*rate_1))"
        record["data_axis_scaling"] = {
            "R": R,
            "rows_per_sec": round(rate_r, 1),
            "efficiency": round(eff, 3),
            "basis": basis,
        }
    return record


def _write_multichip(record: dict) -> str:
    """MULTICHIP_r07.json: the measured pod-scale serving record the
    ROADMAP tracks (SWARM_MULTICHIP_OUT overrides the path). r07 adds
    the full per-shape weak-scaling efficiency table (every
    ``_shard_shapes`` shape, 3-axis meshes included) measured on the
    overlapped split-phase path."""
    out = os.environ.get("SWARM_MULTICHIP_OUT", "") or str(
        Path(__file__).parent / "MULTICHIP_r07.json"
    )
    with open(out, "w") as fh:
        json.dump(record, fh, indent=2)
        fh.write("\n")
    log(f"sharded phase: record written to {out}")
    return out


#: recorded weak-scaling efficiency floors for the sharded serving
#: phase (tools/preflight.sh gate; same skip/factor conventions as
#: tools/device_floor.json and tools/walk_floor.json)
_SHARD_FLOOR_PATH = Path(__file__).parent / "tools" / "shard_floor.json"


def _shard_floor_config(record: dict) -> dict:
    """The measurement basis a recorded shard floor is only comparable
    under — any mismatch downgrades the check to a skip, exactly like
    tools/profile_device.py's gate."""
    return {
        "platform": record.get("platform"),
        "n_devices": record.get("n_devices"),
        "rows": record.get("rows"),
        "templates": record.get("templates"),
        "rows_per_rank": (record.get("weak_scaling") or {}).get(
            "rows_per_rank"
        ),
    }


def _shard_floor_record(record: dict) -> int:
    weak = (record.get("weak_scaling") or {}).get("per_mesh") or {}
    if not weak:
        log("shard floor: no weak-scaling table to record; skipping")
        return 0
    rec = dict(_shard_floor_config(record))
    rec["weak_efficiency"] = {
        key: entry["efficiency"] for key, entry in weak.items()
    }
    _SHARD_FLOOR_PATH.write_text(json.dumps(rec, indent=2) + "\n")
    log(f"shard floor recorded: {rec} -> {_SHARD_FLOOR_PATH}")
    return 0


def _shard_floor_check(record: dict) -> int:
    """Gate the weak-scaling efficiency table against the recorded
    per-mesh-shape floors. Efficiency is higher-better, so a shape
    fails when its current figure drops below floor/SWARM_FLOOR_FACTOR
    (default 2.0); a shape recorded in the floor but absent from the
    sweep also fails — silently shrinking coverage is a regression."""
    if os.environ.get("SWARM_FLOOR_SKIP") == "1":
        log("shard floor check skipped (SWARM_FLOOR_SKIP=1)")
        return 0
    if not _SHARD_FLOOR_PATH.exists():
        log(
            f"no recorded shard floor at {_SHARD_FLOOR_PATH}; "
            "run --record-floor"
        )
        return 0  # missing floor is not a failure — first run records
    floor = json.loads(_SHARD_FLOOR_PATH.read_text())
    current = _shard_floor_config(record)
    mismatched = {
        k: (floor.get(k), v)
        for k, v in current.items()
        if floor.get(k) != v
    }
    if mismatched:
        log(
            "shard floor check skipped: recorded floor does not match "
            f"this configuration ({mismatched}); re-record with "
            "--record-floor"
        )
        return 0
    factor = float(os.environ.get("SWARM_FLOOR_FACTOR", "2.0"))
    weak = (record.get("weak_scaling") or {}).get("per_mesh") or {}
    rc = 0
    for key, floor_eff in sorted(
        (floor.get("weak_efficiency") or {}).items()
    ):
        cur = (weak.get(key) or {}).get("efficiency")
        if cur is None:
            log(
                f"FLOOR REGRESSION: mesh {key} missing from the weak "
                f"sweep (floor efficiency {floor_eff})"
            )
            rc = 1
            continue
        if cur < floor_eff / factor:
            log(
                f"FLOOR REGRESSION: mesh {key} weak-scaling efficiency "
                f"{cur:.3f} < recorded floor {floor_eff:.3f} / {factor}"
            )
            rc = 1
        else:
            log(
                f"shard floor ok: mesh {key} efficiency {cur:.3f} >= "
                f"{floor_eff:.3f} / {factor}"
            )
    return rc


def _percentile_ms(vals: list, p: float) -> float:
    if not vals:
        return 0.0
    return float(np.percentile(np.asarray(vals, dtype=np.float64), p)) * 1e3


def _qos_probe_lines(n: int, seed: int) -> list:
    """Single-target interactive lookups: fingerprint-ish pages of
    MIXED widths (each probe salted unique, so neither arm is memo-
    served), the shape a real ad-hoc lookup has."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        salt = bytes(rng.integers(97, 123, size=32, dtype=np.uint8)).decode()
        pad = "p" * int(rng.integers(16, 600 + 700 * (i % 3)))
        out.append(
            json.dumps(
                {"host": f"203.0.113.{i}", "port": 443, "status": 200,
                 "body": f"<title>Probe {i} Admin</title> {salt} {pad}"}
            ) + "\n"
        )
    return out


class _QosStack:
    """Shared in-process server + worker harness for the QoS latency
    phase and the QoS smoke clause — ONE copy of the bring-up, submit
    and completion-wait logic, so the smoke gate and the latency
    phase's arms can never drift apart on the wire shape or the
    completion predicate."""

    def __init__(
        self, tag: str, cache_backend: str = "off",
        pipeline: str = "off", busy_s: float = 0.005,
        extra_cfg: "dict | None" = None,
    ):
        import tempfile
        import threading as _threading

        from swarm_tpu.client.cli import JobClient
        from swarm_tpu.config import Config
        from swarm_tpu.server.app import SwarmServer
        from swarm_tpu.worker.runtime import JobProcessor

        tmp = tempfile.mkdtemp(prefix=f"swarm_qos_{tag}_")
        modules_dir = os.path.join(tmp, "modules")
        os.makedirs(modules_dir)
        corpus = os.environ.get("SWARM_BENCH_CORPUS", str(BUNDLED_CORPUS))
        with open(os.path.join(modules_dir, "fingerprint.json"), "w") as f:
            json.dump({"backend": "tpu", "templates": corpus}, f)
        self.cfg = Config(
            host="127.0.0.1", port=0, api_key="qos",
            blob_root=os.path.join(tmp, "blobs"),
            doc_root=os.path.join(tmp, "docs"),
            modules_dir=modules_dir,
            poll_interval_idle_s=0.02, poll_interval_busy_s=busy_s,
            cache_backend=cache_backend, pipeline=pipeline,
            **(extra_cfg or {}),
        )
        self.srv = SwarmServer(self.cfg)
        self.srv.start_background()
        self.cfg.server_url = f"http://127.0.0.1:{self.srv.port}"
        self.client = JobClient(self.cfg.resolve_url(), self.cfg.api_key)
        self.worker = JobProcessor(
            Config(**{**self.cfg.__dict__, "worker_id": f"qos-{tag}"})
        )
        self._wt = _threading.Thread(
            target=self.worker.process_jobs, daemon=True
        )
        self._wt.start()

    def submit(self, scan_id, lines, batch, qos=None) -> int:
        import requests as _requests

        headers = {"Authorization": f"Bearer {self.cfg.api_key}"}
        if qos:
            headers["X-Swarm-QoS"] = qos
        return _requests.post(
            f"{self.cfg.resolve_url()}/queue",
            json={"module": "fingerprint", "file_content": lines,
                  "batch_size": batch, "scan_id": scan_id,
                  "chunk_index": 0},
            headers=headers, timeout=30,
        ).status_code

    def wait_complete(self, scan_ids, deadline_s=600):
        """(all_done, final statuses payload)."""
        pending = set(scan_ids)
        deadline = time.time() + deadline_s
        while time.time() < deadline and pending:
            time.sleep(0.05)
            statuses = self.client.get_statuses()
            if statuses is None:
                continue
            pending -= {
                s["scan_id"] for s in statuses.get("scans", [])
                if s["percent_complete"] == 100.0
            }
        return not pending, self.client.get_statuses() or {}

    def close(self) -> None:
        self.worker.stop_requested = True
        self._wt.join(timeout=30)
        self.srv.shutdown()


def _qos_serving_arm(
    tag: str, flood_lines: list, flood_batch: int, probe_lines: list,
    arrivals: list, express: bool, cache_backend: str = "off",
) -> dict:
    """One latency-A/B arm: real server + real worker, one bulk flood
    scan plus open-loop interactive probes. ``express`` arms send
    X-Swarm-QoS: interactive on the probes; the baseline arm submits
    the SAME probes with no header, so they ride the bulk lane.
    Latency accounting is open-loop and coordinated-omission-free:
    each probe's latency is measured from its SCHEDULED arrival (the
    submitter sleeps to the schedule; admitted_at lands within a
    request of it) to its job record's completed_at."""
    import threading as _threading

    stack = _QosStack(tag, cache_backend=cache_backend)
    submit, wait_complete = stack.submit, stack.wait_complete
    try:
        # engine warm-up OUTSIDE the timed window: the first job pays
        # corpus load + compile, which is the AOT phase's story
        assert submit("qwarm_1", [flood_lines[0]], 1) == 200
        ok_warm, _ = wait_complete(["qwarm_1"])
        probe_qos = "interactive" if express else None
        probe_ids = [f"qprobe{i}_1" for i in range(len(probe_lines))]
        sched_abs: list = []
        probe_codes: list = []

        def probe_submitter(t0: float) -> None:
            # every outcome is recorded: a shed/failed probe must fail
            # the arm FAST with a diagnosable record, not burn the full
            # completion deadline waiting for a job that never existed
            for i, (dt, line) in enumerate(zip(arrivals, probe_lines)):
                lag = t0 + dt - time.perf_counter()
                if lag > 0:
                    time.sleep(lag)
                sched_abs.append((i, time.time()))
                try:
                    probe_codes.append(
                        submit(probe_ids[i], [line], 1, qos=probe_qos)
                    )
                except Exception as e:
                    probe_codes.append(f"{type(e).__name__}: {e}")

        t0 = time.perf_counter()
        assert submit("qflood_1", flood_lines, flood_batch) == 200
        pt = _threading.Thread(target=probe_submitter, args=(t0,),
                               daemon=True)
        pt.start()
        pt.join()
        if any(c != 200 for c in probe_codes):
            log(f"!!! qos arm {tag}: probe submissions failed: {probe_codes}")
            return {
                "ok": False, "probe_codes": probe_codes,
                "probe_latency_s": [],
                "p50_ms": 0.0, "p95_ms": 0.0, "p99_ms": 0.0,
                "bulk_rows_per_sec": 0.0, "bulk_wall_s": 0.0,
                "probe_raw": {}, "probe_attempts": {},
            }
        all_done, statuses = wait_complete(["qflood_1"] + probe_ids)
        jobs = statuses.get("jobs", {})
        sched_at = dict(sched_abs)
        probe_lat: list = []
        for i, scan_id in enumerate(probe_ids):
            recs = [j for j in jobs.values() if j.get("scan_id") == scan_id]
            if not recs or recs[0].get("completed_at") is None:
                continue
            probe_lat.append(
                recs[0]["completed_at"] - sched_at.get(
                    i, recs[0].get("admitted_at") or 0.0
                )
            )
        # throughput accounting is over the arm's WHOLE drain (flood +
        # probes): both arms do identical total work, so the retention
        # ratio isolates what the express-lane MACHINERY costs bulk —
        # not when within the window the probes happened to execute
        timed = [
            j for j in jobs.values()
            if j.get("scan_id") != "qwarm_1" and j.get("completed_at")
        ]
        if not timed or not probe_lat:
            # nothing completed (dead worker / timeout): a structured
            # failure record, not a min()-of-empty traceback — the
            # phase's rc-1 path owns reporting it
            return {
                "ok": False, "probe_latency_s": probe_lat,
                "p50_ms": 0.0, "p95_ms": 0.0, "p99_ms": 0.0,
                "bulk_rows_per_sec": 0.0, "bulk_wall_s": 0.0,
                "probe_raw": {}, "probe_attempts": {},
            }
        t_start = min(
            (j.get("admitted_at") or j.get("started_at") or 0.0)
            for j in timed
        )
        t_end = max(j["completed_at"] for j in timed)
        total_rows = len(flood_lines) + len(probe_lines)
        wall = max(1e-9, t_end - t_start)
        probe_raw = {s: stack.client.fetch_raw(s) for s in probe_ids}
        probe_attempts = {
            j.get("scan_id"): j.get("attempts")
            for j in jobs.values() if j.get("scan_id") in set(probe_ids)
        }
        return {
            "ok": bool(ok_warm and all_done),
            "probe_latency_s": probe_lat,
            "p50_ms": _percentile_ms(probe_lat, 50),
            "p95_ms": _percentile_ms(probe_lat, 95),
            "p99_ms": _percentile_ms(probe_lat, 99),
            "bulk_rows_per_sec": round(total_rows / wall, 1),
            "bulk_wall_s": round(wall, 3),
            "probe_raw": probe_raw,
            "probe_attempts": probe_attempts,
        }
    finally:
        stack.close()


def bench_qos_latency(
    flood_jobs: int = 96, flood_batch: int = 8, probes: int = 8
) -> dict:
    """Bimodal open-loop serving A/B (docs/GATEWAY.md §QoS): one bulk
    flood (many chunk-jobs through a real server + worker) with
    Poisson interactive arrivals riding alongside. The express arm
    sends the probes as QoS interactive; the baseline arm submits the
    SAME probes unclassed, so they queue behind the flood. Gates (the
    acceptance criteria, not just recorded): interactive p99 ≥5x lower
    on the express lane, bulk throughput retained within 10%, probe
    verdicts bit-identical between arms."""
    from swarm_tpu.server.queue import _EXPRESS_SERVED

    rng = np.random.default_rng(41)
    flood_lines = []
    for i in range(flood_jobs * flood_batch):
        salt = bytes(rng.integers(97, 123, size=24, dtype=np.uint8)).decode()
        flood_lines.append(
            json.dumps(
                {"host": f"198.51.100.{i % 254}", "port": 80,
                 "status": 200,
                 "body": f"<title>Bulk {i}</title> {salt} build {i % 9}"}
            ) + "\n"
        )
    probe_lines = _qos_probe_lines(probes, seed=43)
    # Poisson arrivals paced WELL below the worker's single-probe
    # service rate (2 s mean — headroom for a noisy/loaded CI box
    # where per-job service stretches past 1 s) and spread across the
    # flood window: open-loop latency is meaningful only while the
    # express lane itself is unsaturated — an overloaded express lane
    # measures its own queueing, not the lane design (the
    # starvation-bound tests cover sustained interactive overload
    # separately)
    arrivals = list(np.cumsum(rng.exponential(scale=2.0, size=probes)))

    x0 = _EXPRESS_SERVED.labels().value
    express = _qos_serving_arm(
        "x", flood_lines, flood_batch, probe_lines, arrivals, express=True
    )
    express_served = _EXPRESS_SERVED.labels().value - x0
    baseline = _qos_serving_arm(
        "b", flood_lines, flood_batch, probe_lines, arrivals, express=False
    )
    identical = bool(express["probe_raw"]) and all(
        express["probe_raw"][s] == baseline["probe_raw"].get(s)
        and bool(express["probe_raw"][s])
        for s in express["probe_raw"]
    )
    p99_speedup = baseline["p99_ms"] / max(express["p99_ms"], 1e-9)
    retention = express["bulk_rows_per_sec"] / max(
        baseline["bulk_rows_per_sec"], 1e-9
    )
    ok = (
        express["ok"] and baseline["ok"] and identical
        and p99_speedup >= 5.0 and retention >= 0.9
        and express_served > 0
    )
    rec = {
        "ok": bool(ok),
        "interactive_p99_ms": round(express["p99_ms"], 2),
        "interactive_p50_ms": round(express["p50_ms"], 2),
        "bulk_lane_p99_ms": round(baseline["p99_ms"], 2),
        "bulk_lane_p50_ms": round(baseline["p50_ms"], 2),
        "p99_speedup": round(p99_speedup, 2),
        "bulk_retention_ratio": round(retention, 3),
        "bulk_rows_per_sec": {
            "express_arm": express["bulk_rows_per_sec"],
            "baseline_arm": baseline["bulk_rows_per_sec"],
        },
        "express_served": int(express_served),
        "verdicts_identical": bool(identical),
        "flood_jobs": flood_jobs,
        "flood_batch": flood_batch,
        "probes": probes,
    }
    log(
        f"qos latency: interactive p99 {rec['interactive_p99_ms']:.1f} ms "
        f"(express) vs {rec['bulk_lane_p99_ms']:.1f} ms (bulk lane) = "
        f"{p99_speedup:.1f}x; bulk retention {retention:.3f}; "
        f"verdicts identical={identical}; express_served={express_served}"
    )
    return rec


def _trace_latency_breakdown(
    flood_jobs: int = 4, flood_batch: int = 4, probes: int = 3
) -> dict:
    """Span-derived per-QoS-class latency decomposition for the
    latency phase's headline JSON (docs/OBSERVABILITY.md §Tracing): a
    short traced run — one bulk flood plus interactive probes against
    a real server + worker — whose assembled waterfalls are reduced to
    per-segment medians (queue-wait / sched / download / execute /
    device.launch / device.result_wait / engine.confirm_walk / upload,
    ms) per class. Runs SEPARATELY from the timed A/B arms on purpose:
    the headline latency numbers stay tracing-free, and this run
    answers the follow-up question those numbers raise ("where does
    the interactive p99 actually go?")."""
    import statistics

    from swarm_tpu.telemetry import tracing

    rng = np.random.default_rng(47)
    flood_lines = []
    for i in range(flood_jobs * flood_batch):
        salt = bytes(rng.integers(97, 123, size=24, dtype=np.uint8)).decode()
        flood_lines.append(
            json.dumps(
                {"host": f"198.51.101.{i % 254}", "port": 80,
                 "status": 200,
                 "body": f"<title>TBulk {i}</title> {salt}"}
            ) + "\n"
        )
    probe_lines = _qos_probe_lines(probes, seed=53)
    tracing.set_enabled(True)
    stack = _QosStack("tlat", busy_s=0.01)
    try:
        ids = {
            "bulk": ["tlflood_1"],
            "interactive": [f"tlprobe{i}_1" for i in range(probes)],
        }
        codes = [stack.submit("tlflood_1", flood_lines, flood_batch)]
        codes += [
            stack.submit(ids["interactive"][i], [line], 1,
                         qos="interactive")
            for i, line in enumerate(probe_lines)
        ]
        done, _ = stack.wait_complete(
            ids["bulk"] + ids["interactive"], deadline_s=300
        )
        segs = ("queue-wait", "sched", "download", "execute",
                "device.launch", "device.result_wait",
                "engine.confirm_walk", "upload")
        out: dict = {
            "all_complete": bool(done),
            "codes_ok": all(c == 200 for c in codes),
        }
        for cls, scan_ids in ids.items():
            by_name: dict = {}
            n_docs = 0
            for sid in scan_ids:
                doc = stack.client.get_trace(sid)
                if not doc:
                    continue
                n_docs += 1
                for s in doc.get("spans", []):
                    if (
                        s.get("name") in segs
                        and s.get("duration_s") is not None
                    ):
                        by_name.setdefault(s["name"], []).append(
                            float(s["duration_s"])
                        )
            out[cls] = {
                "traces": n_docs,
                "segment_median_ms": {
                    name: round(statistics.median(vals) * 1000.0, 2)
                    for name, vals in sorted(by_name.items())
                },
            }
        return out
    finally:
        stack.close()
        tracing.set_enabled(None)


def bench_trace_overhead_ab(
    templates=None, db=None, n_rows: int = 0, reps: int = 3
) -> dict:
    """Tracing-overhead A/B (docs/OBSERVABILITY.md §Tracing): the same
    fresh content matched with tracing ENABLED — an active per-attempt
    context bound, exactly as the worker binds one, and the shared
    result tier attached so the per-operation cache spans fire (the
    span-densest production path) — vs DISABLED, on an identical
    sibling setup. Both arms match the SAME fresh content in adjacent
    interleaved pairs (order alternating per pair) and their walls are
    summed, so the retention ratio comes from two equally-drifted long
    windows; verdict identity is asserted on every pair. The gate
    is <2% fresh-content throughput regression (retention >= 0.98) —
    the "near-zero cost" contract that lets tracing ship default-off
    yet be flipped on in production without a capacity conversation."""
    import time as _time

    from swarm_tpu.cache import ResultCacheClient, SharedResultTier
    from swarm_tpu.fingerprints.dbcache import load_or_compile
    from swarm_tpu.ops.engine import MatchEngine
    from swarm_tpu.stores import MemoryBlobStore, MemoryStateStore
    from swarm_tpu.telemetry import tracing

    if templates is None:
        corpus = Path(
            os.environ.get("SWARM_BENCH_CORPUS", str(BUNDLED_CORPUS))
        )
        templates, db = load_or_compile(corpus)
    n_rows = n_rows or 512
    #: pairs per rep: a single 512-row match is ~0.2 s on a 2-core CPU
    #: box, where scheduler jitter alone swings one pair ratio by ±10%
    #: — far too noisy for a 2% gate; summing walls over reps×passes
    #: adjacent interleaved pairs gives each arm one long effective
    #: window that actually resolves the regression being gated
    passes = 3
    rng = np.random.default_rng(4747)
    base = realistic_rows(n_rows, seed=74)
    for r in base:
        # one width class (see bench_dedup_fleet's salt rationale)
        r.body = (b"<!-- 0123456789012345678901234567890123456789 -->"
                  + r.body)[:448]

    def resalt(rows):
        # fresh digests at EXACTLY the original lengths: same width
        # classes and batch shapes, nothing left to compile inside the
        # timed window
        out = _clone_rows(rows)
        for r in out:
            s = bytes(rng.integers(97, 123, size=40, dtype=np.uint8))
            r.body = r.body[:5] + s + r.body[45:]
        return out

    def mk_arm():
        # each arm keeps its OWN engine + tier for the whole run: fresh
        # content always misses, so every pass does identical work and
        # pays the per-operation cache spans (the span-densest path)
        eng = MatchEngine(
            templates, mesh=None, batch_rows=max(64, n_rows // 4),
            max_body=MAX_BODY, max_header=MAX_HEADER, db=db,
        )
        tier = SharedResultTier(MemoryStateStore(), MemoryBlobStore())
        eng.attach_result_cache(
            ResultCacheClient(tier, worker_id="bench-trace-ab")
        )
        eng.match(resalt(base))  # untimed same-shape warm
        return eng

    engines = {False: mk_arm(), True: mk_arm()}
    walls = {False: [], True: []}
    identical = True
    traced_spans = 0
    n_pairs = max(reps, 1) * passes
    n_pairs += n_pairs % 2  # even: each arm runs second equally often
    for k in range(n_pairs):
        content = resalt(base)  # fresh per pair, shared by both arms
        # adjacent-in-time pairs with the arm order alternating per
        # pair: box-level drift (CPU frequency, page cache warming)
        # hits both arms symmetrically instead of favoring whichever
        # arm habitually runs second
        order = (False, True) if k % 2 == 0 else (True, False)
        outs: dict = {}
        for traced in order:
            tracing.set_enabled(traced)
            try:
                # same code path both arms: disabled ⇒ ctx is None and
                # activate/span are the documented no-ops being measured
                ctx = tracing.attempt_context(
                    "bench-trace-ab", job_id=f"ab{k}"
                )
                timed = _clone_rows(content)
                t0 = _time.perf_counter()
                with tracing.activate(ctx):
                    with tracing.span("execute"):
                        outs[traced] = engines[traced].match(timed)
                walls[traced].append(_time.perf_counter() - t0)
            finally:
                tracing.set_enabled(None)
            if ctx is not None:
                traced_spans = max(traced_spans, ctx.span_count())
        identical = identical and _verdicts_equal(outs[False], outs[True])

    def trimmed(lst):
        # drop each arm's single worst wall: one stray GC pause / cron
        # tick otherwise swings the summed ratio past the 2% gate, and
        # dropping the max from BOTH arms keeps the comparison fair
        return sum(lst) - (max(lst) if len(lst) > 2 else 0.0)

    wall_off, wall_on = trimmed(walls[False]), trimmed(walls[True])
    n_eff = n_pairs - (1 if n_pairs > 2 else 0)
    off = {"rows_per_sec": round(n_rows * n_eff / wall_off, 1)}
    on = {"rows_per_sec": round(n_rows * n_eff / wall_on, 1)}
    retention = wall_off / max(wall_on, 1e-9)
    ok = bool(identical) and retention >= 0.98
    rec = {
        "ok": ok,
        "retention": round(retention, 4),
        "verdicts_identical": bool(identical),
        "tracing_off_rows_per_sec": off["rows_per_sec"],
        "tracing_on_rows_per_sec": on["rows_per_sec"],
        "traced_spans": traced_spans,
        "n_rows": n_rows,
        "passes": passes,
        "reps": reps,
    }
    log(
        f"trace overhead A/B: off {off['rows_per_sec']:.0f} -> on "
        f"{on['rows_per_sec']:.0f} rows/s (retention {retention:.4f}, "
        f"gate >= 0.98); {traced_spans} spans/attempt; verdicts "
        f"{'identical' if identical else 'MISMATCH'}"
    )
    return rec


def _setup_phase(need_corpus: bool):
    """Per-phase process setup: backend + (optionally) corpus. Returns
    (templates, db, dev) — templates/db None when not needed."""
    dev = resolve_device()
    if not need_corpus:
        return None, None, dev
    # SWARM_BENCH_CORPUS overrides the corpus dir (smoke-testing the
    # bench pipeline without the full 3,989-template compile)
    corpus = Path(
        os.environ.get("SWARM_BENCH_CORPUS", "")
        or (REFERENCE_CORPUS if REFERENCE_CORPUS.is_dir() else BUNDLED_CORPUS)
    )
    from swarm_tpu.fingerprints.dbcache import load_or_compile

    templates, db = load_or_compile(corpus)
    log(f"corpus loaded: {len(templates)} templates")
    return templates, db, dev


def run_phase(phase: str) -> int:
    """One bench phase in this process. Emits its JSON metric lines."""
    if phase == "aot_child":
        # the cold-start A/B's measured arm: minimal setup on purpose
        # (its OWN bring-up is the number)
        return _aot_child()
    if phase in ("sharded", "shard_smoke"):
        # the mesh path needs >1 device: force the virtual host-
        # platform mesh BEFORE jax initializes (a no-op for real
        # accelerator backends — the flag only shapes the CPU
        # platform), so CPU-only boxes still exercise sharded serving.
        # Scoped to these phases' SUBPROCESSES on purpose: the flag
        # also reshapes XLA's CPU thread pools, and the other smoke/
        # bench clauses must keep their single-device measurement basis
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    if phase == "shard_smoke":
        global ROWS, ITERS
        ROWS, ITERS = 256, 2
        os.environ.setdefault("SWARM_BENCH_CORPUS", str(BUNDLED_CORPUS))
    templates, db, dev = _setup_phase(
        need_corpus=phase in ("exact", "oracle", "device", "sharded",
                              "shard_smoke", "workflow")
    )
    if phase == "workflow":
        wab = bench_workflow_ab(templates)
        emit(
            "workflow_device_speedup",
            wab["speedup"],
            "x (device gate planes vs host-twin workflow gating, "
            "bit-identical per-row results)",
            wab["speedup"] / BASELINES["workflow_device_speedup"],
            extra={"workflow_ab": wab},
        )
        if not wab["identical"]:
            log("!!! workflow device/twin per-row mismatch — phase FAILED")
            return 1
        return 0
    if phase == "exact":
        (
            exact, fresh_rate, fresh_walk, eng, engine_stats, device_rec,
        ) = bench_exact_engine(templates, db=db)
        # two-phase kernel trajectory metrics (ISSUE 3): TIME values,
        # lower is better — vs_baseline is baseline/value so >1 means
        # faster than the pre-change record and a regression is a
        # driver-visible ratio collapse
        emit(
            "device_compile_seconds",
            device_rec["device_compile_seconds"],
            "s (first-shape compile+dispatch; lower is better)",
            BASELINES["device_compile_seconds"]
            / max(device_rec["device_compile_seconds"], 1e-9),
            extra={"compile_count": device_rec["device_compile_count"]},
        )
        emit(
            "fresh_batch_device_ms",
            device_rec["fresh_batch_ms"],
            "ms/batch (total fresh %d-row batch wall, like-for-like "
            "with the pre-change record; device half in extra)"
            % device_rec["fresh_batch_rows"],
            BASELINES["fresh_batch_device_ms"]
            / max(device_rec["fresh_batch_ms"], 1e-9),
            extra={
                "device_ms": device_rec["fresh_batch_device_ms"],
                "rows": device_rec["fresh_batch_rows"],
            },
        )
        # donated+compacted dispatch A/B (docs/DEVICE_MATCH.md): the
        # ISSUE-6 tentpole's device-path win, isolated from the host
        # walk and gated on bit-identical fused planes
        dab = bench_dispatch_ab(db)
        emit(
            "fresh_dispatch_ab_speedup",
            dab["speedup"],
            "x (donation+compaction vs legacy fused dispatch, "
            "bit-identical planes)",
            dab["speedup"] / BASELINES["fresh_dispatch_ab_speedup"],
            extra={"dispatch_ab": dab},
        )
        # continuous-batching A/B (same engine, same corpus, chunked
        # feed): rides in the headline extra so BENCH_* files track
        # the pipeline=on vs =off record per round
        ab = bench_pipeline_ab(eng)
        ab_speed = ab["fresh"]["on"]["rows_per_sec"] / max(
            ab["fresh"]["off"]["rows_per_sec"], 1e-9
        )
        emit(
            "pipeline_ab_fresh_speedup",
            ab_speed,
            "x (pipeline on/off, chunked fresh feed, bit-identical "
            "verdicts)",
            ab_speed / BASELINES["pipeline_ab_fresh_speedup"],
            extra={"ab": ab},
        )
        # fleet-replay dedup scenario (docs/CACHING.md, ISSUE 9): the
        # shared result tier's headline pair — a second engine lifetime
        # over tier-known content vs the same lifetime tier-off,
        # identity-gated, plus the shared hit ratio on its rows
        ded = bench_dedup_fleet(templates, db=db)
        emit(
            "dedup_warm_speedup",
            ded["speedup"],
            "x (tier-on vs tier-off second engine lifetime, "
            "bit-identical verdicts)",
            ded["speedup"] / BASELINES["dedup_warm_speedup"],
            extra={"dedup": ded},
        )
        emit(
            "dedup_cache_hit_ratio",
            ded["hit_ratio"],
            "ratio (shared-tier hits over the second lifetime's rows)",
            ded["hit_ratio"] / BASELINES["dedup_cache_hit_ratio"],
        )
        # adversarial floor: every row carries never-seen content, so
        # neither dedup nor the cross-batch memos help
        emit(
            "exact_fresh_content_fingerprints_per_sec_per_chip",
            fresh_rate,
            "fingerprints/sec/chip",
            fresh_rate / TARGET_PER_CHIP,
        )
        # the floor's host-walk component. An unmeasurably small walk
        # (rate 0 sentinel) is a SKIP, not a collapse — emitting 0.0
        # would read as the worst possible rate on any trend chart.
        # same-run paired walk A/B (docs/HOST_WALK.md): the serial
        # reference walk vs the row-parallel batched walk on a
        # confirm-heavy fresh feed — the stale-record-free comparison
        # the round-5 verdict asked for, attached to the walk metric
        wab = bench_walk_ab(templates)
        emit(
            "walk_ab_fresh_speedup",
            wab["speedup"],
            "x (batched/serial host walk, confirm-heavy fresh feed, "
            "bit-identical results)",
            wab["speedup"] / BASELINES["walk_ab_fresh_speedup"],
            extra={"walk_ab": wab},
        )
        if fresh_walk > 0:
            emit(
                "exact_fresh_content_host_walk_rows_per_sec",
                fresh_walk,
                "rows/sec (host sparse-confirm+extraction on fresh "
                "content)",
                fresh_walk
                / BASELINES["exact_fresh_content_host_walk_rows_per_sec"],
                extra={"walk_ab": wab},
            )
        else:
            log("!!! fresh host walk unmeasurably small; metric omitted")
        # workflow gate-plane A/B (docs/WORKFLOWS.md, ISSUE 20): host
        # twin vs device gate planes over the workflow-heavy synthetic
        # fleet, rc-gated on bit-identical per-row workflow results
        wfab = bench_workflow_ab(templates)
        emit(
            "workflow_device_speedup",
            wfab["speedup"],
            "x (device gate planes vs host-twin workflow gating, "
            "bit-identical per-row results)",
            wfab["speedup"] / BASELINES["workflow_device_speedup"],
            extra={"workflow_ab": wfab},
        )
        if not wfab["identical"]:
            log("!!! workflow device/twin per-row mismatch — phase FAILED")
            return 1
        # the HEADLINE emits LAST within the phase (and the phase runs
        # last overall) so the driver's tail-parse captures the honest
        # end-to-end exact metric, not an auxiliary line
        emit(
            "exact_fingerprints_per_sec_per_chip",
            exact,
            "fingerprints/sec/chip",
            exact / TARGET_PER_CHIP,
            extra={
                "engine_stats": engine_stats,
                # scheduler A/B record: both runs + bucket-fill/stall
                "pipeline_ab": ab,
                # per-phase device attribution + survivor-compaction
                # evidence (which phase did ISSUE 6 move, and at what
                # phase-B width) — docs/DEVICE_MATCH.md
                "device_phase_ms": device_rec.get("device_phase_ms"),
                "last_compact": device_rec.get("last_compact"),
                # the dispatch A/B record rides here too so one JSON
                # line carries the whole device-path story
                "dispatch_ab": dab,
                # workflow gate-plane A/B (docs/WORKFLOWS.md)
                "workflow_ab": wfab,
            },
        )
    elif phase == "service":
        svc = bench_service_classifier()
        emit(
            "service_probe_classifications_per_sec",
            svc,
            "banners/sec",
            svc / BASELINES["service_probe_classifications_per_sec"],
        )
    elif phase == "service_full":
        large = (
            Path(__file__).parent
            / "swarm_tpu" / "data" / "service-probes-large.txt"
        )
        svc = bench_service_classifier(db_path=str(large))
        emit(
            "service_full_db_classifications_per_sec",
            svc,
            "banners/sec (487 probes / 12.3k signatures)",
            svc / BASELINES["service_full_db_classifications_per_sec"],
        )
    elif phase == "streaming":
        stream = bench_streaming_classifier()
        emit(
            "streamed_service_classifications_per_sec",
            stream,
            "rows/sec",
            stream / BASELINES["streamed_service_classifications_per_sec"],
        )
    elif phase == "oracle":
        oracle = bench_oracle_ab(templates)
        emit(
            "cpu_oracle_rows_per_sec",
            oracle,
            "rows/sec",
            oracle / BASELINES["cpu_oracle_rows_per_sec"],
        )
    elif phase == "jarm":
        jarm = bench_jarm_cluster()
        emit(
            "jarm_cluster_rows_per_sec",
            jarm,
            "fingerprints/sec",
            jarm / BASELINES["jarm_cluster_rows_per_sec"],
        )
    elif phase == "device":
        devrate = bench_device_only(db, dev)
        emit(
            "service_fingerprints_per_sec_per_chip",
            devrate,
            "fingerprints/sec/chip",
            devrate / TARGET_PER_CHIP,
        )
    elif phase == "sharded":
        rec = bench_sharded_serving(db)
        rec["multichip_out"] = _write_multichip(rec)
        if rec.get("skipped"):
            return 0
        scaling = rec.get("data_axis_scaling") or {}
        if scaling:
            emit(
                "sharded_data_axis_efficiency",
                scaling["efficiency"],
                f"ratio ({scaling['basis']}; >=0.7 acceptance)",
                scaling["efficiency"]
                / BASELINES["sharded_data_axis_efficiency"],
                extra={"sharded": rec},
            )
            emit(
                "sharded_serving_rows_per_sec",
                scaling["rows_per_sec"],
                f"rows/sec ({scaling['R']}-way data mesh, full-corpus "
                "dispatch/collect serve, identity-gated)",
                scaling["rows_per_sec"] / TARGET_PER_CHIP,
            )
        if not rec["ok"]:
            # identity gate is REAL: a sharded plane mismatch is a
            # correctness bug, not a throughput datapoint
            log("!!! sharded serving planes MISMATCH — phase FAILED")
            return 1
        # regression gate (tools/shard_floor.json): --record-floor
        # pins the weak-scaling efficiency table, --check-floor fails
        # the phase when any recorded shape regresses past
        # SWARM_FLOOR_FACTOR (tools/preflight.sh runs the check)
        if "--record-floor" in sys.argv:
            return _shard_floor_record(rec)
        if "--check-floor" in sys.argv:
            return _shard_floor_check(rec)
    elif phase == "aot":
        # AOT cold-start A/B (docs/AOT.md): fresh-process fetch-vs-
        # compile bring-up over a file-backed artifact store, paired
        # and identity-gated on the verdict planes. Children inherit
        # the same corpus resolution as every other phase.
        os.environ.setdefault(
            "SWARM_BENCH_CORPUS",
            str(
                REFERENCE_CORPUS
                if REFERENCE_CORPUS.is_dir()
                else BUNDLED_CORPUS
            ),
        )
        rec = bench_aot_coldstart(reps=2)
        if not rec.get("ok"):
            log(f"!!! AOT cold-start phase FAILED: {rec}")
            return 1
        emit(
            "aot_coldstart_speedup",
            rec["speedup"],
            "x (fresh-process bring-up: compile arm / warm-fetch arm, "
            "planes identity-gated)",
            rec["speedup"],
            extra={"aot": {k: v for k, v in rec.items() if k != "seed"}},
        )
        emit(
            "aot_bringup_seconds",
            rec["fetch_bringup_seconds"],
            "s (median warm-fetch bring-up to first full-plane batch; "
            "compile arm in extra)",
            rec["compile_bringup_seconds"]
            / max(rec["fetch_bringup_seconds"], 1e-9),
            extra={
                "compile_bringup_seconds": rec["compile_bringup_seconds"],
            },
        )
    elif phase == "latency":
        # latency-tiered serving A/B (docs/GATEWAY.md §QoS): bimodal
        # open-loop load against a real server + worker, gated on the
        # interactive p99 / bulk-retention / verdict-identity triplet.
        # Always the bundled corpus: this phase measures the SERVING
        # lanes, not corpus scale (the exact phase owns that).
        os.environ.setdefault("SWARM_BENCH_CORPUS", str(BUNDLED_CORPUS))
        rec = bench_qos_latency()
        # span-derived per-class decomposition rides the headline JSON
        # (a separate short traced run — the timed A/B arms above stay
        # tracing-free; docs/OBSERVABILITY.md §Tracing)
        rec["span_breakdown"] = _trace_latency_breakdown()
        emit(
            "qos_interactive_p99_speedup",
            rec["p99_speedup"],
            "x (interactive admission-to-verdict p99: bulk lane / "
            "express lane, open-loop bimodal load)",
            rec["p99_speedup"] / BASELINES["qos_interactive_p99_speedup"],
            extra={
                "interactive_p99_ms": rec["interactive_p99_ms"],
                "bulk_retention_ratio": rec["bulk_retention_ratio"],
                "qos_latency": rec,
            },
        )
        if not rec.get("ok"):
            log(f"!!! qos latency phase FAILED: {rec}")
            return 1
        # tracing-overhead gate (docs/OBSERVABILITY.md §Tracing):
        # tracing-on vs tracing-off fresh-content throughput, paired
        # interleaved, rc-gated at <2% regression + verdict identity
        tab = bench_trace_overhead_ab()
        emit(
            "trace_overhead_retention",
            tab["retention"],
            " (tracing-on / tracing-off fresh-content rows/s; paired "
            "interleaved A/B, gate >= 0.98)",
            tab["retention"],
            extra={"trace_overhead": tab},
        )
        if not tab.get("ok"):
            log(f"!!! trace overhead gate FAILED: {tab}")
            return 1
    elif phase == "monitor":
        # continuous-monitoring cost gate (docs/MONITORING.md §Cost
        # model): a 95%-unchanged fleet's steady-state rescan must
        # dispatch <= 5% of the first scan's chunks, and the stored
        # change feed must be bit-identical to the brute-force replay
        # diff over the persisted epoch inputs/outputs
        rec = bench_monitor()
        ratio = rec.get("steady_cost_ratio", 1.0)
        ok = (
            rec.get("ok_run")
            and rec.get("replay_identical")
            and rec.get("dispatched", [0])[0] == rec.get("n_targets")
            and ratio <= 0.05 + 1e-9
        )
        emit(
            "monitor_steady_rescan_cost_ratio",
            ratio,
            " (steady-state dispatched chunks / first-scan dispatched; "
            "95%-unchanged fleet, gate <= 0.05 + bit-identical replay "
            "diff)",
            0.05 / max(ratio, 1e-9),
            extra={"monitor": rec},
        )
        if not ok:
            log(f"!!! monitor phase FAILED: {rec}")
            return 1
    elif phase == "autoscale":
        # closed-loop elastic-fleet replay (docs/RESILIENCE.md
        # §Preemption): diurnal curve vs the simulated preemptible
        # provider, real workers attached per node, seeded preemption
        # notices on the spike. Gates: zero lost jobs, /raw identity
        # vs a fixed fleet, forecast lead >= 0 on the shoulder,
        # scale-to-zero re-warm cold-start within the SLO, and
        # bulk-sheds-before-interactive.
        os.environ.setdefault("SWARM_BENCH_CORPUS", str(BUNDLED_CORPUS))
        rec = bench_autoscale()
        emit(
            "autoscale_forecast_lead_steps",
            float(
                -1 if rec.get("forecast_lead_steps") is None
                else rec["forecast_lead_steps"]
            ),
            " steps (spike-peak step minus first nonzero-forecast "
            "step; gate >= 0 — the advisor scales AHEAD of the spike)",
            1.0 if rec.get("ok") else 0.0,
            extra={
                "autoscale": {
                    k: v for k, v in rec.items() if k != "steps"
                },
                "curve": rec.get("steps"),
            },
        )
        emit(
            "autoscale_rewarm_coldstart_s",
            float(rec.get("scale_to_zero", {}).get("coldstart_s")
                  or 0.0),
            "s (scale-to-zero re-warm: parked fleet's first node "
            "servable; gate <= fleet_coldstart_slo_s, AOT-warm)",
            (
                rec["coldstart_slo_s"]
                / max(rec["scale_to_zero"].get("coldstart_s") or 1e-9,
                      1e-9)
                if rec.get("scale_to_zero", {}).get("coldstart_s")
                else 0.0
            ),
            extra={"scale_to_zero": rec.get("scale_to_zero")},
        )
        if not rec.get("ok"):
            log(
                "!!! autoscale phase FAILED: "
                f"{ {k: v for k, v in rec.items() if k != 'steps'} }"
            )
            return 1
    elif phase == "shard_smoke":
        # run_smoke's child: engine-level sharded-vs-single verdict
        # identity on the forced 8-device host-platform mesh
        ok, rec = _smoke_shard_clause(templates, db)
        emit(
            "smoke_shard_identity",
            1.0 if ok else 0.0,
            "bool (sharded mesh engine vs single-device verdict "
            "identity)",
            1.0 if ok else 0.0,
            extra={"shard_smoke": rec},
        )
        return 0 if ok else 1
    else:
        log(f"unknown phase {phase!r}")
        return 2
    return 0


def _bench_resilience_overhead() -> dict | None:
    """Measured fault-free cost of the resilience layer's two hot-path
    touch points (docs/RESILIENCE.md): an unarmed fault_point call and
    the retrying-transport facade over a no-op inner client. Skipped
    (None) when a fault plan is armed — the numbers would measure the
    plan, not the no-op path."""
    from swarm_tpu.resilience.faults import active_plan, fault_point
    from swarm_tpu.resilience.transport import RetryingServerClient

    if active_plan() is not None:
        return None
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        fault_point("bench.noop")
    fp_ns = (time.perf_counter() - t0) / n * 1e9

    class _Inner:
        def get_job(self, worker_id):
            return None

    inner = _Inner()
    wrapped = RetryingServerClient(inner)
    m = 20_000
    t0 = time.perf_counter()
    for _ in range(m):
        inner.get_job("w")
    raw_ns = (time.perf_counter() - t0) / m * 1e9
    t0 = time.perf_counter()
    for _ in range(m):
        wrapped.get_job("w")
    wrapped_ns = (time.perf_counter() - t0) / m * 1e9
    return {
        "fault_point_ns": round(fp_ns, 1),
        "transport_wrap_ns": round(max(wrapped_ns - raw_ns, 0.0), 1),
    }


def _smoke_shard_clause(templates, db) -> "tuple[bool, dict]":
    """shard_smoke (docs/SHARDING.md): run the sharded serving path on
    the host-platform mesh and gate on verdict identity with the
    single-device engine — placement, dispatch/collect split, psum and
    host redo all exercised on every CPU-only box. Returns
    ``(ok, record)``; ok also covers "the mesh actually engaged"."""
    import jax

    from swarm_tpu.ops.engine import MatchEngine
    from swarm_tpu.parallel.mesh import make_mesh
    from swarm_tpu.telemetry import shard_export

    n_dev = len(jax.devices())
    if n_dev < 2:
        # the forced host-platform flag didn't take (jax was already
        # initialized) — loud, but not a verdict failure
        log("!!! shard smoke SKIPPED: only 1 device visible")
        return True, {"skipped": True, "n_devices": n_dev}
    mesh = make_mesh()
    eng = MatchEngine(
        templates, mesh=mesh, batch_rows=ROWS, max_body=MAX_BODY,
        max_header=MAX_HEADER, db=db,
    )
    single = MatchEngine(
        templates, mesh=None, batch_rows=ROWS, max_body=MAX_BODY,
        max_header=MAX_HEADER, db=db,
    )
    # a full chunk plus a partial one (13 rows: per-rank placement +
    # mesh row padding + the gather-back index all engage)
    rows = realistic_rows(64, seed=3)
    d0 = shard_export.SHARD_DISPATCHES.labels().value
    ok = True
    for chunk in (rows[:48], rows[48:61]):
        got = eng.match(chunk)
        want = single.match(chunk)
        for g, w in zip(got, want):
            if (
                sorted(g.template_ids) != sorted(w.template_ids)
                or g.extractions != w.extractions
            ):
                ok = False
    dispatches = shard_export.SHARD_DISPATCHES.labels().value - d0
    engaged = eng.sharded is not None and dispatches > 0
    mesh_shape = dict(eng.sharded.ranks) if eng.sharded else {}
    log(
        f"shard smoke: mesh {mesh_shape} dispatches={dispatches} "
        f"verdicts {'identical' if ok else 'MISMATCH'}"
    )
    if not engaged:
        log("!!! shard smoke: mesh path did not engage — smoke FAILED")
    return ok and engaged, {
        "mesh": mesh_shape,
        "dispatches": int(dispatches),
        "identical": bool(ok),
    }


def _smoke_gateway_clause() -> "tuple[bool, dict]":
    """Gateway smoke (docs/GATEWAY.md): three tenants against a REAL
    in-process server — one tenant rate-limited into 429s — drained by
    a real worker over the bundled corpus. The gate is cross-tenant
    VERDICT IDENTITY (same content, different tenants, byte-identical
    /raw) plus shed-count > 0 (the abusive tenant actually observed
    backpressure); shed/admit counts are recorded, not gated."""
    import tempfile
    import threading as _threading

    import requests as _requests

    from swarm_tpu.client.cli import JobClient
    from swarm_tpu.config import Config
    from swarm_tpu.server.app import SwarmServer
    from swarm_tpu.worker.runtime import JobProcessor

    tmp = tempfile.mkdtemp(prefix="swarm_gateway_smoke_")
    modules_dir = os.path.join(tmp, "modules")
    os.makedirs(modules_dir)
    corpus = os.environ.get("SWARM_BENCH_CORPUS", str(BUNDLED_CORPUS))
    with open(os.path.join(modules_dir, "fingerprint.json"), "w") as f:
        json.dump({"backend": "tpu", "templates": corpus}, f)
    cfg = Config(
        host="127.0.0.1", port=0, api_key="gwsmoke",
        blob_root=os.path.join(tmp, "blobs"),
        doc_root=os.path.join(tmp, "docs"),
        modules_dir=modules_dir,
        poll_interval_idle_s=0.02, poll_interval_busy_s=0.01,
        gateway_tenant_rate=2.0, gateway_tenant_burst=2,
    )
    srv = SwarmServer(cfg)
    srv.start_background()
    cfg.server_url = f"http://127.0.0.1:{srv.port}"
    lines = [
        json.dumps(
            {"host": f"10.9.0.{i}", "port": 443, "status": 200,
             "body": f"<title>Demo Admin</title> demo-build 7.{i} page {i}"}
        ) + "\n"
        for i in range(4)
    ]

    def submit(tenant: str, scan_id: str) -> int:
        return _requests.post(
            f"{cfg.resolve_url()}/queue",
            json={"module": "fingerprint", "file_content": lines,
                  "batch_size": 2, "scan_id": scan_id, "chunk_index": 0},
            headers={"Authorization": f"Bearer {cfg.api_key}",
                     "X-Swarm-Tenant": tenant},
            timeout=30,
        ).status_code

    try:
        codes = [submit("alpha", "gwa_1"), submit("beta", "gwb_1")]
        noisy_codes = [submit("noisy", f"gwn{k}_1") for k in range(6)]
        admitted_noisy = [k for k, c in enumerate(noisy_codes) if c == 200]
        shed = noisy_codes.count(429)
        scans = ["gwa_1", "gwb_1"] + [f"gwn{k}_1" for k in admitted_noisy]
        worker = JobProcessor(Config(**{**cfg.__dict__, "worker_id": "gw-w"}))
        wt = _threading.Thread(target=worker.process_jobs, daemon=True)
        wt.start()
        client = JobClient(cfg.resolve_url(), cfg.api_key)
        deadline = time.time() + 180
        pending = set(scans)
        while time.time() < deadline and pending:
            time.sleep(0.2)
            statuses = client.get_statuses()
            if statuses is None:
                continue
            done = {
                s["scan_id"] for s in statuses.get("scans", [])
                if s["percent_complete"] == 100.0
            }
            pending -= done
        worker.stop_requested = True
        wt.join(timeout=30)
        ref = client.fetch_raw("gwa_1")
        identical = (
            not pending
            and bool(ref)
            and all(
                client.fetch_raw(s) == ref.replace("gwa_1", s)
                for s in scans[1:]
            )
        )
        rec = {
            "admitted": codes + [c for c in noisy_codes if c == 200],
            "shed_429": shed,
            "admitted_noisy": len(admitted_noisy),
            "scans_completed": len(scans) - len(pending),
            "identical": identical,
        }
        ok = identical and shed > 0 and all(c == 200 for c in codes)
        log(
            f"gateway smoke: {len(scans)} admitted scans complete, "
            f"{shed} shed (429), verdicts identical={identical}"
        )
        if not ok:
            log(f"!!! gateway smoke FAILED: {rec}")
        return ok, rec
    finally:
        srv.shutdown()


def _smoke_restart_clause() -> "tuple[bool, dict]":
    """Restart smoke (docs/DURABILITY.md): one mid-scan server restart
    against the durable queue journal. A real worker drains a scan
    while the server is torn down and rebuilt on the same port with a
    FRESH state store + the same blob store (journal + chunks); the
    gate is verdict identity vs a restart-free baseline run plus zero
    lost jobs (every chunk complete, nothing dead-lettered)."""
    import tempfile
    import threading as _threading

    from swarm_tpu.client.cli import JobClient
    from swarm_tpu.config import Config
    from swarm_tpu.server.app import SwarmServer
    from swarm_tpu.worker.runtime import JobProcessor

    tmp = tempfile.mkdtemp(prefix="swarm_restart_smoke_")
    modules_dir = os.path.join(tmp, "modules")
    os.makedirs(modules_dir)
    corpus = os.environ.get("SWARM_BENCH_CORPUS", str(BUNDLED_CORPUS))
    with open(os.path.join(modules_dir, "fingerprint.json"), "w") as f:
        json.dump({"backend": "tpu", "templates": corpus}, f)
    lines = [
        json.dumps(
            {"host": f"10.8.0.{i}", "port": 443, "status": 200,
             "body": f"<title>Demo Admin</title> demo-build 8.{i} page {i}"}
        ) + "\n"
        for i in range(8)
    ]
    n_chunks = len(lines)  # batch_size 1 → one job per row

    def _cfg(root: str) -> Config:
        return Config(
            host="127.0.0.1", port=0, api_key="rssmoke",
            blob_root=os.path.join(tmp, root, "blobs"),
            doc_root=os.path.join(tmp, root, "docs"),
            modules_dir=modules_dir,
            poll_interval_idle_s=0.02, poll_interval_busy_s=0.01,
            transport_retries=2, transport_backoff_s=0.02,
            transport_backoff_max_s=0.1,
            transport_breaker_threshold=500,
            lease_seconds=5.0, heartbeat_interval_s=0.25,
        )

    def _drain(cfg: Config, scan_id: str, max_jobs: int) -> str:
        worker = JobProcessor(
            Config(**{**cfg.__dict__, "worker_id": f"rs-{scan_id}",
                      "max_jobs": max_jobs})
        )
        worker.process_jobs()
        return JobClient(cfg.resolve_url(), cfg.api_key).fetch_raw(scan_id)

    def _submit(cfg: Config, scan_id: str) -> None:
        f = os.path.join(tmp, f"{scan_id}.jsonl")
        with open(f, "w") as fh:
            fh.writelines(lines)
        code, _ = JobClient(cfg.resolve_url(), cfg.api_key).start_scan(
            f, "fingerprint", 0, 1, scan_id=scan_id
        )
        assert code == 200

    # --- restart-free baseline ---
    base_cfg = _cfg("base")
    base_srv = SwarmServer(base_cfg)
    base_srv.start_background()
    base_cfg.server_url = f"http://127.0.0.1:{base_srv.port}"
    try:
        _submit(base_cfg, "rsbase_1")
        baseline_raw = _drain(base_cfg, "rsbase_1", n_chunks)
    finally:
        base_srv.shutdown()

    # --- live run with one mid-scan restart ---
    cfg = _cfg("live")
    srv = SwarmServer(cfg)
    srv.start_background()
    port = srv.port
    cfg.server_url = f"http://127.0.0.1:{port}"
    client = JobClient(cfg.resolve_url(), cfg.api_key)
    srv2 = None
    worker = JobProcessor(Config(**{**cfg.__dict__, "worker_id": "rs-live"}))
    wt = _threading.Thread(target=worker.process_jobs, daemon=True)
    try:
        _submit(cfg, "rsmoke_1")
        wt.start()
        deadline = time.time() + 120
        while time.time() < deadline:
            statuses = client.get_statuses()
            done = sum(
                1 for j in (statuses or {}).get("jobs", {}).values()
                if j.get("status") == "complete"
            )
            if done >= 2:
                break
            time.sleep(0.05)
        restarted_mid_scan = done < n_chunks
        srv.shutdown()  # the restart: in-memory job table dies here
        srv2 = SwarmServer(Config(**{**cfg.__dict__, "port": port}))
        srv2.start_background()
        complete = False
        while time.time() < deadline and not complete:
            time.sleep(0.1)
            statuses = client.get_statuses()
            if statuses is None:
                continue
            jobs = statuses.get("jobs", {})
            complete = len(jobs) == n_chunks and all(
                j.get("status") == "complete" for j in jobs.values()
            )
        worker.stop_requested = True
        wt.join(timeout=30)
        raw = client.fetch_raw("rsmoke_1")
        health = client.get_healthz() or {}
        identical = bool(baseline_raw) and raw == baseline_raw.replace(
            "rsbase_1", "rsmoke_1"
        )
        rec = {
            "identical": identical,
            "all_complete": complete,
            "restarted_mid_scan": restarted_mid_scan,
            "generation": health.get("generation"),
            "recovery": health.get("recovery"),
            "dead_letter": health.get("dead_letter_jobs"),
        }
        ok = (
            identical and complete
            and int(health.get("generation") or 0) >= 2
            and not health.get("dead_letter_jobs")
        )
        log(
            f"restart smoke: mid_scan={restarted_mid_scan} "
            f"generation={rec['generation']} identical={identical} "
            f"zero_lost={complete}"
        )
        if not ok:
            log(f"!!! restart smoke FAILED: {rec}")
        return ok, rec
    finally:
        worker.stop_requested = True
        if srv2 is not None:
            srv2.shutdown()


class _FleetStack:
    """Elastic-fleet harness for the autoscale phase and smoke clause:
    a real server whose fleet is the deterministic
    :class:`SimulatedProvider`, with a ``node_factory`` that attaches a
    REAL in-process worker to every node the moment its cold-start
    elapses. ONE copy of the bring-up / submit / completion-wait logic
    for both the phase and the smoke gate (same reasoning as
    :class:`_QosStack`) — and the same harness, minus the simulated
    provider, doubles as the fixed-fleet identity baseline."""

    def __init__(self, tag: str, extra_cfg: "dict | None" = None):
        import tempfile
        import threading as _threading

        from swarm_tpu.client.cli import JobClient
        from swarm_tpu.config import Config
        from swarm_tpu.server.app import SwarmServer
        from swarm_tpu.server.fleet import InflowForecaster
        from swarm_tpu.worker.runtime import JobProcessor

        self._threading = _threading
        self._Config = Config
        self._JobProcessor = JobProcessor
        tmp = tempfile.mkdtemp(prefix=f"swarm_fleet_{tag}_")
        modules_dir = os.path.join(tmp, "modules")
        os.makedirs(modules_dir)
        corpus = os.environ.get("SWARM_BENCH_CORPUS", str(BUNDLED_CORPUS))
        with open(os.path.join(modules_dir, "fingerprint.json"), "w") as f:
            json.dump({"backend": "tpu", "templates": corpus}, f)
        self.cfg = Config(
            host="127.0.0.1", port=0, api_key="fleet",
            blob_root=os.path.join(tmp, "blobs"),
            doc_root=os.path.join(tmp, "docs"),
            modules_dir=modules_dir,
            poll_interval_idle_s=0.02, poll_interval_busy_s=0.005,
            lease_seconds=3.0, heartbeat_interval_s=0.25,
            **(extra_cfg or {}),
        )
        self.workers: "dict[str, tuple]" = {}
        self.srv = SwarmServer(self.cfg)
        self.srv.start_background()
        self.cfg.server_url = f"http://127.0.0.1:{self.srv.port}"
        self.client = JobClient(self.cfg.resolve_url(), self.cfg.api_key)
        self.provider = self.srv.fleet
        self.advisor = self.srv.autoscaler
        if getattr(self.provider, "node_factory", "absent") is None:
            self.provider.node_factory = self._spawn_worker
        # compressed forecaster window: the diurnal curve replays in
        # seconds, not hours — the control LAW is what's under test,
        # so the EWMA must both rise within a step or two of the
        # shoulder and decay to zero within the scale-to-zero wait
        self.advisor.forecaster = InflowForecaster(alpha=0.7, window_s=0.2)

    def _spawn_worker(self, name: str):
        proc = self._JobProcessor(
            self._Config(**{**self.cfg.__dict__, "worker_id": name})
        )
        t = self._threading.Thread(target=proc.process_jobs, daemon=True)
        t.start()
        self.workers[name] = (proc, t)

        class _Handle:
            def stop(self):  # graceful spin-down rides the drain path
                proc.request_drain("spin-down")
                t.join(timeout=30)

            def kill(self):  # post-grace preemption force-kill: no
                proc.stop_requested = True  # drain, no spool flush

        return _Handle()

    def submit(self, scan_id: str, lines: list, batch: int = 1,
               qos=None) -> int:
        import requests as _requests

        headers = {"Authorization": f"Bearer {self.cfg.api_key}"}
        if qos:
            headers["X-Swarm-QoS"] = qos
        return _requests.post(
            f"{self.cfg.resolve_url()}/queue",
            json={"module": "fingerprint", "file_content": lines,
                  "batch_size": batch, "scan_id": scan_id,
                  "chunk_index": 0},
            headers=headers, timeout=30,
        ).status_code

    def wait_complete(self, scan_ids, deadline_s: float = 180,
                      autoscale: bool = False,
                      prefix: str = "node") -> bool:
        pending = set(scan_ids)
        deadline = time.time() + deadline_s
        tick = 0
        while time.time() < deadline and pending:
            time.sleep(0.05)
            tick += 1
            if autoscale and tick % 4 == 0:
                # keep the control loop closed while draining: boots
                # complete, kills land, and the advisor may still
                # scale (a mid-drain spin-down exercises the graceful
                # drain + requeue path under load)
                self.provider.poll()
                self.advisor.apply(prefix)
            statuses = self.client.get_statuses()
            if statuses is None:
                continue
            pending -= {
                s["scan_id"] for s in statuses.get("scans", [])
                if s["percent_complete"] == 100.0
            }
        return not pending

    def close(self) -> None:
        for proc, _t in self.workers.values():
            proc.stop_requested = True
        shutdown = getattr(self.provider, "shutdown", None)
        if shutdown:
            shutdown()
        for _proc, t in self.workers.values():
            t.join(timeout=10)
        self.srv.shutdown()


def bench_autoscale(
    curve: "list | None" = None,
    step_s: float = 0.45,
    n_preempts: int = 3,
    rows_per_submit: int = 4,
    full_gates: bool = True,
    deadline_s: float = 240,
) -> dict:
    """Closed-loop elastic-fleet replay (docs/RESILIENCE.md
    §Preemption, docs/GATEWAY.md): a diurnal submission curve against a
    REAL server whose fleet is the SimulatedProvider, the advisor's
    ``apply()`` spinning real in-process workers up and down, with
    seeded preemption notices landing on the spike. Gates:

    - zero lost jobs: every scan reaches 100%, nothing dead-lettered,
      across >= ``n_preempts`` preemptions and every drain/deregister;
    - /raw bit-identical to a fixed-fleet (one static worker) replay
      of the same submissions — elasticity and preemption change WHEN
      work runs, never WHAT it answers;
    - per-class shed ordering: at one fixed mid pressure, bulk sheds
      while interactive (and the default class) still admit;
    - (full gates) forecast lead >= 0: the EWMA forecaster shows a
      nonzero forecast on the spike's rising shoulder, at or before
      the peak submission step — the advisor scales AHEAD;
    - (full gates) scale-to-zero parks the idle fleet, and the re-warm
      cold-start (AOT-warm bring-up) lands within
      ``cfg.fleet_coldstart_slo_s``.
    """
    from swarm_tpu.gateway.admission import (
        AdmissionController,
        PressureSnapshot,
    )

    curve = curve or [1, 1, 2, 3, 6, 8, 6, 3, 1, 0, 0, 0]
    peak_step = max(range(len(curve)), key=lambda i: curve[i])
    lines = [
        json.dumps(
            {"host": f"10.9.0.{i}", "port": 443, "status": 200,
             "body": f"<title>Demo Admin</title> demo-build 9.{i} "
                     f"page {i}"}
        ) + "\n"
        for i in range(rows_per_submit)
    ]
    extra = dict(
        fleet_provider="sim",
        gateway_autoscale_apply=True,
        gateway_autoscale_jobs_per_node=2,
        gateway_autoscale_min_nodes=0,
        gateway_autoscale_max_nodes=3,
        fleet_scaledown_hysteresis=2,
        fleet_sim_preempt_grace_s=1.5,
        fleet_scale_to_zero_after_s=(0.8 if full_gates else 0.0),
    )
    prefix = "node"
    stack = _FleetStack("elastic", extra_cfg=extra)
    scan_ids: list = []
    steps: list = []
    preempted: list = []
    try:
        # --- elastic arm: replay the curve, advisor in the loop ---
        sidx = 0
        for step, n_sub in enumerate(curve):
            t_step = time.perf_counter()
            for _ in range(n_sub):
                sid = f"ase{sidx}_1"
                sidx += 1
                assert stack.submit(sid, lines, 1) == 200
                scan_ids.append(sid)
            stack.provider.poll()
            rec = stack.advisor.apply(prefix)
            steps.append({
                "step": step, "submitted": n_sub,
                "depth": rec["queue_depth"],
                "forecast_jobs": rec["forecast_jobs"],
                "target": rec["target_nodes"],
                "nodes": rec["current_nodes"],
                "action": rec["action"],
            })
            # seeded preemptions land on the spike: one notice per
            # step from the peak on, against a node that is actually
            # up, until the quota is in
            if len(preempted) < n_preempts and step >= peak_step:
                ready = [
                    n for n in stack.provider.ready_nodes(prefix)
                    if n not in preempted
                ]
                if ready:
                    stack.provider.preempt(ready[0])
                    preempted.append(ready[0])
            lag = step_s - (time.perf_counter() - t_step)
            if lag > 0:
                time.sleep(lag)
        all_done = stack.wait_complete(
            scan_ids, deadline_s=deadline_s, autoscale=True,
            prefix=prefix,
        )

        # --- scale-to-zero + re-warm (full gates only) ---
        s2z = {"parked": None, "coldstart_s": None, "rewarm_ok": None}
        if full_gates and all_done:
            park_deadline = time.time() + 30
            parked = False
            while time.time() < park_deadline and not parked:
                stack.provider.poll()
                rec = stack.advisor.apply(prefix)
                parked = (
                    rec["target_nodes"] == 0
                    and not stack.provider.list_nodes(prefix)
                )
                time.sleep(0.15)
            s2z["parked"] = parked
            if parked:
                mark = len(stack.provider.events)
                rw = "aserw_1"
                assert stack.submit(rw, lines, 1) == 200
                stack.provider.poll()
                stack.advisor.apply(prefix)
                s2z["rewarm_ok"] = stack.wait_complete(
                    [rw], deadline_s=60, autoscale=True, prefix=prefix,
                )
                scan_ids.append(rw)
                spun: dict = {}
                cold: list = []
                for t, ev, name in list(stack.provider.events)[mark:]:
                    if ev == "spin_up":
                        spun[name] = t
                    elif ev == "ready" and name in spun:
                        cold.append(t - spun[name])
                if cold:
                    s2z["coldstart_s"] = round(max(cold), 3)

        notices = sum(
            1 for _t, ev, _n in stack.provider.events
            if ev == "preempt_notice"
        )
        health = stack.client.get_healthz() or {}
        drain_outcomes = [
            p.drain_outcome for p, _t in stack.workers.values()
            if p.drain_outcome is not None
        ]
        elastic_raw = {s: stack.client.fetch_raw(s) for s in scan_ids}
    finally:
        stack.close()

    # --- fixed-fleet identity baseline: same submissions, one static
    # worker, no provider — elasticity must change nothing in /raw ---
    base = _FleetStack("fixed")
    try:
        base._spawn_worker("fixed1")
        base_ids = [s.replace("ase", "asb", 1) for s in scan_ids]
        for bsid in base_ids:
            assert base.submit(bsid, lines, 1) == 200
        base_done = base.wait_complete(base_ids, deadline_s=deadline_s)
        identical = base_done and all(
            elastic_raw[sid]
            == (base.client.fetch_raw(bsid) or "").replace("asb", "ase")
            for sid, bsid in zip(scan_ids, base_ids)
        )
    finally:
        base.close()

    # --- per-class shed ordering: bulk sheds first, deterministically
    ctl = AdmissionController(
        shed_pressure=0.9, shed_pressure_bulk=0.5,
        shed_pressure_interactive=0.95,
    )
    snap = PressureSnapshot(saturation=0.7)
    shed_order_ok = (
        not ctl.decide("t_b", snap, 0.0, qos="bulk").admitted
        and ctl.decide("t_i", snap, 0.0, qos="interactive").admitted
        and ctl.decide("t_d", snap, 0.0).admitted
    )

    first_forecast = next(
        (s["step"] for s in steps if s["forecast_jobs"] > 0), None
    )
    forecast_lead = (
        peak_step - first_forecast if first_forecast is not None else None
    )
    slo = getattr(stack.cfg, "fleet_coldstart_slo_s", 2.0)
    zero_lost = bool(all_done and not health.get("dead_letter_jobs"))
    ok = (
        zero_lost
        and identical
        and shed_order_ok
        and notices >= n_preempts
    )
    if full_gates:
        ok = ok and (
            forecast_lead is not None and forecast_lead >= 0
            and bool(s2z["parked"]) and bool(s2z["rewarm_ok"])
            and s2z["coldstart_s"] is not None
            and s2z["coldstart_s"] <= slo
        )
    return {
        "ok": ok,
        "zero_lost": zero_lost,
        "identical": identical,
        "shed_order_ok": shed_order_ok,
        "preempt_notices": notices,
        "preempted_nodes": preempted,
        "drain_outcomes": drain_outcomes,
        "forecast_lead_steps": forecast_lead,
        "scale_to_zero": s2z,
        "coldstart_slo_s": slo,
        "dead_letter": health.get("dead_letter_jobs"),
        "draining_at_end": health.get("draining_workers"),
        "steps": steps,
    }


def _smoke_autoscale_clause() -> "tuple[bool, dict]":
    """Autoscale smoke (docs/RESILIENCE.md §Preemption): a mini
    diurnal curve against the simulated preemptible fleet with ONE
    seeded preemption notice — rc-gated on zero lost jobs, the notice
    actually landing, per-class shed ordering, and /raw identity vs
    the fixed-fleet baseline. Under the chaos plan the armed
    ``fleet.preempt`` / ``worker.drain`` faults additionally inject a
    dispatch-path preemption and one aborted drain; the identity gate
    must hold regardless (spool + fencing + lease expiry own the
    recovery)."""
    rec = bench_autoscale(
        curve=[1, 2, 4, 2, 0, 0], step_s=0.4, n_preempts=1,
        full_gates=False, deadline_s=120,
    )
    ok = bool(rec.get("ok"))
    if not ok:
        log(f"!!! autoscale smoke FAILED: "
            f"{ {k: v for k, v in rec.items() if k != 'steps'} }")
    return ok, rec


def _smoke_qos_clause() -> "tuple[bool, dict]":
    """QoS smoke (docs/GATEWAY.md §QoS): one bulk flood + interactive
    probes against a REAL server + worker with the shared tier on
    (the same :class:`_QosStack` harness the latency phase's arms
    use). The rc gates: probe verdict identity (the express lane and
    the gateway cache change WHEN, never WHAT), measured express-lane
    use (swarm_queue_express_served_total advanced), and — fault-
    plan-free runs only, since the chaos plan's cache.get/cache.put
    injections force the documented pass-through — the gateway-cache
    short-circuit (the repeated probe completes with attempts == 0:
    zero worker dispatch)."""
    from swarm_tpu.resilience.faults import active_plan
    from swarm_tpu.server.queue import _EXPRESS_SERVED

    probe_line = (
        json.dumps(
            {"host": "203.0.113.9", "port": 443, "status": 200,
             "body": "<title>QoS Probe Admin</title> qos-probe-build 1"}
        ) + "\n"
    )
    flood_lines = [
        json.dumps(
            {"host": f"10.7.0.{i}", "port": 443, "status": 200,
             "body": f"<title>Demo Admin</title> demo-build 9.{i}"}
        ) + "\n"
        for i in range(8)
    ]
    stack = _QosStack(
        "smoke", cache_backend="memory",
        # the scheduler's express-bucket path rides the smoke's
        # pipeline mode (preflight invokes both)
        pipeline=os.environ.get("SWARM_PIPELINE", "off"),
        busy_s=0.01,
    )
    x0 = _EXPRESS_SERVED.labels().value
    try:
        codes = [
            stack.submit("qsflood_1", flood_lines, 2),
            stack.submit("qsprobe1_1", [probe_line], 1, qos="interactive"),
        ]
        done, _ = stack.wait_complete(
            ["qsflood_1", "qsprobe1_1"], deadline_s=240
        )
        express_served = _EXPRESS_SERVED.labels().value - x0
        # the repeat: fleet-known content must answer at the gateway
        codes.append(
            stack.submit("qsprobe2_1", [probe_line], 1, qos="interactive")
        )
        done2, statuses = stack.wait_complete(["qsprobe2_1"], deadline_s=240)
        done = done and done2
        raw1 = stack.client.fetch_raw("qsprobe1_1")
        raw2 = stack.client.fetch_raw("qsprobe2_1")
        probe2 = [
            j for j in statuses.get("jobs", {}).values()
            if j.get("scan_id") == "qsprobe2_1"
        ]
        short_circuited = bool(probe2) and all(
            j.get("attempts") == 0 for j in probe2
        )
        identical = bool(raw1) and raw1 == raw2
        chaos = active_plan() is not None
        rec = {
            "codes": codes,
            "all_complete": bool(done),
            "identical": identical,
            "express_served": int(express_served),
            "short_circuited": short_circuited,
            "chaos_plan": chaos,
        }
        ok = (
            done and identical and express_served > 0
            and all(c == 200 for c in codes)
            and (short_circuited or chaos)
        )
        log(
            f"qos smoke: express_served={int(express_served)} "
            f"short_circuited={short_circuited} identical={identical}"
            + (" (chaos: short-circuit gate relaxed)" if chaos else "")
        )
        if not ok:
            log(f"!!! qos smoke FAILED: {rec}")
        return ok, rec
    finally:
        stack.close()


def _monitor_bruteforce_feed(blobs, monitor_id: str) -> list:
    """Brute-force replay of a monitor's ENTIRE change feed from first
    principles: for every marked epoch, re-read the epoch scan's stored
    chunk inputs/outputs straight from the blob store and re-run the
    pure diff over the replayed prior plane. Returns canonical record
    bytes — the bench gate is the stored feed being BIT-IDENTICAL to
    this replay (docs/MONITORING.md §Diff records)."""
    from swarm_tpu.datamodel import chunk_input_key, chunk_output_key
    from swarm_tpu.monitor import feed as mfeed
    from swarm_tpu.monitor.diff import (
        diff_epoch,
        encode_record,
        extract_verdicts,
    )

    plane: dict = {}
    out: list = []
    seq = 0
    for epoch in mfeed.marked_epochs(blobs, monitor_id):
        mark = json.loads(
            blobs.get(mfeed.mark_key(monitor_id, epoch)).decode()
        )
        sid = mark["scan_id"]
        chunks: list = []
        while blobs.exists(chunk_input_key(sid, len(chunks))):
            raw = blobs.get(chunk_input_key(sid, len(chunks)))
            # exact inverse of queue_scan's '\n'.join persistence
            chunks.append(
                raw.decode("utf-8", "surrogateescape").split("\n")
            )
        outputs = {
            j: blobs.get(chunk_output_key(sid, j))
            for j in range(len(chunks))
            if blobs.exists(chunk_output_key(sid, j))
        }
        records, plane = diff_epoch(
            monitor_id, epoch, plane,
            extract_verdicts(chunks, outputs),
            [t for c in chunks for t in c], seq,
        )
        seq += len(records)
        out.extend(encode_record(r) for r in records)
    return out


def _monitor_register(
    stack: "_QosStack", monitor_id: str, targets: list,
    interval_s: float = 3600.0,
) -> int:
    import requests as _requests

    return _requests.post(
        f"{stack.cfg.resolve_url()}/monitor",
        json={"monitor_id": monitor_id, "module": "fingerprint",
              "targets": targets, "interval_s": interval_s,
              "batch_size": 1},
        headers={"Authorization": f"Bearer {stack.cfg.api_key}"},
        timeout=30,
    ).status_code


def _monitor_drive_epoch(
    stack: "_QosStack", monitor_id: str, deadline_s: float = 600.0
) -> bool:
    """Fire exactly one epoch (forced-due tick) and wait for its diff
    commit. The stack's ticker thread is parked (monitor_tick_s high),
    so the bench owns the cadence deterministically. Waits for the
    epoch scan's STATUS completion (not just its output blobs) before
    draining: the completion POST is also the gateway-cache writeback
    site, and the next epoch's zero-dispatch accounting must not race
    the last chunk's writeback."""
    mon = stack.srv.monitor
    if mon.tick(now=time.time() + 86400.0) != 1:
        return False
    spec = stack.srv.queue.get_monitor(monitor_id) or {}
    sid = spec.get("last_scan_id")
    if sid:
        stack.wait_complete([sid], deadline_s=deadline_s)
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        if mon.drain():
            return True
        time.sleep(0.05)
    return False


def bench_monitor(
    n_targets: int = 100, epochs: int = 4, changed_per_epoch: int = 5
) -> dict:
    """Continuous-monitoring cost + correctness run (docs/MONITORING.md
    §Cost model): ONE standing spec over ``n_targets`` fingerprint
    targets at batch 1, driven through ``epochs`` epochs against a real
    server + worker with the shared tier on. Between epochs,
    ``changed_per_epoch`` targets mutate (the 95%-unchanged fleet);
    everything else must be answered by the per-target gateway cache
    with ZERO dispatch. Returns per-epoch dispatched/cached chunk
    counts and whether the stored feed is bit-identical to the
    brute-force replay diff — the caller owns the rc gates."""
    from swarm_tpu.monitor.feed import feed_prefix

    def line(i: int, rev: int) -> str:
        # matches the bundled demo-panel template (title + demo-build
        # words), so every target carries a real non-empty finding and
        # a rev bump changes the extracted version string
        return json.dumps(
            {"host": f"198.51.100.{i % 250}", "port": 443, "status": 200,
             "body": f"<title>Demo Admin</title> demo-build {i}.{rev}"}
        ) + "\n"

    revs = [0] * n_targets
    stack = _QosStack(
        "monitor", cache_backend="memory",
        extra_cfg={"monitor_tick_s": 3600.0},
    )
    mid = "benchmon"
    try:
        dispatched: list = []
        cached: list = []
        for k in range(1, epochs + 1):
            if k > 1:
                base = ((k - 2) * changed_per_epoch) % n_targets
                for j in range(changed_per_epoch):
                    revs[(base + j) % n_targets] += 1
            targets = [line(i, revs[i]) for i in range(n_targets)]
            code = _monitor_register(stack, mid, targets)
            if code != 200:
                return {"ok_run": False, "reason": f"register -> {code}"}
            if not _monitor_drive_epoch(stack, mid):
                return {"ok_run": False,
                        "reason": f"epoch {k} did not complete"}
            statuses = stack.client.get_statuses() or {}
            jobs = [
                j for j in statuses.get("jobs", {}).values()
                if j.get("monitor_epoch") == k
            ]
            dispatched.append(
                sum(1 for j in jobs if j.get("started_at"))
            )
            cached.append(
                sum(1 for j in jobs if not j.get("started_at"))
            )
        blobs = stack.srv.queue.blobs
        stored = b"".join(
            blobs.get(key) for key in blobs.list(feed_prefix(mid))
        )
        replay = b"".join(_monitor_bruteforce_feed(blobs, mid))
        first = max(1, dispatched[0])
        steady = max(dispatched[1:]) if len(dispatched) > 1 else 0
        return {
            "ok_run": True,
            "n_targets": n_targets,
            "epochs": epochs,
            "changed_per_epoch": changed_per_epoch,
            "dispatched": dispatched,
            "cached": cached,
            "steady_cost_ratio": round(steady / first, 4),
            "replay_identical": bool(stored) and stored == replay,
            "feed_records": stored.count(b"\n"),
            "gateway_cache": stack.srv.qos_cache.counters()
            if stack.srv.qos_cache is not None else {},
        }
    finally:
        stack.close()


def _smoke_monitor_clause() -> "tuple[bool, dict]":
    """Monitor smoke (docs/MONITORING.md): a 2-epoch mini-monitor (one
    target changed between epochs) through the same harness as the full
    phase. The rc gates: the stored change feed is bit-identical to the
    brute-force replay diff, and the second epoch saw at least one
    ZERO-DISPATCH rescan chunk (the per-target gateway cache answered
    fleet-known content). Under an armed chaos plan the zero-dispatch
    gate is relaxed — the plan's cache.get/cache.put injections force
    the documented pass-through — but the replay-identity gate always
    holds."""
    from swarm_tpu.resilience.faults import active_plan

    rec = bench_monitor(n_targets=8, epochs=2, changed_per_epoch=1)
    chaos = active_plan() is not None
    rec["chaos_plan"] = chaos
    if not rec.get("ok_run"):
        log(f"!!! monitor smoke FAILED: {rec}")
        return False, rec
    zero_dispatch = rec["cached"][1] >= 1
    ok = rec["replay_identical"] and (zero_dispatch or chaos)
    log(
        f"monitor smoke: epochs dispatched={rec['dispatched']} "
        f"cached={rec['cached']} replay_identical="
        f"{rec['replay_identical']}"
        + (" (chaos: zero-dispatch gate relaxed)" if chaos else "")
    )
    if not ok:
        log(f"!!! monitor smoke FAILED: {rec}")
    return ok, rec


def _smoke_trace_clause() -> "tuple[bool, dict]":
    """Trace-waterfall smoke (docs/OBSERVABILITY.md §Tracing): one scan
    through a REAL server + worker with tracing enabled. The rc gates:
    the assembled waterfall exists, has ZERO orphan spans (every span's
    parent resolves — a lossy assembly would break attribution
    silently), and its root-level segments sum to within 10% of the
    scan's gateway-latency observation. Under an armed chaos plan the
    sum gate is relaxed (injected faults force retries whose re-queue
    gaps legitimately stretch the window) but the orphan gate holds —
    a retried attempt must still link into ONE waterfall."""
    from swarm_tpu.resilience.faults import active_plan
    from swarm_tpu.telemetry import tracing

    tracing.set_enabled(True)
    stack = _QosStack(
        "trace",
        # ride the smoke's pipeline mode so the sched span path is
        # exercised when preflight's pipeline=on invocation runs
        pipeline=os.environ.get("SWARM_PIPELINE", "off"),
        busy_s=0.01,
    )
    try:
        lines = [
            json.dumps(
                {"host": f"10.6.0.{i}", "port": 443, "status": 200,
                 "body": f"<title>Demo Admin</title> demo-build 6.{i}"}
            ) + "\n"
            for i in range(4)
        ]
        code = stack.submit("trsmoke_1", lines, 2)
        done, _ = stack.wait_complete(["trsmoke_1"], deadline_s=240)
        doc = stack.client.get_trace("trsmoke_1")
        chaos = active_plan() is not None
        if doc is None:
            rec = {"code": code, "all_complete": bool(done), "doc": None}
            log(f"!!! trace smoke FAILED (no waterfall): {rec}")
            return False, rec
        orphans = tracing.waterfall_orphans(doc)
        gl = float(doc.get("gateway_latency_s") or 0.0)
        seg = float(doc.get("segments_sum_s") or 0.0)
        within = gl > 0 and abs(seg - gl) / gl <= 0.10
        cp = tracing.critical_path(doc)
        rec = {
            "code": code,
            "all_complete": bool(done),
            "status": doc.get("status"),
            "span_count": len(doc.get("spans", [])),
            "span_names": sorted({s["name"] for s in doc.get("spans", [])}),
            "orphans": len(orphans),
            "gateway_latency_s": round(gl, 4),
            "segments_sum_s": round(seg, 4),
            "within_10pct": within,
            "critical_path": [
                (n, round(s, 4), round(f, 3)) for n, s, f in cp[:4]
            ],
            "chaos_plan": chaos,
        }
        ok = (
            code == 200 and bool(done) and not orphans
            and (within or chaos)
        )
        log(
            f"trace smoke: {rec['span_count']} spans, "
            f"{len(orphans)} orphan(s), segments {seg:.3f}s vs gateway "
            f"{gl:.3f}s (within 10%: {within})"
            + (" (chaos: sum gate relaxed)" if chaos else "")
        )
        if not ok:
            log(f"!!! trace smoke FAILED: {rec}")
        return ok, rec
    finally:
        stack.close()
        tracing.set_enabled(None)  # back to env/config-driven default


def _aot_child() -> int:
    """Child entry of the AOT cold-start A/B (docs/AOT.md): ONE fresh
    process measuring engine bring-up — corpus load (dbcache-warm, so
    both arms pay the same host cost) then DeviceDB construction
    through the first full-plane match. Mode ``fetch`` attaches a
    local-dir AOT store (empty store ⇒ this child is the publisher;
    warm store ⇒ it loads instead of compiling); mode ``compile`` is
    the no-AOT reference arm. Prints one ``AOTCHILD {json}`` line."""
    import hashlib

    resolve_device()
    mode = os.environ.get("SWARM_AOT_CHILD_MODE", "compile")
    root = os.environ.get("SWARM_AOT_CHILD_DIR", "")
    corpus = Path(
        os.environ.get("SWARM_BENCH_CORPUS", str(BUNDLED_CORPUS))
    )
    from swarm_tpu.fingerprints.dbcache import load_or_compile
    from swarm_tpu.ops.encoding import encode_batch
    from swarm_tpu.ops.match import DeviceDB

    templates, db = load_or_compile(corpus)
    rows = realistic_rows(64, seed=5)
    batch = encode_batch(
        rows, max_body=1024, max_header=512, pad_rows_to=64
    )
    client = None
    if mode == "fetch" and root:
        from swarm_tpu.aot import build_aot_client
        from swarm_tpu.config import Config

        client = build_aot_client(
            Config(
                aot_backend="local",
                aot_dir=root,
                worker_id=f"bench-aot-{os.getpid()}",
            )
        )
    t0 = time.perf_counter()
    dev = DeviceDB(db)
    if client is not None:
        dev.attach_aot(client)
        dev.aot_prewarm()
    planes = dev.match(
        batch.streams, batch.lengths, batch.status, full=True
    )
    bringup = time.perf_counter() - t0
    h = hashlib.sha256()
    for p in planes:
        h.update(np.ascontiguousarray(np.asarray(p)).tobytes())
    rec = {
        "mode": mode,
        "bringup_seconds": round(bringup, 4),
        "planes_sha256": h.hexdigest(),
        "executable_count": dev.executable_count(),
        "fetched_executable_count": dev.fetched_executable_count(),
        "compile_count": dev.compile_count,
        "fetch_count": dev.fetch_count,
    }
    print("AOTCHILD " + json.dumps(rec), flush=True)
    return 0


def bench_aot_coldstart(reps: int = 2, timeout_s: int = 900) -> dict:
    """Fresh-process fetch-vs-compile bring-up A/B (docs/AOT.md):
    seed a file-backed artifact store with one publisher child, then
    run PAIRED fresh-process reps — a no-AOT compile arm and a
    warm-store fetch arm, alternating — and gate on every child's
    verdict planes hashing identically. The per-process persistent
    XLA cache is disabled in the children (a joining fleet node's
    local cache is cold; that is the cliff being measured)."""
    import statistics
    import subprocess
    import tempfile

    store_dir = tempfile.mkdtemp(prefix="swarm_bench_aot_")

    def child(mode: str):
        env = dict(os.environ)
        env["SWARM_AOT_CHILD_MODE"] = mode
        env["SWARM_AOT_CHILD_DIR"] = store_dir
        # the persistent XLA cache OFF in every child (same directory,
        # never read) — the scenario is a fresh autoscaled node, and a
        # warm persistent cache would fake the compile arm's cost
        env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
        # the chaos plan's AOT levers (aot.fetch/aot.put) are THIS
        # clause's contract; the engine-layer levers (device.dispatch
        # etc.) are exercised by the engine-backed clauses and would
        # kill a raw-DeviceDB child that has no breaker to absorb them
        plan = env.get("SWARM_FAULT_PLAN", "")
        if plan:
            kept = [
                item
                for item in plan.split(";")
                if item.startswith(("seed=", "aot."))
            ]
            env["SWARM_FAULT_PLAN"] = ";".join(kept)
        try:
            r = subprocess.run(
                [sys.executable, __file__, "--phase", "aot_child"],
                stdout=subprocess.PIPE,
                text=True,
                env=env,
                timeout=timeout_s,
            )
        except subprocess.TimeoutExpired:
            return None
        if r.returncode != 0:
            return None
        for line in r.stdout.splitlines():
            if line.startswith("AOTCHILD "):
                try:
                    return json.loads(line[len("AOTCHILD "):])
                except json.JSONDecodeError:
                    return None
        return None

    import shutil

    try:
        seed = child("fetch")  # empty store: compiles AND publishes
        if seed is None:
            return {"ok": False, "reason": "seed child failed"}
        compile_s: list = []
        fetch_s: list = []
        warm: list = []
        digests = {seed["planes_sha256"]}
        for i in range(max(reps, 1)):
            # alternate the arm order so drift (page cache, thermal)
            # can't systematically favor one side
            order = (
                ("compile", "fetch") if i % 2 == 0 else ("fetch", "compile")
            )
            for mode in order:
                rec = child(mode)
                if rec is None:
                    return {"ok": False, "reason": f"{mode} child failed"}
                digests.add(rec["planes_sha256"])
                if mode == "compile":
                    compile_s.append(rec["bringup_seconds"])
                else:
                    fetch_s.append(rec["bringup_seconds"])
                    warm.append(rec)
    finally:
        # the store holds serialized executables (MBs per shape class)
        # — a leaked dir per smoke/bench run would steadily fill /tmp
        shutil.rmtree(store_dir, ignore_errors=True)
    identical = len(digests) == 1
    from swarm_tpu.resilience.faults import active_plan

    # the children inherit SWARM_FAULT_PLAN via env, so the plan may
    # be armed there even before this process fired any point
    chaos = active_plan() is not None or bool(
        os.environ.get("SWARM_FAULT_PLAN", "")
    )
    # zero-compile is the warm-fetch contract — except under an armed
    # chaos plan, where injected aot.fetch faults legitimately force
    # the fallback compile (the identity gate still holds)
    warm_zero_compile = all(r["compile_count"] == 0 for r in warm)
    med_c = statistics.median(compile_s)
    med_f = statistics.median(fetch_s)
    return {
        "ok": identical and (warm_zero_compile or chaos),
        "identical": identical,
        "warm_zero_compile": warm_zero_compile,
        "chaos_plan": chaos,
        "speedup": med_c / max(med_f, 1e-9),
        "compile_bringup_seconds": med_c,
        "fetch_bringup_seconds": med_f,
        "seed": seed,
        "warm_fetched": [r["fetched_executable_count"] for r in warm],
    }


def _smoke_aot_clause() -> "tuple[bool, dict]":
    """AOT cold-start smoke (docs/AOT.md): one seed + one paired
    fresh-process rep on the bundled corpus, rc-gated on verdict-plane
    identity across every arm AND on the warm fetch compiling nothing
    (relaxed to identity-only under an armed chaos fault plan, whose
    aot.fetch/aot.put injections force the documented fallback)."""
    rec = bench_aot_coldstart(reps=1)
    ok = bool(rec.get("ok"))
    if not ok:
        log(f"!!! AOT smoke FAILED: {rec}")
    return ok, rec


def _smoke_shard_child() -> "tuple[bool, list]":
    """Shard smoke: the sharded serving path on the 8-device host-
    platform mesh, in its OWN subprocess — the forced device-count flag
    also reshapes XLA's CPU thread pools, and the A/B clauses must keep
    the single-device measurement basis preflight has recorded all
    along. → (rc ok, the child's JSON lines)."""
    import subprocess

    try:
        r = subprocess.run(
            [sys.executable, __file__, "--phase", "shard_smoke"],
            stdout=subprocess.PIPE,
            text=True,
            timeout=900,
        )
    except subprocess.TimeoutExpired:
        log("!!! shard smoke timed out — smoke FAILED")
        return False, []
    lines = [ln for ln in r.stdout.splitlines() if ln.strip().startswith("{")]
    return r.returncode == 0, lines


def run_smoke() -> int:
    """CI-fast pipeline A/B (tools/preflight.sh): bundled corpus,
    tiny batches, no subprocess phases. Honors SWARM_PIPELINE as the
    engine's configured mode (recorded in the emitted line) and always
    A/Bs both modes. rc 1 on any verdict mismatch between modes — the
    exactness contract is the gate; speed is recorded, not gated
    (preflight machines are noisy). Under SWARM_FAULT_PLAN this doubles
    as the chaos smoke: injected faults must leave the A/B verdicts
    identical (rc-gated), and the fault-free runs additionally record
    the resilience layer's measured no-op overhead."""
    global ROWS, ITERS
    ROWS, ITERS = 256, 2
    os.environ.setdefault("SWARM_BENCH_CORPUS", str(BUNDLED_CORPUS))
    # the clauses that start JAX child processes run FIRST, while this
    # process has not touched JAX: on a chip host a parent holding the
    # device leaves its children none
    aot_ok, aot_rec = _smoke_aot_clause()
    shard_ok, shard_lines = _smoke_shard_child()
    templates, db, _dev = _setup_phase(need_corpus=True)
    from swarm_tpu.ops.engine import MatchEngine

    mode = os.environ.get("SWARM_PIPELINE", "off")
    eng = MatchEngine(
        templates, mesh=None, batch_rows=ROWS, max_body=MAX_BODY,
        max_header=MAX_HEADER, db=db, pipeline=mode,
    )
    ab = bench_pipeline_ab(eng, chunk_rows=256, n_chunks=6)
    ok = ab["verdicts_identical"]
    speed = ab["fresh"]["on"]["rows_per_sec"] / max(
        ab["fresh"]["off"]["rows_per_sec"], 1e-9
    )
    # walk A/B rides the smoke too: serial vs batched walk must be
    # bit-identical (rc-gated); the speedup is recorded, not gated
    # (CI hosts are noisy and often core-starved)
    wab = bench_walk_ab(templates, n_rows=256, n_batches=2, reps=2)
    ok = ok and wab["identical"]
    emit(
        "smoke_walk_ab_speedup",
        wab["speedup"],
        "x (batched/serial host walk, bundled-corpus+stress smoke)",
        wab["speedup"],
        extra={"walk_ab": wab},
    )
    # workflow A/B rides the smoke too (docs/WORKFLOWS.md): device
    # gate planes vs the bit-identical host twin over a workflow-heavy
    # synthetic fleet on ONE engine — per-row result equality is
    # rc-gated on every repeat; the speedup is recorded, not gated
    wfab = bench_workflow_ab(
        templates, n_rows=128, n_batches=2, reps=2, n_workflows=8
    )
    ok = ok and wfab["identical"]
    emit(
        "smoke_workflow_ab_speedup",
        wfab["speedup"],
        "x (device gate planes vs host-twin workflow gating, "
        "bundled-corpus smoke)",
        wfab["speedup"],
        extra={"workflow_ab": wfab},
    )
    # dedup fleet-replay smoke (docs/CACHING.md): the shared result
    # tier FORCED ON for a second engine lifetime — verdicts must be
    # bit-identical to the tier-off lifetime (rc-gated); speed and hit
    # ratio are recorded, not gated (CI hosts are noisy). Under
    # SWARM_FAULT_PLAN this doubles as the tier's chaos clause: a
    # faulted cache.get/cache.put degrades to L1-only and the identity
    # gate still holds.
    ded = bench_dedup_fleet(templates, db=db, n_rows=192, reps=2)
    ok = ok and ded["identical"]
    emit(
        "smoke_dedup_warm_speedup",
        ded["speedup"],
        "x (tier-on vs tier-off second engine lifetime, "
        "bundled-corpus smoke)",
        ded["speedup"],
        extra={"dedup": ded},
    )
    # AOT cold-start smoke (docs/AOT.md, run above before this process
    # touched JAX): fresh-process fetch-vs-compile bring-up over a
    # file-backed store — rc-gated on verdict identity across every
    # arm, and on the warm fetch compiling nothing (identity-only under
    # the chaos plan, whose aot.* faults force the documented compile
    # fallback)
    ok = ok and aot_ok
    emit(
        "smoke_aot_coldstart_speedup",
        aot_rec.get("speedup", 0.0),
        "x (fresh-process compile vs warm-fetch bring-up, "
        "bundled-corpus smoke)",
        aot_rec.get("speedup", 0.0),
        extra={
            "aot": {k: v for k, v in aot_rec.items() if k != "seed"}
        },
    )
    # gateway smoke (docs/GATEWAY.md): 3 tenants (one rate-limited)
    # against a real server + worker — rc-gated on cross-tenant verdict
    # identity AND on the abusive tenant observing at least one shed
    gw_ok, gw_rec = _smoke_gateway_clause()
    ok = ok and gw_ok
    emit(
        "smoke_gateway_shed_count",
        float(gw_rec["shed_429"]),
        " sheds (429) observed by the rate-limited smoke tenant",
        float(gw_rec["shed_429"]),
        extra={"gateway": gw_rec},
    )
    # QoS smoke (docs/GATEWAY.md §QoS): bulk flood + interactive probes
    # against a real server + worker — rc-gated on probe verdict
    # identity, measured express-lane use, and (fault-plan-free runs)
    # the gateway-cache short-circuit
    qos_ok, qos_rec = _smoke_qos_clause()
    ok = ok and qos_ok
    emit(
        "smoke_qos_express_served",
        float(qos_rec.get("express_served", 0)),
        " express-lane dispatches (interactive probes vs bulk flood; "
        "identity + short-circuit rc-gated)",
        1.0 if qos_ok else 0.0,
        extra={"qos": qos_rec},
    )
    # monitor smoke (docs/MONITORING.md): a 2-epoch mini-monitor —
    # rc-gated on the stored feed matching the brute-force replay diff
    # and (fault-plan-free runs) at least one zero-dispatch rescan
    # chunk riding the per-target gateway cache
    mon_ok, mon_rec = _smoke_monitor_clause()
    ok = ok and mon_ok
    emit(
        "smoke_monitor_zero_dispatch_chunks",
        float((mon_rec.get("cached") or [0, 0])[-1]),
        " epoch-2 chunks answered with zero dispatch (replay-identity "
        "rc-gated)",
        1.0 if mon_ok else 0.0,
        extra={"monitor": mon_rec},
    )
    # trace smoke (docs/OBSERVABILITY.md §Tracing): one traced scan
    # through a real server + worker — rc-gated on an assembled
    # waterfall with zero orphan spans whose segments sum within 10%
    # of the gateway latency observation (sum gate relaxed under the
    # chaos plan; the orphan gate always holds)
    tr_ok, tr_rec = _smoke_trace_clause()
    ok = ok and tr_ok
    emit(
        "smoke_trace_waterfall",
        1.0 if tr_ok else 0.0,
        " (assembled waterfall: zero orphans + segments within 10% "
        "of gateway latency)",
        1.0 if tr_ok else 0.0,
        extra={"trace": tr_rec},
    )
    # restart smoke (docs/DURABILITY.md): one mid-scan server restart
    # against the durable journal — rc-gated on verdict identity vs the
    # restart-free baseline AND zero lost jobs
    rs_ok, rs_rec = _smoke_restart_clause()
    ok = ok and rs_ok
    emit(
        "smoke_restart_identity",
        1.0 if rs_ok else 0.0,
        " (mid-scan server restart: raw identity + zero lost jobs)",
        1.0 if rs_ok else 0.0,
        extra={"restart": rs_rec},
    )
    # autoscale smoke (docs/RESILIENCE.md §Preemption): mini diurnal
    # curve against the simulated preemptible fleet, one seeded
    # preemption — rc-gated on zero lost jobs + /raw identity vs the
    # fixed-fleet baseline + per-class shed ordering (chaos plan runs
    # additionally inject a dispatch-path preemption + aborted drain)
    as_ok, as_rec = _smoke_autoscale_clause()
    ok = ok and as_ok
    emit(
        "smoke_autoscale_identity",
        1.0 if as_ok else 0.0,
        " (diurnal replay vs simulated preemptible fleet: zero lost "
        "jobs + raw identity + bulk-sheds-first)",
        1.0 if as_ok else 0.0,
        extra={
            "autoscale": {
                k: v for k, v in as_rec.items() if k != "steps"
            }
        },
    )
    # shard smoke (run above, in its own process): rc-gated on verdict
    # identity (docs/SHARDING.md)
    for line in shard_lines:
        print(line, flush=True)
    ok = ok and shard_ok
    from swarm_tpu.resilience.faults import active_plan

    plan = active_plan()
    emit(
        "smoke_pipeline_ab_fresh_speedup",
        speed,
        "x (pipeline on/off, bundled-corpus smoke)",
        speed,
        extra={
            "pipeline": eng.pipeline,
            "ab": ab,
            "fault_plan": plan.spec if plan is not None else "",
            "degraded_batches": eng.stats.degraded_batches,
            "device_faults": eng.stats.device_faults,
        },
    )
    if plan is not None:
        # chaos smoke contract: the injected faults must actually have
        # fired (a typo'd plan silently testing nothing is a failure)
        fired = sum(c["fired"] for c in plan.snapshot().values())
        log(
            f"chaos smoke: plan {plan.spec!r} fired {fired} fault(s), "
            f"{eng.stats.degraded_batches} degraded batch(es)"
        )
        if not fired:
            log("!!! fault plan armed but nothing fired — smoke FAILED")
            return 1
    else:
        # fault-free run: record the resilience layer's measured no-op
        # cost (the "provably costs nothing on the happy path" gate)
        overhead = _bench_resilience_overhead()
        if overhead is not None:
            emit(
                "resilience_faultfree_overhead_ns",
                overhead["fault_point_ns"],
                "ns/call (unarmed fault_point; transport wrap in extra)",
                1.0,
                extra=overhead,
            )
    if not ok:
        log(
            "!!! pipeline/walk/workflow/shard/dedup/gateway/monitor/"
            "restart verdict mismatch — smoke FAILED"
        )
    return 0 if ok else 1


#: phase order; the LAST phase's LAST metric is the headline line the
#: driver tails — the END-TO-END exact engine rate at 100% parity
#: (BASELINE.md's declared headline), not an auxiliary or device-only
#: line. oracle runs before exact so the speedup ratio main()
#: synthesizes never delays the headline.
PHASES = [
    "service", "service_full", "streaming", "jarm", "device", "sharded",
    "aot", "latency", "monitor", "autoscale", "workflow", "oracle",
    "exact",
]


def main() -> int:
    """Run every phase, each in its OWN subprocess, one at a time.

    This parent never touches JAX, so each phase child can hold the
    chip; per-phase processes give every phase a clean device (no
    executables or buffers left by earlier phases) and share the
    persistent XLA compile cache. ``--phase <name>`` runs one phase
    inline (the child entry point; also handy for debugging)."""
    import subprocess

    if len(sys.argv) >= 3 and sys.argv[1] == "--phase":
        return run_phase(sys.argv[2])
    if "--smoke" in sys.argv[1:]:
        return run_smoke()
    values: dict = {}
    devices: dict = {}
    failed = []
    headline_line = ""
    for phase in PHASES:
        try:
            r = subprocess.run(
                [sys.executable, __file__, "--phase", phase],
                stdout=subprocess.PIPE,
                text=True,
                timeout=3600,
            )
        except subprocess.TimeoutExpired:
            failed.append(phase)
            log(f"!!! phase {phase} timed out; continuing")
            continue
        if r.returncode != 0:
            failed.append(phase)
            log(f"!!! phase {phase} failed (rc {r.returncode})")
            continue
        for line in r.stdout.splitlines():
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            values[rec["metric"]] = rec["value"]
            devices[rec["metric"]] = rec.get("device") or {}
            if rec["metric"] == "cpu_oracle_rows_per_sec":
                # input to the speedup ratio synthesized below — not a
                # standalone headline
                continue
            if rec["metric"] == "exact_fingerprints_per_sec_per_chip":
                # hold the headline back so it is the LAST line emitted
                # (the driver tail-parses stdout)
                headline_line = line
                continue
            print(line, flush=True)
    exact = values.get("exact_fingerprints_per_sec_per_chip")
    oracle = values.get("cpu_oracle_rows_per_sec")
    if exact and oracle:
        # the synthesized line names the device the EXACT child (the
        # numerator) ran on — this parent never touches JAX
        _DEVICE.update(devices.get("exact_fingerprints_per_sec_per_chip", {}))
        speedup = exact / oracle
        emit(
            "device_vs_cpu_oracle_speedup",
            speedup,
            "x (same rows, same corpus, parity-identical results)",
            speedup / BASELINES["device_vs_cpu_oracle_speedup"],
        )
    else:
        # a missing side → no honest ratio; a 0.0x line would read as
        # a measured regression
        log("!!! speedup metric skipped (missing exact or oracle rate)")
    if headline_line:
        print(headline_line, flush=True)
    else:
        log("!!! exact headline missing (phase failed?)")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
