"""nmap-service-probes parsing + TPU-prefiltered service classification.

Covers the reference's nmap -sV capability (SURVEY.md §2.2): probes-DB
parsing (payload escapes, match directives, version templates), probe
selection per port, and banner → service/product/version classification
with the device match engine as prefilter.
"""

from __future__ import annotations

import re
import socketserver
import threading

import pytest

from swarm_tpu.fingerprints.nmap_probes import (
    load_probes,
    parse_port_spec,
    parse_probes,
    substitute_version,
    unescape_payload,
)
from swarm_tpu.fingerprints.model import Response
from swarm_tpu.ops.service import ServiceClassifier

MINI_DB = """
Probe TCP NULL q||
totalwaitms 5000
rarity 1
ports 1-65535
match ssh m|^SSH-([\\d.]+)-OpenSSH[_-]([^\\s\\r\\n]+)| p/OpenSSH/ v/$2/ i/protocol $1/ cpe:/a:openbsd:openssh:$2/
softmatch ssh m|^SSH-[\\d.]+-|
match ftp m|^220[ -].*\\(vsFTPd ([^)]+)\\)| p/vsftpd/ v/$1/

Probe TCP GetRequest q|GET / HTTP/1.0\\r\\n\\r\\n|
rarity 1
ports 80,8000-8100
fallback NULL
match http m|^HTTP/1\\.[01] \\d\\d\\d.*Server: nginx/([^\\s\\r\\n]*)|s p/nginx/ v/$1/
softmatch http m|^HTTP/1\\.[01] \\d\\d\\d|s
"""


def test_parse_probes_structure():
    probes, skipped = parse_probes(MINI_DB)
    assert skipped == 0
    assert [p.name for p in probes] == ["NULL", "GetRequest"]
    null, get = probes
    assert null.payload == b""
    assert null.totalwaitms == 5000
    assert get.payload == b"GET / HTTP/1.0\r\n\r\n"
    assert get.fallback == ["NULL"]
    assert get.covers_port(8080) and get.covers_port(80)
    assert not get.covers_port(443)
    assert len(null.matches) == 3
    ssh = null.matches[0]
    assert ssh.service == "ssh" and ssh.product == "OpenSSH"
    assert ssh.version == "$2" and ssh.info == "protocol $1"
    assert ssh.cpe == ["a:openbsd:openssh:$2"]
    assert null.matches[1].soft


def test_match_regex_is_compiled_once():
    """The loader's check compiles each directive's regex once; the
    classifier reuses that pattern (re's own cache holds 512 patterns,
    a DB at nmap's scale ~12k, so a second compile is paid again)."""
    probes, _ = parse_probes(MINI_DB)
    matches = [m for p in probes for m in p.matches]
    first = [m.compile() for m in matches]
    assert all(first)
    re.purge()
    clf = ServiceClassifier(probes=probes)
    assert all(a is b for a, b in zip(clf._compiled, first, strict=True))
    assert all(m.compile() is c for m, c in zip(matches, first))


def test_unescape_payload():
    assert unescape_payload(r"a\r\n\0\x41\\b") == b"a\r\n\x00A\\b"


def test_parse_port_spec():
    assert parse_port_spec("80,443,8000-8002") == [(80, 80), (443, 443), (8000, 8002)]


def test_substitute_version():
    import re

    mo = re.search(rb"v(\d+)\.(\d+)", b"v8.9")
    assert substitute_version("$1.$2p1", mo) == "8.9p1"
    assert substitute_version("fixed", mo) == "fixed"
    assert substitute_version(None, mo) is None
    assert substitute_version("$1 and $5", mo) == "8 and"


def test_version_info_unknown_fields_stay_aligned():
    # d/…/ (devicetype) values must not be scanned for field keys —
    # 'h' inside 'switch' is not a hostname field
    probes, _ = parse_probes(
        "Probe TCP NULL q||\n"
        'match http m|^HTTP| p/Cisco IOS http config/ d/switch/ o/IOS/\n'
    )
    m = probes[0].matches[0]
    assert m.product == "Cisco IOS http config"
    assert m.ostype == "IOS"
    assert m.hostname is None


def test_substitute_version_helpers():
    import re

    mo = re.search(rb"(v[\x01\x02\d.]+)_(\w+)", b"\x00v1\x01.2_beta\x00")
    assert substitute_version("$P(1)", mo) == "v1.2"
    assert substitute_version('$SUBST(2,"e","E")', mo) == "bEta"
    mo2 = re.search(rb"x(..)", b"x\x01\x02")
    assert substitute_version('$I(1,">")', mo2) == str(0x0102)
    assert substitute_version('$I(1,"<")', mo2) == str(0x0201)


def test_classify_probe_match_ordering():
    # the sent probe's own matches are tried before fallback (NULL)
    # matches even though NULL appears first in the DB
    db = (
        "Probe TCP NULL q||\n"
        "ports 1-65535\n"
        "match generic m|^BANNER| p/generic-from-null/\n"
        "Probe TCP Poke q|hi|\n"
        "ports 9000\n"
        "fallback NULL\n"
        "match specific m|^BANNER-X| p/specific-from-poke/\n"
    )
    clf = ServiceClassifier(probes=parse_probes(db)[0])
    rows = [Response(host="a", port=9000, banner=b"BANNER-X here")]
    info = clf.classify(rows, sent_probes=["Poke"])[0]
    assert info.service == "specific" and info.product == "specific-from-poke"


def test_classify_softmatch_restricts_service():
    # once a softmatch names a service, hard matches for other services
    # cannot win (nmap -sV softmatch semantics)
    db = (
        "Probe TCP NULL q||\n"
        "ports 1-65535\n"
        "softmatch ftp m|^220[ -]|\n"
        "match smtp m|^220[ -].*mail| p/maild/\n"
        "match ftp m|^220[ -].*FTP| p/ftpd/\n"
    )
    clf = ServiceClassifier(probes=parse_probes(db)[0])
    got = clf.classify([Response(host="a", port=21, banner=b"220 mail FTP ready")])
    # softmatch ftp fires first; the smtp hard match is skipped; ftp wins
    assert got[0].service == "ftp" and got[0].product == "ftpd"


def test_bundled_db_loads():
    probes, skipped = load_probes()
    names = [p.name for p in probes]
    assert "NULL" in names and "GetRequest" in names
    assert skipped == 0, f"{skipped} bundled matches failed to compile"
    total = sum(len(p.matches) for p in probes)
    assert total >= 30


@pytest.fixture(scope="module")
def classifier():
    return ServiceClassifier(probes=parse_probes(MINI_DB)[0])


def test_classify_hard_match_with_version(classifier):
    rows = [
        Response(host="a", port=22, banner=b"SSH-2.0-OpenSSH_8.9p1 Ubuntu-3ubuntu0.1\r\n"),
        Response(host="b", port=21, banner=b"220 (vsFTPd 3.0.5)\r\n"),
        Response(host="c", port=2222, banner=b"SSH-2.0-CustomSSHd_1.0\r\n"),
        Response(host="d", port=9999, banner=b"hello whoever you are"),
        Response(host="e", port=23, banner=b"", alive=False),
    ]
    infos = classifier.classify(rows, sent_probes=["NULL"] * 5)
    assert infos[0].service == "ssh"
    assert infos[0].product == "OpenSSH" and infos[0].version == "8.9p1"
    assert infos[0].info == "protocol 2.0"
    assert infos[0].cpe == ["a:openbsd:openssh:8.9p1"]
    assert infos[1].service == "ftp" and infos[1].version == "3.0.5"
    # only the softmatch fires for an unknown SSH implementation
    assert infos[2].service == "ssh" and infos[2].soft
    assert infos[3].service is None and infos[3].open
    assert not infos[4].open and infos[4].service is None


def test_classify_probe_scoping(classifier):
    # an HTTP banner elicited by the NULL probe must NOT match GetRequest
    # matches (nmap scopes match directives to their probe + fallbacks)
    http_banner = b"HTTP/1.1 200 OK\r\nServer: nginx/1.25.3\r\n\r\nhi"
    rows = [Response(host="a", port=8080, banner=http_banner)]
    got_null = classifier.classify(rows, sent_probes=["NULL"])[0]
    assert got_null.service is None
    got_get = classifier.classify(rows, sent_probes=["GetRequest"])[0]
    assert got_get.service == "http"
    assert got_get.product == "nginx" and got_get.version == "1.25.3"


def test_classify_without_probe_bookkeeping(classifier):
    rows = [Response(host="a", port=80, banner=b"HTTP/1.0 404 Not Found\r\n\r\n")]
    info = classifier.classify(rows)[0]  # no sent_probes: everything applies
    assert info.service == "http" and info.soft


def test_probe_for_port(classifier):
    assert classifier.probe_for_port(8080).name == "GetRequest"
    assert classifier.probe_for_port(22).name == "NULL"


def test_service_info_line():
    from swarm_tpu.ops.service import ServiceInfo

    info = ServiceInfo(
        host="10.0.0.1", port=22, open=True,
        service="ssh", product="OpenSSH", version="8.9p1", info="protocol 2.0",
    )
    assert info.line() == "10.0.0.1:22\topen\tssh\tOpenSSH 8.9p1\t(protocol 2.0)"


# ---------------------------------------------------------------------------
# End to end over a live socket: probe payload selection + classify
# ---------------------------------------------------------------------------


class _SSHServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True


class _SSHHandler(socketserver.BaseRequestHandler):
    def handle(self):
        self.request.sendall(b"SSH-2.0-OpenSSH_9.6\r\n")


def test_service_scan_end_to_end():
    srv = _SSHServer(("127.0.0.1", 0), _SSHHandler)
    srv.daemon_threads = True
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    port = srv.server_address[1]
    try:
        from swarm_tpu.worker.executor import ProbeExecutor

        classifier = ServiceClassifier(probes=parse_probes(MINI_DB)[0])
        rows, sent = ProbeExecutor({"read_timeout_ms": 1500}).run_service(
            [f"127.0.0.1:{port}"], classifier
        )
        assert len(rows) == 1 and rows[0].alive
        assert sent == ["NULL"]
        info = classifier.classify(rows, sent)[0]
        assert info.service == "ssh"
        assert info.product == "OpenSSH" and info.version == "9.6"
    finally:
        srv.shutdown()


def test_top_ports_default():
    from swarm_tpu.worker.executor import top_ports

    ports = top_ports()
    assert 22 in ports and 443 in ports and len(ports) >= 80
    assert top_ports(5) == ports[:5]


# ---------------------------------------------------------------------------
# Production-scale DB: the reference's nmap module ran -sV over the real
# nmap-service-probes (thousands of match directives). The bundled DB is
# hundreds of directives; this generated DB proves the pipeline at full
# scale — parse -> classifier compile (documented time) -> device
# prefilter -> exact first-match-wins classification.
# ---------------------------------------------------------------------------

N_SCALE_PROBES = 520


def _scale_db(n: int = N_SCALE_PROBES) -> str:
    lines = []
    for i in range(n):
        lines += [
            "##############################NEXT PROBE#####################",
            f"Probe TCP P{i} q|Q{i}\\r\\n|",
            "totalwaitms 4000",
            f"rarity {1 + i % 9}",
            f"ports {1000 + i}",
            f"match svc{i}a m|^BANNER-{i}-ALPHA ([\\d.]+)| p/Prod{i}A/ v/$1/",
            f"match svc{i}b m|^BANNER-{i}-BETA/([\\w.]+)| p/Prod{i}B/ v/$1/",
            f"match svc{i}c m|SIG-{i}-GAMMA| p/Prod{i}C/",
            f"match svc{i}d m|^DELTA-{i}:(\\d+)$| p/Prod{i}D/ v/$1/",
            f"softmatch svc{i} m|^BANNER-{i}-|",
        ]
    return "\n".join(lines) + "\n"


def test_scale_db_parse_and_classify():
    import time

    probes, skipped = parse_probes(_scale_db())
    assert len(probes) == N_SCALE_PROBES
    n_matches = sum(len(p.matches) for p in probes)
    assert n_matches == N_SCALE_PROBES * 5 and skipped == 0

    t0 = time.monotonic()
    clf = ServiceClassifier(probes=probes)
    compile_s = time.monotonic() - t0
    # the device prefilter must carry the DB: every directive above has
    # a required literal, so none may fall into the host-always tail
    db = clf.engine.db
    assert db.num_templates == n_matches
    assert len(db.host_always) == 0, [t.id for t in db.host_always[:5]]
    print(
        f"\nscale DB: {len(probes)} probes / {n_matches} directives, "
        f"classifier compile {compile_s:.1f}s"
    )

    from swarm_tpu.fingerprints.model import Response

    rows, expected = [], []
    for k in (0, 7, 123, 400, N_SCALE_PROBES - 1):
        rows.append(Response(host="h", port=1000 + k,
                             banner=f"BANNER-{k}-ALPHA 2.{k}.1\r\n".encode()))
        expected.append((f"svc{k}a", f"Prod{k}A", f"2.{k}.1"))
        rows.append(Response(host="h", port=1000 + k,
                             banner=f"prefix SIG-{k}-GAMMA suffix".encode()))
        expected.append((f"svc{k}c", f"Prod{k}C", None))
        rows.append(Response(host="h", port=1000 + k,
                             banner=f"BANNER-{k}-UNKNOWNTAIL".encode()))
        expected.append((f"svc{k}", None, None))  # softmatch only
    rows.append(Response(host="h", port=9, banner=b"no service here at all"))
    expected.append((None, None, None))

    t0 = time.monotonic()
    infos = clf.classify(rows)
    first_s = time.monotonic() - t0
    t0 = time.monotonic()
    infos = clf.classify(rows)
    steady_s = time.monotonic() - t0
    print(f"scale classify: first {first_s:.1f}s, steady {steady_s*1e3:.0f}ms")
    for info, (svc, prod, ver) in zip(infos, expected):
        assert info.service == svc, (info, svc)
        assert info.product == prod, (info, prod)
        assert info.version == ver, (info, ver)
        if svc and not prod:
            assert info.soft  # softmatch-only rows are marked soft


def test_bundled_db_scale_and_split():
    """The shipped DB meets the production contract: hundreds of match
    directives, nothing skipped, and the device prefilter carries all
    but a bounded tail."""
    from swarm_tpu.fingerprints.nmap_probes import BUNDLED_DB

    probes, skipped = load_probes(BUNDLED_DB)
    n_matches = sum(len(p.matches) for p in probes)
    assert skipped == 0
    assert len(probes) >= 20
    assert n_matches >= 290
    clf = ServiceClassifier(probes=probes)
    db = clf.engine.db
    # regression fence for the device/host split: binary-payload regexes
    # without extractable literals may host-confirm, but the bulk must
    # stay device-resident
    assert len(db.host_always) <= n_matches * 0.05, (
        len(db.host_always), n_matches)


def test_top_ports_full_contract():
    """The reference contract is --top-ports 1000 (worker/modules/
    nmap.json); the shipped list must carry exactly 1000 unique ports
    with the high-value head first."""
    from swarm_tpu.worker.executor import top_ports

    ports = top_ports()
    assert len(ports) == 1000
    assert len(set(ports)) == 1000
    assert set(ports[:10]) >= {80, 443, 22, 21}
    assert all(0 < p < 65536 for p in ports)


def test_nmap_report_format():
    from swarm_tpu.ops.service import ServiceInfo
    from swarm_tpu.worker.formats import format_nmap_report

    infos = [
        ServiceInfo(host="10.0.0.5", port=22, open=True, service="ssh",
                    product="OpenSSH", version="9.6p1", info="protocol 2.0"),
        ServiceInfo(host="10.0.0.5", port=80, open=True, service="http",
                    product="nginx", version="1.18.0"),
        ServiceInfo(host="10.0.0.5", port=25, open=True, service="smtp",
                    soft=True),
        ServiceInfo(host="10.0.0.9", port=443, open=False),  # closed: omitted
    ]
    out = format_nmap_report(infos)
    assert "Nmap scan report for 10.0.0.5" in out
    assert "22/tcp    open  ssh            OpenSSH 9.6p1 (protocol 2.0)" in out
    assert "80/tcp    open  http           nginx 1.18.0" in out
    assert "25/tcp    open  smtp?" in out  # softmatch marked tentative
    assert "10.0.0.9" not in out


# --- production-scale DB (round 3) -----------------------------------------

LARGE_DB = "swarm_tpu/data/service-probes-large.txt"
RECALL = "swarm_tpu/data/service-probes-large.recall.json"


def _repo(p):
    from pathlib import Path

    return Path(__file__).resolve().parent.parent / p


def test_large_db_parses_at_nmap_scale():
    """The production DB must be at real nmap-service-probes scale
    (reference: nmap -sV's ~12k signatures — worker/Dockerfile:13) and
    parse in bounded time with zero skipped directives."""
    import time

    t0 = time.time()
    probes, skipped = load_probes(_repo(LARGE_DB))
    dt = time.time() - t0
    n_matches = sum(len(p.matches) for p in probes)
    assert skipped == 0
    assert len(probes) >= 400
    assert n_matches >= 10_000
    assert dt < 30, f"parse took {dt:.1f}s"
    # version-capture coverage: the point of -sV is versions
    with_version = sum(
        1 for p in probes for m in p.matches if m.version
    )
    assert with_version > n_matches * 0.5


@pytest.fixture(scope="module")
def large_classifier():
    return ServiceClassifier(db_path=str(_repo(LARGE_DB)))


def test_large_db_recall_end_to_end(large_classifier):
    """A spread sample of the generated recall corpus classifies to the
    exact product+version through the REAL batched classify path
    (device prefilter -> host verify -> version substitution)."""
    import base64
    import json

    recall = json.loads(_repo(RECALL).read_text())
    sample = recall[:: max(1, len(recall) // 48)][:48]
    rows = [
        Response(host=f"198.51.100.{i}", port=2121,
                 banner=base64.b64decode(r["banner"]))
        for i, r in enumerate(sample)
    ]
    out = large_classifier.classify(
        rows, sent_probes=[r["probe"] for r in sample]
    )
    for r, info in zip(sample, out):
        assert info.service == r["service"], (r["product"], info.line())
        assert info.product == r["product"], info.line()
        assert info.version == r["version"], info.line()


def test_large_db_head_still_wins(large_classifier):
    """The hand-written head (real-world products) must keep firing
    with the generated tail loaded — DB order preserved."""
    rows = [
        Response(host="a", port=22,
                 banner=b"SSH-2.0-OpenSSH_8.9p1 Ubuntu-3ubuntu0.1\r\n"),
        Response(host="b", port=21, banner=b"220 (vsFTPd 3.0.3)\r\n"),
    ]
    out = large_classifier.classify(rows, sent_probes=["NULL", "NULL"])
    assert out[0].service == "ssh" and out[0].product == "OpenSSH"
    assert out[0].version == "8.9p1"
    assert out[1].service == "ftp" and out[1].product == "vsftpd"
    assert out[1].version == "3.0.3"


def test_large_db_compile_is_cached(tmp_path, monkeypatch):
    """Second construction must come from the keyed disk cache — the
    18s cold lowering is paid once per DB+compiler version."""
    import time

    monkeypatch.setenv("SWARM_DB_CACHE_DIR", str(tmp_path))
    t0 = time.time()
    ServiceClassifier(db_path=str(_repo(LARGE_DB)))
    cold = time.time() - t0
    t0 = time.time()
    ServiceClassifier(db_path=str(_repo(LARGE_DB)))
    warm = time.time() - t0
    assert warm < cold / 2, (cold, warm)
    assert list(tmp_path.glob("svcdb-*.pkl"))
