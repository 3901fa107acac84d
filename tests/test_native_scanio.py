"""Native scan I/O engine tests — hermetic, localhost-only.

Covers the four behaviors the worker pipeline depends on: banner grab
on connect, payload probe (HTTP-style request/response), closed-port
detection, and silent-port read timeout; plus bulk DNS against a local
UDP responder.
"""

from __future__ import annotations

import socket
import socketserver
import struct
import threading

import numpy as np
import pytest

from swarm_tpu.native import (
    STATUS_CLOSED,
    STATUS_OPEN,
    dns_resolve,
    tcp_scan,
)
from swarm_tpu.native.scanio import parse_ipv4, format_ipv4


class _TCPServer(socketserver.ThreadingTCPServer):
    # default backlog (5) drops concurrent handshakes under load — the
    # engine sees them as open-but-silent, which is correct behavior
    # for an overloaded peer but not what these tests exercise
    request_queue_size = 256
    allow_reuse_address = True


class _BannerHandler(socketserver.BaseRequestHandler):
    def handle(self):
        self.request.sendall(b"220 test-ftp ready\r\n")


class _EchoHTTPHandler(socketserver.BaseRequestHandler):
    def handle(self):
        data = self.request.recv(4096)
        if data.startswith(b"GET "):
            body = b"<html><title>scanio test</title></html>"
            self.request.sendall(
                b"HTTP/1.1 200 OK\r\nServer: scanio-test\r\n"
                b"Content-Length: %d\r\n\r\n%s" % (len(body), body)
            )


class _SilentHandler(socketserver.BaseRequestHandler):
    def handle(self):
        self.request.recv(1)  # hold the connection open, send nothing


@pytest.fixture(scope="module")
def servers():
    servers = []

    def start(handler):
        srv = _TCPServer(("127.0.0.1", 0), handler)
        srv.daemon_threads = True
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        servers.append(srv)
        return srv.server_address[1]

    ports = {
        "banner": start(_BannerHandler),
        "http": start(_EchoHTTPHandler),
        "silent": start(_SilentHandler),
    }
    yield ports
    for srv in servers:
        srv.shutdown()


def test_banner_http_closed_silent(servers):
    # a closed port: bind+close to find a free one
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    closed_port = probe.getsockname()[1]
    probe.close()

    hosts = ["127.0.0.1"] * 4
    ports = [servers["banner"], servers["http"], closed_port, servers["silent"]]
    payloads = [None, b"GET / HTTP/1.1\r\nHost: localhost\r\n\r\n", None, None]
    res = tcp_scan(
        hosts, ports, payloads,
        connect_timeout_ms=1000, read_timeout_ms=400, banner_cap=512,
    )

    assert res.status[0] == STATUS_OPEN
    assert res.banner(0) == b"220 test-ftp ready\r\n"
    assert res.status[1] == STATUS_OPEN
    assert b"scanio test" in res.banner(1)
    assert res.banner(1).startswith(b"HTTP/1.1 200 OK")
    assert res.status[2] == STATUS_CLOSED
    assert res.status[3] == STATUS_OPEN  # connected; read timed out
    assert res.banner_len[3] == 0
    assert res.rtt_us[0] >= 0 and res.rtt_us[2] == -1


def test_tcp_scan_many_concurrent(servers):
    n = 200
    res = tcp_scan(
        ["127.0.0.1"] * n,
        [servers["banner"]] * n,
        max_concurrency=64,
        connect_timeout_ms=2000,
        read_timeout_ms=1000,
        banner_cap=64,
    )
    assert int(res.open_mask.sum()) == n
    assert all(res.banner(i) == b"220 test-ftp ready\r\n" for i in range(n))


def test_banner_cap_truncates(servers):
    res = tcp_scan(
        ["127.0.0.1"], [servers["banner"]], banner_cap=8,
        read_timeout_ms=500,
    )
    assert res.status[0] == STATUS_OPEN
    assert res.banner(0) == b"220 test"


# ---------------------------------------------------------------------------


class _DNSHandler(socketserver.BaseRequestHandler):
    """Minimal DNS responder: answers A 192.0.2.7 for names containing
    'good', NXDOMAIN otherwise."""

    def handle(self):
        data, sock = self.request
        if len(data) < 12:
            return
        qname = []
        off = 12
        while off < len(data) and data[off] != 0:
            lab = data[off]
            qname.append(data[off + 1 : off + 1 + lab])
            off += lab + 1
        name = b".".join(qname)
        question = data[12 : off + 5]
        if b"good" in name:
            header = data[:2] + b"\x81\x80\x00\x01\x00\x01\x00\x00\x00\x00"
            answer = (
                b"\xc0\x0c\x00\x01\x00\x01\x00\x00\x00\x3c\x00\x04"
                + socket.inet_aton("192.0.2.7")
            )
            sock.sendto(header + question + answer, self.client_address)
        else:
            header = data[:2] + b"\x81\x83\x00\x01\x00\x00\x00\x00\x00\x00"
            sock.sendto(header + question, self.client_address)


@pytest.fixture(scope="module")
def dns_server():
    srv = socketserver.ThreadingUDPServer(("127.0.0.1", 0), _DNSHandler)
    srv.daemon_threads = True
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield srv.server_address[1]
    srv.shutdown()


def test_dns_resolve(dns_server):
    names = ["good.example.com", "bad.example.com", "also-good.example.org"]
    res = dns_resolve(
        names, ["127.0.0.1"], resolver_port=dns_server,
        timeout_ms=1500, retries=1,
    )
    assert res.status[0] == STATUS_OPEN
    assert res.addresses(0) == ["192.0.2.7"]
    assert res.status[1] == STATUS_CLOSED
    assert res.naddrs[1] == 0
    assert res.status[2] == STATUS_OPEN


def test_dns_resolve_bulk(dns_server):
    names = [f"good-{i}.example.com" for i in range(300)]
    res = dns_resolve(
        names, ["127.0.0.1"], resolver_port=dns_server,
        timeout_ms=2000, retries=2,
    )
    assert int(res.resolved_mask.sum()) == 300


def test_ip_roundtrip():
    arr = parse_ipv4(["10.1.2.3", "192.168.0.1"])
    assert format_ipv4(arr) == ["10.1.2.3", "192.168.0.1"]
    assert arr.dtype == np.uint32
    # network byte order: first octet in the low byte on little-endian
    assert struct.pack("=I", int(arr[0]))[0] == 10


@pytest.mark.parametrize("loader", ["scanio", "crex"])
def test_failed_native_build_raises(monkeypatch, loader):
    """A build from the committed sources that fails raises: a library
    some other build left on disk must never load in its place."""
    import subprocess

    from swarm_tpu.native import crex as ncrex
    from swarm_tpu.native import scanio

    monkeypatch.setattr(
        scanio.subprocess, "run",
        lambda cmd, **kw: subprocess.CompletedProcess(cmd, 2, "", "boom"),
    )
    if loader == "scanio":
        monkeypatch.setattr(scanio, "_lib", None)
        ensure = scanio.ensure_lib
    else:
        monkeypatch.setattr(ncrex, "_lib", None)
        monkeypatch.setattr(ncrex, "_lib_failed", False)
        monkeypatch.setattr(ncrex, "_DIR_OVERRIDDEN", False)
        ensure = ncrex.ensure_crex
    monkeypatch.setattr(scanio, "_DIR_OVERRIDDEN", False)
    with pytest.raises(RuntimeError, match="native build failed"):
        ensure()
