"""Fleet orchestration tests (server/fleet.py).

The reference's elastic scaling (`server/server.py:47-162,517-546`) is
a DO droplet fleet with a 250/min rate limiter and idle teardown. These
tests pin: name generation, the token-bucket limiter, the provider
factory, and the DigitalOcean provider's wire shape (create payload,
cloud-init user_data, prefix-scoped deletion) against a fake requests
layer — no egress.
"""

import threading
import time

import pytest

from swarm_tpu.config import Config
from swarm_tpu.server.fleet import (
    AutoscaleAdvisor,
    DigitalOceanProvider,
    InflowForecaster,
    NullProvider,
    ProcessProvider,
    RateLimiter,
    SimulatedProvider,
    build_provider,
    generate_node_names,
)


def test_node_names_reference_format():
    assert generate_node_names("sw", 3) == ["sw1", "sw2", "sw3"]
    assert generate_node_names("x", 0) == []


def test_rate_limiter_caps_burst():
    rl = RateLimiter(per_minute=5)
    t0 = time.monotonic()
    for _ in range(5):
        rl.acquire()
    assert time.monotonic() - t0 < 0.5  # first 5 are immediate
    # the 6th would block ~60s; assert it does NOT return immediately
    done = threading.Event()

    def sixth():
        rl.acquire()
        done.set()

    t = threading.Thread(target=sixth, daemon=True)
    t.start()
    assert not done.wait(0.3)


def test_build_provider_dispatch():
    assert isinstance(build_provider(Config()), NullProvider)
    assert isinstance(
        build_provider(Config(fleet_provider="process")), ProcessProvider
    )
    assert isinstance(
        build_provider(Config(fleet_provider="digitalocean")),
        DigitalOceanProvider,
    )
    assert isinstance(
        build_provider(Config(fleet_provider="sim")), SimulatedProvider
    )


class _FakeResponse:
    def __init__(self, payload):
        self.status_code = 200
        self._payload = payload

    def json(self):
        return self._payload


class _FakeRequests:
    """Records calls; serves a fixed droplet inventory."""

    def __init__(self, droplets=()):
        self.droplets = list(droplets)
        self.posts: list[tuple[str, dict]] = []
        self.deletes: list[str] = []
        self._lock = threading.Lock()

    def post(self, url, headers=None, json=None, timeout=None):
        with self._lock:
            self.posts.append((url, json))
        return _FakeResponse({})

    def delete(self, url, headers=None, timeout=None):
        with self._lock:
            self.deletes.append(url)
        return _FakeResponse({})

    def get(self, url, headers=None, timeout=None):
        return _FakeResponse({"droplets": self.droplets})


def _do_provider(fake, **cfg_kw):
    cfg = Config(
        fleet_provider="digitalocean",
        fleet_api_token="tok",
        server_url="http://c2.example:5001",
        api_key="fleetkey",
        fleet_image="snapshot-123",
        **cfg_kw,
    )
    p = DigitalOceanProvider(cfg)
    p._requests = fake
    return p


def test_do_spin_up_wire_shape():
    fake = _FakeRequests()
    p = _do_provider(fake)
    p.spin_up("sw", 3)
    assert len(fake.posts) == 3
    urls = {u for u, _ in fake.posts}
    assert urls == {"https://api.digitalocean.com/v2/droplets"}
    names = sorted(body["name"] for _, body in fake.posts)
    assert names == ["sw1", "sw2", "sw3"]
    _, body = fake.posts[0]
    assert body["image"] == "snapshot-123"
    # cloud-init user_data boots the worker image with the C2 wiring
    # (reference server.py:79-102)
    ud = body["user_data"]
    assert "#cloud-config" in ud
    assert "SERVER_URL=http://c2.example:5001" in ud
    assert "API_KEY=fleetkey" in ud
    assert f"WORKER_ID={body['name']}" in ud


def test_do_spin_down_prefix_scoped():
    fake = _FakeRequests(
        droplets=[
            {"id": 11, "name": "sw1"},
            {"id": 12, "name": "sw2"},
            {"id": 99, "name": "other1"},
        ]
    )
    p = _do_provider(fake)
    assert p.list_nodes("sw") == ["sw1", "sw2"]
    p.spin_down("sw")
    assert sorted(fake.deletes) == [
        "https://api.digitalocean.com/v2/droplets/11",
        "https://api.digitalocean.com/v2/droplets/12",
    ]


def test_process_provider_lifecycle(tmp_path):
    """ProcessProvider spawns real worker processes and kills them —
    the single-host analog of a droplet fleet."""
    cfg = Config(
        fleet_provider="process",
        server_url="http://127.0.0.1:1",  # nothing listening: they just poll
        api_key="k",
    )
    p = ProcessProvider(cfg)
    try:
        p.spin_up("pw", 2)
        assert sorted(p.list_nodes("pw")) == ["pw1", "pw2"]
        p.spin_down("pw")
        deadline = time.monotonic() + 10
        while p.list_nodes("pw") and time.monotonic() < deadline:
            time.sleep(0.1)
        assert p.list_nodes("pw") == []
    finally:
        p.shutdown()


def test_process_provider_one_worker_per_chip(monkeypatch):
    """A chip belongs to one process: on a one-chip host a second local
    worker is refused with a clear error before anything spawns, and
    the CPU (selected explicitly) stays unbounded."""
    from swarm_tpu.server import fleet as fleet_mod

    cfg = Config(fleet_provider="process", server_url="http://127.0.0.1:1",
                 api_key="k")
    monkeypatch.setattr(fleet_mod, "local_chip_count", lambda: 1)
    monkeypatch.setenv("JAX_PLATFORMS", "")
    p = ProcessProvider(cfg)
    assert p.capacity() == 1
    with pytest.raises(RuntimeError, match="one worker per chip"):
        p.spin_up("pw", 2)
    assert p.list_nodes("pw") == []
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert ProcessProvider(cfg).capacity() is None


def test_idle_teardown_via_queue():
    """Reference behavior: >N empty polls flips the worker inactive and
    tears its node down (server.py:499-512) — wired through the queue
    service's fleet hook here."""
    from swarm_tpu.server.queue import JobQueueService
    from swarm_tpu.stores import (
        MemoryBlobStore,
        MemoryDocStore,
        MemoryStateStore,
    )

    class RecordingProvider(NullProvider):
        def __init__(self):
            self.torn_down = []

        def teardown_async(self, prefix):
            self.torn_down.append(prefix)

    fleet = RecordingProvider()
    cfg = Config(api_key="k", idle_polls_before_teardown=3)
    q = JobQueueService(
        cfg, MemoryStateStore(), MemoryBlobStore(), MemoryDocStore(),
        fleet=fleet,
    )
    for i in range(4):
        assert q.next_job("idle-w") is None
    st = q.statuses()["workers"]["idle-w"]
    assert st["status"] == "pending"
    assert fleet.torn_down == []
    q.next_job("idle-w")  # crosses the idle threshold
    st = q.statuses()["workers"]["idle-w"]
    assert st["status"] == "inactive"
    assert fleet.torn_down == ["idle-w"]
    # a job arriving revives the worker on its next successful poll
    q.queue_scan({"module": "echo", "file_content": ["x\n"],
                  "batch_size": 1, "scan_id": "echo_42"})
    assert q.next_job("idle-w") is not None
    assert q.statuses()["workers"]["idle-w"]["status"] == "active"


# ---------------------------------------------------------------------------
# Inflow forecaster (docs/GATEWAY.md: the advisor's look-ahead signal)
# ---------------------------------------------------------------------------


def test_forecaster_ewma_rise_and_idle_decay_deterministic():
    f = InflowForecaster(alpha=0.3, window_s=1.0)
    f.record(10, now=0.0)
    # the open window hasn't closed: nothing folded yet
    assert f.rate(now=0.5) == 0.0
    r1 = f.rate(now=1.0)  # window closes: 0 + 0.3 * (10/s - 0)
    assert abs(r1 - 3.0) < 1e-9
    # one empty window blends toward zero
    r2 = f.rate(now=2.0)
    assert abs(r2 - 2.1) < 1e-9
    # a long quiet gap decays all the way to zero (bounded fold cost),
    # which is exactly what lets scale-to-zero park the fleet
    assert f.rate(now=500.0) == 0.0


def test_forecaster_per_tenant_rates_and_sum():
    f = InflowForecaster(alpha=1.0, window_s=1.0)
    f.record(4, tenant="a", now=0.0)
    f.record(2, tenant="b", now=0.0)
    assert abs(f.rate("a", now=1.0) - 4.0) < 1e-9
    assert abs(f.rate(now=1.0) - 6.0) < 1e-9  # summed across tenants
    rates = f.tenant_rates(now=1.0)
    assert set(rates) == {"a", "b"}
    assert abs(rates["a"] - 4.0) < 1e-9 and abs(rates["b"] - 2.0) < 1e-9


# ---------------------------------------------------------------------------
# Simulated preemptible provider (docs/RESILIENCE.md §Preemption)
# ---------------------------------------------------------------------------


def test_sim_provider_coldstart_preempt_grace_kill_cycle():
    t = [0.0]
    notices, killed = [], []
    p = SimulatedProvider(
        preempt_grace_s=5.0, coldstart_warm_s=0.25, aot_warm=True,
        clock=lambda: t[0],
        on_preempt_notice=notices.append, on_kill=killed.append,
    )
    p.spin_up("n", 2)
    assert sorted(p.list_nodes("n")) == ["n1", "n2"]
    assert p.ready_nodes("n") == []  # still paying the cold-start
    t[0] = 0.3
    assert sorted(p.ready_nodes("n")) == ["n1", "n2"]
    assert p.preempt("n1") is True
    assert notices == ["n1"]
    assert p.preempt("n1") is False  # already draining
    t[0] = 3.0
    p.poll()
    # inside the grace window the node is still up, finishing its lease
    assert "n1" in p.list_nodes("n")
    assert killed == []
    t[0] = 5.4  # past notice + grace
    p.poll()
    assert p.list_nodes("n") == ["n2"]
    # the kill is the authoritative deregister hook (app.py wires it to
    # queue.deregister_worker so leases hand back NOW)
    assert killed == ["n1"]
    evs = [(e, n) for _ts, e, n in p.events]
    assert ("preempt_notice", "n1") in evs and ("killed", "n1") in evs


def test_sim_spin_up_never_reprovisions_a_draining_name():
    """Re-using a preemption-doomed name early would cancel the pending
    kill while the old (possibly wedged) worker still owns the name's
    drain state — ensure-up must skip draining names outright."""
    t = [0.0]
    p = SimulatedProvider(
        preempt_grace_s=2.0, coldstart_warm_s=0.0, clock=lambda: t[0]
    )
    p.spin_up("n", 1)
    assert p.ready_nodes("n") == ["n1"]
    p.preempt("n1")  # kill_at = 2.0
    p.spin_up("n", 1)  # the advisor re-asks for 1 node mid-grace
    spin_ups = [n for _ts, e, n in p.events if e == "spin_up"]
    assert spin_ups == ["n1"]  # not re-provisioned
    t[0] = 2.5
    p.poll()
    assert p.list_nodes("n") == []  # the pending kill still landed


def test_sim_coldstart_cold_vs_aot_warm():
    t = [0.0]
    cold = SimulatedProvider(
        aot_warm=False, coldstart_cold_s=4.2, coldstart_warm_s=0.23,
        clock=lambda: t[0],
    )
    warm = SimulatedProvider(
        aot_warm=True, coldstart_cold_s=4.2, coldstart_warm_s=0.23,
        clock=lambda: t[0],
    )
    assert cold.coldstart_s == 4.2 and warm.coldstart_s == 0.23
    cold.spin_up("c", 1)
    warm.spin_up("w", 1)
    t[0] = 1.0
    assert cold.ready_nodes("c") == []  # full compile still running
    assert warm.ready_nodes("w") == ["w1"]  # AOT fetch already served
    t[0] = 4.3
    assert cold.ready_nodes("c") == ["c1"]


def test_sim_node_factory_attaches_and_kill_reaches_handle():
    t = [0.0]

    class _Handle:
        def __init__(self, name):
            self.name = name
            self.stopped = self.killed = False

        def stop(self):
            self.stopped = True

        def kill(self):
            self.killed = True

    handles = {}

    def factory(name):
        handles[name] = _Handle(name)
        return handles[name]

    p = SimulatedProvider(
        preempt_grace_s=1.0, coldstart_warm_s=0.0,
        clock=lambda: t[0], node_factory=factory,
    )
    p.spin_up("n", 2)
    assert set(handles) == {"n1", "n2"}  # attached when ready
    p.preempt("n1")
    t[0] = 1.5
    p.poll()
    assert handles["n1"].killed  # post-grace kill, not graceful stop
    p.spin_down("n")
    assert handles["n2"].stopped and not handles["n2"].killed


# ---------------------------------------------------------------------------
# Forecast-ahead autoscale advisor (docs/GATEWAY.md)
# ---------------------------------------------------------------------------


class _FakeQueue:
    def __init__(self):
        self.depth = 0

    def queue_depth(self):
        return self.depth


class _NodesProvider(NullProvider):
    def __init__(self):
        self.nodes: list[str] = []

    def spin_up(self, prefix, nodes):
        for name in generate_node_names(prefix, nodes):
            if name not in self.nodes:
                self.nodes.append(name)

    def list_nodes(self, prefix):
        return [n for n in self.nodes if n.startswith(prefix)]

    def teardown_async(self, name):
        if name in self.nodes:
            self.nodes.remove(name)


def test_advisor_scales_ahead_of_the_spike():
    """The forecast term grows the fleet while queue depth is still
    zero — the spike's shoulder, not its peak."""
    t = [0.0]
    fq, prov = _FakeQueue(), _NodesProvider()
    fc = InflowForecaster(alpha=0.5, window_s=1.0, clock=lambda: t[0])
    adv = AutoscaleAdvisor(
        fq, prov, jobs_per_node=4, min_nodes=0, max_nodes=8,
        apply_enabled=True, forecaster=fc, forecast_horizon_s=8.0,
        clock=lambda: t[0],
    )
    assert adv.recommend("node")["target_nodes"] == 0
    fc.record(10, now=0.0)  # admission burst lands
    t[0] = 1.0
    rec = adv.apply("node")
    # rate 5 jobs/s x 8 s horizon = 40 forecast jobs -> ceil(40/4)=10,
    # clamped to max_nodes
    assert rec["action"] == "spin-up" and rec["applied"]
    assert rec["target_nodes"] == 8
    assert rec["queue_depth"] == 0  # scaled BEFORE depth materialized
    assert len(prov.nodes) == 8


def test_advisor_scaledown_hysteresis_then_scale_to_zero():
    t = [0.0]
    fq, prov = _FakeQueue(), _NodesProvider()
    prov.spin_up("node", 2)
    adv = AutoscaleAdvisor(
        fq, prov, jobs_per_node=1, min_nodes=1, max_nodes=4,
        apply_enabled=True, forecaster=None, scaledown_hysteresis=1,
        scale_to_zero_after_s=10.0, clock=lambda: t[0],
    )
    rec = adv.apply("node")  # idle: clamp to min_nodes=1
    assert rec["action"] == "spin-down" and not rec["scale_to_zero"]
    assert prov.nodes == ["node1"]
    t[0] = 11.0  # idle past scale_to_zero_after_s
    rec = adv.apply("node")
    assert rec["scale_to_zero"] and rec["target_nodes"] == 0
    assert prov.nodes == []  # parked BELOW min_nodes
    rec = adv.recommend("node")  # already parked: nothing to do
    assert rec["action"] == "hold" and not rec["scale_to_zero"]


def test_advisor_status_reads_without_advancing_the_control_law():
    fq, prov = _FakeQueue(), _NodesProvider()
    prov.spin_up("node", 2)
    adv = AutoscaleAdvisor(
        fq, prov, jobs_per_node=1, min_nodes=0, max_nodes=4,
        apply_enabled=False, forecaster=None, scaledown_hysteresis=3,
    )
    assert adv.recommend("node")["action"] == "hold"  # streak 1 of 3
    for _ in range(5):
        s = adv.status("node")  # /healthz readout, no law step
        assert s["target_nodes"] == 0 and s["current_nodes"] == 2
    assert adv.recommend("node")["action"] == "hold"  # streak 2
    rec = adv.recommend("node")  # streak 3: hysteresis satisfied
    assert rec["action"] == "spin-down" and rec["dry_run"]
    assert prov.nodes == ["node1", "node2"]  # dry-run never applies


def test_render_workers_drain_annotation_and_advisor_line():
    """`swarm workers` (docs/OBSERVABILITY.md): per-worker state with
    the drain reason inlined, heartbeat age, and the advisor's
    target-vs-actual line when /healthz carries a recommendation."""
    from swarm_tpu.client.cli import _fmt_age, render_workers

    assert _fmt_age(None) == ""
    assert _fmt_age(100.0, now=103.2) == "3.2s"
    assert _fmt_age(1.0, now=301.0) == "5.0m"
    assert _fmt_age(1.0, now=7201.0) == "2.0h"

    statuses = {
        "workers": {
            "w0": {"status": "active", "last_contact": 100.0,
                   "polls_with_no_jobs": 0},
            "w1": {"status": "preempted", "last_contact": 99.0,
                   "polls_with_no_jobs": 3},
        },
        "draining": {"w1": "preempted"},
    }
    health = {
        "autoscale": {
            "prefix": "swarm-", "target_nodes": 8, "current_nodes": 3,
            "action": "spin-up", "dry_run": True, "queue_depth": 12,
            "forecast_jobs": 40.5,
        }
    }
    out = render_workers(statuses, health)
    assert "preempted (preempted)" in out  # drain reason annotated
    assert "active" in out
    assert (
        "autoscale[swarm-]: target 8 vs actual 3 nodes"
        " (spin-up, dry-run); queue depth 12, forecast 40.5 jobs" in out
    )
    # no /healthz (or no advisor): the table renders without the line
    assert "autoscale[" not in render_workers(statuses, None)
