"""The nmap -sV service census (configurations with ``"driver": "service_census"``).

The timed path is the worker's chip-facing service path,
``ServiceClassifier.classify(rows, sent)`` (worker/runtime.py), built as
the worker builds it from the configuration's ``db`` and ``mesh``. A
call's rows come from the census generator (lib/traffic.py), each with
the probe a scan sends to its port, which set-up picks from the DB's
Probe lines alone. The classifier's ``MatchEngine`` gives the counters
and the encoded shapes (lib/matchengine.py). The reference is
lib/reference.py, a plain nmap-service-probes walker that imports
nothing of the program; the control is that walker with its capture
binding off, in the program's place.
"""

from __future__ import annotations

from types import SimpleNamespace

from lib.matchengine import EngineProbe
from lib.reference import Reference, parse_db, probe_for_port
from lib.traffic import WARMUP, WINDOW, Generator

#: a row's answer, as ``ServiceInfo`` and the reference give it
FIELDS = ("service", "product", "version", "info", "cpe", "soft")
STREAMS = {"warmup": WARMUP, "window": WINDOW}
#: every span the census path opens: the service classifier's, and the
#: engine's and device's that every MatchEngine opens
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


def _db_text(root, config) -> str:
    return (root / config["db"]).read_text(encoding="latin-1")


class ControlClassifier:
    """The reference in the program's place, its capture binding off."""

    def __init__(self, db_text: str):
        self.ref = Reference(db_text, capture=False)

    def classify(self, rows, sent):
        return [SimpleNamespace(host=r.host, port=r.port,
                                **dict(zip(FIELDS, self.ref.classify(r.banner, s))))
                for r, s in zip(rows, sent)]


class Census:
    def __init__(self, root, config: dict, mix: dict, seed: int):
        from swarm_tpu.fingerprints.model import Response
        from swarm_tpu.ops.service import ServiceClassifier

        self._response = Response
        self.db_text = _db_text(root, config)
        probes = parse_db(self.db_text, matches=False)  # ports and rarities alone
        self.gen = Generator(root, mix, seed, lambda port: probe_for_port(probes, port))
        self.program = ServiceClassifier(db_path=str(root / config["db"]), mesh=config["mesh"])
        probe = EngineProbe(self.program.engine)
        self.stats, self.notes = probe.stats, probe.notes

    def call(self, stream: str, k: int) -> tuple:
        rows = self.gen.call(STREAMS[stream], k)
        inputs = ([self._response(host=h, port=p, banner=b) for h, p, b, _s in rows],
                  [s for _h, _p, _b, s in rows])
        return inputs, rows, [len(b) for _h, _p, b, _s in rows]

    @staticmethod
    def run(program, inputs) -> list:
        return program.classify(*inputs)

    @staticmethod
    def answer(info) -> tuple:
        return ((info.service, info.product, info.version, info.info,
                 tuple(info.cpe), bool(info.soft)), (info.host, info.port))

    def control(self) -> ControlClassifier:
        return ControlClassifier(self.db_text)


class Check:
    """The reference's answer to a row: its classification and host:port."""

    def __init__(self, root, config: dict):
        self.ref = Reference(_db_text(root, config))
        self.verdict_width = self.ref.n_directives  # match directives

    def expected(self, row) -> tuple:
        host, port, banner, sent = row
        return self.ref.classify(banner, sent), (host, port)


def build(root, config: dict, mix: dict, seed: int) -> Census:
    return Census(root, config, mix, seed)


def reference(root, config: dict) -> Check:
    return Check(root, config)
