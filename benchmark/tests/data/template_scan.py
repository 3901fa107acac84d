"""A test deployment that is not the census: the worker's template path,
``MatchEngine`` over a template corpus (worker/runtime.py
``_engine_for``), on calls of HTTP answers and raw banners assembled
from the mix's fragments. The reference is the program's CPU oracle
(ops/cpu_ref.py) over templates it loads itself: enough to show that
run.py drives a second deployment from new files alone."""

from __future__ import annotations

import numpy as np

from lib.matchengine import EngineProbe

STREAMS = {"warmup": 1, "window": 2}
#: the spans every MatchEngine opens
SPANS = (
    "engine.encode_wait",
    "engine.encode_chunk",
    "engine.confirm_walk",
    "engine.assemble",
    "device.launch",
    "device.phase_a_wait",
    "device.result_wait",
)


def _size(r) -> int:
    return len(r.header) + len(r.body) + len(r.banner or b"")


def rows(mix: dict, seed: int, stream: str, k: int) -> list:
    """Call ``k`` of ``stream``: a pure function of the seed."""
    from swarm_tpu.fingerprints.model import Response

    rng = np.random.default_rng([seed, STREAMS[stream], k])
    out = []
    for i in range(mix["rows_per_call"]):
        token = b" id-%d" % rng.integers(1 << 40)
        host = f"10.{STREAMS[stream]}.{k % 256}.{i}"
        if rng.random() < mix["banner_share"]:
            banner = rng.choice(mix["banners"]).encode() + token
            out.append(Response(host=host, port=7777, banner=banner))
            continue
        status = int(rng.choice(mix["statuses"]))
        picks = rng.choice(mix["body_fragments"], size=rng.integers(0, 4))
        body = " ".join(picks).encode() + token
        header = "\r\n".join([f"HTTP/1.1 {status} OK"] + list(
            rng.choice(mix["header_fragments"], size=rng.integers(0, 3)))).encode()
        out.append(Response(host=host, port=443, status=status, body=body, header=header))
    return out


class TemplateScan:
    def __init__(self, root, config: dict, mix: dict, seed: int):
        from swarm_tpu.fingerprints.dbcache import load_or_compile
        from swarm_tpu.ops.engine import MatchEngine

        self.mix, self.seed = mix, int(seed)
        templates, db = load_or_compile(str(root / config["corpus"]))
        self.program = MatchEngine(templates, db=db)
        probe = EngineProbe(self.program)
        self.stats, self.notes = probe.stats, probe.notes

    def call(self, stream: str, k: int) -> tuple:
        calls = rows(self.mix, self.seed, stream, k)
        return calls, calls, [_size(r) for r in calls]

    @staticmethod
    def run(program, inputs) -> list:
        return program.match(inputs)

    @staticmethod
    def answer(matches) -> tuple:
        return tuple(sorted(matches.template_ids))


class Oracle:
    def __init__(self, root, config: dict):
        from swarm_tpu.fingerprints import load_corpus

        self.templates, _errors = load_corpus(str(root / config["corpus"]))
        self.verdict_width = len(self.templates)

    def expected(self, row) -> tuple:
        from swarm_tpu.ops import cpu_ref

        return tuple(sorted(t.id for t in self.templates
                            if cpu_ref.match_template(t, row).matched))


def build(root, config: dict, mix: dict, seed: int) -> TemplateScan:
    return TemplateScan(root, config, mix, seed)


def reference(root, config: dict) -> Oracle:
    return Oracle(root, config)
