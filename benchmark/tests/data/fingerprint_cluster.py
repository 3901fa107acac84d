"""A test deployment that is not a ``MatchEngine``: the worker's JARM
clustering step (worker/runtime.py ``_execute_jarm``). A call's
JARM-style fingerprints go through ``cluster.pack_strings`` and
``cluster.density_cluster``, one cluster label per fingerprint.

A label depends on every fingerprint of its call, so the reference
answers whole calls (``expected_call``): a plain numpy density-peaks
clustering (Rodriguez & Laio, Science 2014) that imports nothing of the
program. The program opens no spans; its compiles are counted from
JAX's own monitoring events.

A call holds ``rows_per_call`` fingerprints of ``servers`` server
configurations, Zipf(``zipf``) over them. A ``variant_share`` of each
server's rows answered ``variant_probes`` of the ten probes otherwise,
and a ``noise_share`` of the call are one-off servers. That layout is
the same in every call and for every seed; the seed draws the
fingerprints and the order of the rows in each call.
"""

from __future__ import annotations

import numpy as np

SPANS = ()
STREAMS = {"warmup": 1, "window": 2}
#: JAX records one per executable it compiles or loads from its cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
HEX = "0123456789abcdef"
PROBES = 10


def _triple(rng) -> str:
    """One probe's answer: cipher index and version code, or none."""
    if rng.random() < 0.1:
        return "000"
    return f"{int(rng.integers(1, 70)):02x}" + "abcdef"[int(rng.integers(1, 5))]


def _fingerprint(rng) -> str:
    """30 characters for the ten probes, 32 of the extensions' hash."""
    return "".join(_triple(rng) for _ in range(PROBES)) + "".join(rng.choice(list(HEX), 32))


def _layout(mix: dict) -> np.ndarray:
    """Each row's server (``-1``: a one-off server), before the call's
    order is drawn; a variant row's server is ``servers + server``."""
    n, servers = mix["rows_per_call"], mix["servers"]
    noise = round(n * mix["noise_share"])
    p = 1.0 / np.arange(1, servers + 1) ** mix["zipf"]
    counts = np.floor(p / p.sum() * (n - noise)).astype(int)
    counts[: n - noise - counts.sum()] += 1
    out = []
    for s, c in enumerate(counts):
        variants = round(c * mix["variant_share"])
        out += [servers + s] * variants + [s] * (c - variants)
    return np.array(out + [-1] * noise)


def fingerprints(mix: dict, seed: int, stream: str, k: int) -> list:
    """Call ``k`` of ``stream``: a pure function of the seed."""
    servers = mix["servers"]
    base_rng = np.random.default_rng([seed, 0])
    base = [_fingerprint(base_rng) for _ in range(servers)]
    rng = np.random.default_rng([seed, STREAMS[stream], k])
    out = []
    for s in rng.permutation(_layout(mix)):
        if s < 0:
            out.append(_fingerprint(rng))
        elif s < servers:
            out.append(base[s])
        else:
            fp = list(base[s - servers])
            for probe in rng.choice(PROBES, mix["variant_probes"], replace=False):
                fp[3 * probe: 3 * probe + 3] = _triple(rng)
            out.append("".join(fp))
    return out


class Clusterer:
    """The worker's clustering step."""

    def __init__(self, radius: float):
        from swarm_tpu.ops import cluster

        self.cl, self.radius = cluster, radius

    def cluster(self, fps: list):
        labels, _rho = self.cl.density_cluster(self.cl.pack_strings(fps), self.radius)
        return labels


class FingerprintCluster:
    def __init__(self, root, config: dict, mix: dict, seed: int):
        import jax

        self.mix, self.seed = mix, int(seed)
        self.program = Clusterer(float(config["radius"]))
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, _secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1

    def stats(self) -> dict:
        return {"device_compiles": self.compiles}

    def call(self, stream: str, k: int) -> tuple:
        fps = fingerprints(self.mix, self.seed, stream, k)
        return fps, fps, [len(f) for f in fps]

    @staticmethod
    def run(program, inputs):
        return program.cluster(inputs)

    @staticmethod
    def answer(label) -> int:
        return int(label)


class DensityPeaks:
    """Density-peaks clustering over the Hamming distance of each
    fingerprint's first ``fingerprint_bits`` bits (its bytes, low bit
    first, zero-padded). ``rho``: the rows within ``radius``, itself
    included. A row's parent: the nearest row that is denser, or as dense
    and earlier; the lowest index among the nearest. A row with no parent
    or one farther than ``radius`` is a peak. Peaks are numbered densest
    first, the earlier first among equals; every other row takes its
    parent's label."""

    def __init__(self, root, config: dict):
        self.radius = float(config["radius"])
        self.nbytes = int(config["fingerprint_bits"]) // 8

    def _bits(self, fps: list) -> np.ndarray:
        raw = np.zeros((len(fps), self.nbytes), np.uint8)
        for i, fp in enumerate(fps):
            b = fp.encode()[: self.nbytes]
            raw[i, : len(b)] = np.frombuffer(b, np.uint8)
        return np.unpackbits(raw, axis=1, bitorder="little").astype(np.float32)

    def expected_call(self, fps: list) -> list:
        x = self._bits(fps)
        n = len(x)
        pop = x.sum(axis=1)
        idx = np.arange(n)
        step = max(1, (1 << 24) // n)  # rows per block: 64 MiB of distances

        def blocks():
            for lo in range(0, n, step):
                hi = min(n, lo + step)
                yield lo, hi, pop[lo:hi, None] + pop[None, :] - 2.0 * (x[lo:hi] @ x.T)

        rho = np.concatenate([(d <= self.radius).sum(axis=1) for _lo, _hi, d in blocks()])
        parent = np.full(n, -1)
        delta = np.full(n, np.inf)
        for lo, hi, d in blocks():
            r, i = rho[lo:hi, None], idx[lo:hi, None]
            denser = (rho[None, :] > r) | ((rho[None, :] == r) & (idx[None, :] < i))
            d = np.where(denser, d, np.inf)
            j = d.argmin(axis=1)
            delta[lo:hi] = d[np.arange(hi - lo), j]
            parent[lo:hi] = np.where(np.isfinite(delta[lo:hi]), j, -1)
        peak = (parent < 0) | (delta > self.radius)
        label = np.full(n, -1)
        order = sorted(range(n), key=lambda i: (-rho[i], i))  # parents come first
        peaks = 0
        for i in order:
            if peak[i]:
                label[i], peaks = peaks, peaks + 1
            else:
                label[i] = label[parent[i]]
        return label.tolist()


def build(root, config: dict, mix: dict, seed: int) -> FingerprintCluster:
    return FingerprintCluster(root, config, mix, seed)


def reference(root, config: dict) -> DensityPeaks:
    return DensityPeaks(root, config)
