"""Device idle time per call while the engine holds the host: the
window's idle gaps intersected with the program's
``engine.encode_wait``, ``engine.confirm_walk`` and ``engine.assemble``
spans (ops/engine.py), mean over devices."""

from lib import spans


def read(run):
    return spans.exposed_ms(
        run, ["engine.encode_wait", "engine.confirm_walk", "engine.assemble"])
