"""Main-path kernels compiled for a described TPU v5e (no chip attached).

The TPU compiler is installed here and compiles for a topology that is
described, not attached: what it refuses (unaligned slices, VMEM
overuse, programs that do not fit HBM, kernels that cannot be
partitioned) fails here at no chip time. Nothing runs, so these say
nothing about results or speed.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and a collection-
time decision would hand xdist workers different test sets. Keep every
such compile in this one file.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

BUNDLED_DB = "swarm_tpu/data/service-probes.txt"
BATCH_ROWS = 2048  # the production batch shape: 2,048 rows x 2,048 bytes
BATCH_BYTES = 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=False)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(tree, sharding):
    """Shape avatars of ``tree`` placed by ``sharding`` (a sharding, or
    a callable leaf -> sharding)."""
    pick = sharding if callable(sharding) else (lambda _a: sharding)
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=pick(a)),
        tree,
    )


def service_batch(db_path: str, rows: int = BATCH_ROWS, width: int = BATCH_BYTES):
    """(DeviceDB of the service classifier, the batch the single-device
    engine encodes for ``rows`` banners of ``width`` bytes)."""
    from swarm_tpu.fingerprints.model import Response
    from swarm_tpu.ops.encoding import encode_batch
    from swarm_tpu.ops.service import ServiceClassifier

    cl = ServiceClassifier(db_path=db_path, mesh=None)
    rng = np.random.default_rng(0)
    banners = [
        b"220 host.example FTP ready " + bytes(rng.integers(32, 127, width - 27, dtype=np.uint8))
        for _ in range(rows)
    ]
    batch = encode_batch(
        [Response(host="h", port=21, banner=b) for b in banners],
        max_body=cl.engine.max_body, max_header=cl.engine.max_header,
        pad_rows_to=rows, build_all=False, width_multiple=512,
    )
    return cl.engine.device, batch


def dispatch_args(dev, batch, sharding):
    """Shape avatars of what ``DeviceDB.dispatch`` hands its two
    executables — phase A (stacked prefilter) and phase B (survivor
    verify + verdicts): (corpus args, streams, lengths, status, phase-A
    rank plane, overflow), placed on the device behind ``sharding``."""
    _meta, arrays = dev._ensure_layout()
    args = _sds(arrays, sharding)
    s = _sds(batch.streams, sharding)
    ln = _sds(batch.lengths, sharding)
    st = _sds(batch.status, sharding)
    cnt, ovf, _ = jax.eval_shape(dev._phase_a(), args, s, ln)
    return args, s, ln, st, _sds(cnt, sharding), _sds(ovf, sharding)


@pytest.fixture(scope="module")
def service_dispatch(one_chip):
    dev, batch = service_batch(BUNDLED_DB)
    assert batch.streams["body"].shape == (BATCH_ROWS, BATCH_BYTES)
    return dev, dispatch_args(dev, batch, one_chip)


def _fits_hbm(comp) -> None:
    mem = comp.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


def test_service_match_phase_a_compiles(service_dispatch, no_persistent_cache):
    dev, (args, s, ln, _st, _cnt, _ovf) = service_dispatch
    _fits_hbm(dev._phase_a().lower(args, s, ln).compile())


def test_service_match_phase_b_compiles(service_dispatch, no_persistent_cache):
    """Phase B at the smallest survivor rung, the one most batches
    launch at (the executable's code size does not grow with it)."""
    from swarm_tpu.fingerprints import compile as fpc

    dev, (args, s, ln, st, cnt, ovf) = service_dispatch
    kc = fpc.survivor_bucket(0, dev._budget())
    fb = dev._phase_b(True, True)
    _fits_hbm(fb.lower(kc, args, s, ln, st, cnt, ovf).compile())


@pytest.mark.parametrize("n,tile", [(65_536, 256), (16, 16), (8, 8)])
def test_pallas_cluster_compiles(one_chip, no_persistent_cache, n, tile):
    from swarm_tpu.ops import cluster

    if n < cluster._TILE:  # the tile density_cluster picks for small N
        assert tile == min(cluster._TILE, max(8, 1 << (n - 1).bit_length()))
    packed = jax.ShapeDtypeStruct((n, cluster.FP_WORDS), jnp.uint32, sharding=one_chip)
    n_s = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    r_s = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    comp = cluster._cluster_device.lower(
        packed, n_s, r_s, tile=tile, pallas=True
    ).compile()
    assert "tpu_custom_call" in comp.as_text()


def test_sharded_phase_a_compiles_on_2x2(topo, no_persistent_cache, monkeypatch):
    from jax.sharding import NamedSharding

    from swarm_tpu.parallel.mesh import make_mesh
    from swarm_tpu.parallel.sharded import ShardedMatcher

    mesh = make_mesh(devices=topo.devices)
    assert mesh.devices.size == 4
    dev, batch = service_batch(BUNDLED_DB, rows=256, width=512)
    # a described device holds no array: keep the corpus upload on the host
    monkeypatch.setattr(ShardedMatcher, "_global", lambda self, arr, spec: arr)
    sm = ShardedMatcher(dev.db, mesh)
    # the sharded engine ships the host-built "all" stream
    streams = dict(batch.streams)
    streams["all"] = batch.streams["body"]
    tab_specs, _rep, stream_spec, lengths_spec = sm._specs(streams, batch.lengths)
    place = lambda specs: lambda name: NamedSharding(mesh, specs[name])  # noqa: E731
    tab = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                   sharding=place(tab_specs)(k))
           for k, v in sm._tab_np.items()}
    s = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=place(stream_spec)(k))
         for k, v in streams.items()}
    ln = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=place(lengths_spec)(k))
          for k, v in batch.lengths.items()}
    comp = sm._build_phase_a(streams, batch.lengths, False).lower(tab, s, ln).compile()
    assert len(comp.output_shardings[0].device_set) == 4
