"""One rank of the 2-process multi-host sharded-match proof.

Launched by tests/test_sharding.py::test_two_process_distributed_match
with the SWARM_COORDINATOR/NUM_PROCESSES/PROCESS_ID triplet set: forms
a real ``jax.distributed`` process group over localhost (the DCN
stand-in for the reference's multi-droplet fleet,
/root/reference/server/server.py:47-162), builds a mesh spanning BOTH
processes' devices, runs the sharded match, and writes the
host-gathered verdict planes for the parent to bit-compare against a
single-process run.

Also importable: ``build_world()`` is the shared deterministic
db+batch builder, used by the parent for the reference run.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))


def build_world():
    """Deterministic (db, batch) — identical in every process."""
    import random

    from swarm_tpu.fingerprints import load_corpus
    from swarm_tpu.fingerprints.compile import compile_corpus
    from swarm_tpu.ops.encoding import encode_batch

    sys.path.insert(0, str(Path(__file__).parent))
    from test_match_parity import fuzz_rows  # deterministic given rng

    templates, errors = load_corpus(Path(__file__).parent / "data" / "templates")
    assert templates and not errors
    db = compile_corpus(templates)
    rows = fuzz_rows(templates, random.Random(41), 16)
    # one row with OOB interaction data so the oobp/oobr streams
    # materialize at real widths (width-1 placeholders cannot be
    # seq-sharded — same setup as test_sharding's world fixture)
    rows[3].oob_protocols = ("http", "dns")
    rows[3].oob_requests = (
        b"GET /si00aa11bb22cc33 HTTP/1.1\r\nHost: cb.test\r\n\r\n" * 3
    )
    batch = encode_batch(rows, max_body=512, max_header=512, pad_rows_to=16)
    return db, batch


def probe() -> None:
    """Capability probe (SWARM_MH_PROBE=1): form the 2-process group
    and run ONE tiny cross-process psum. Exercises exactly the
    capability the full tests need — a jaxlib whose backend lacks
    multiprocess collectives fails here in seconds with the
    characteristic XlaRuntimeError, and the parent skips the heavy
    cases with that reason instead of timing them out."""
    import jax

    from swarm_tpu.parallel.multihost import maybe_initialize_distributed

    assert maybe_initialize_distributed(), "distributed init did not run"
    assert jax.process_count() == 2, jax.process_count()

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = np.array(jax.devices())
    mesh = Mesh(devices, ("data",))
    fn = jax.jit(
        jax.shard_map(
            lambda x: jax.lax.psum(x.sum(), "data"),
            mesh=mesh,
            in_specs=P("data"),
            out_specs=P(),
            check_vma=False,
        )
    )
    arr = np.ones((len(devices),), dtype=np.int32)
    x = jax.make_array_from_callback(
        arr.shape, NamedSharding(mesh, P("data")), lambda idx: arr[idx]
    )
    total = int(np.asarray(fn(x)))
    assert total == len(devices), total
    print(f"probe rank {jax.process_index()} ok", flush=True)


def main() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")

    if os.environ.get("SWARM_MH_PROBE"):
        probe()
        return

    from swarm_tpu.parallel.multihost import maybe_initialize_distributed

    assert maybe_initialize_distributed(), "distributed init did not run"
    assert jax.process_count() == 2, jax.process_count()

    import numpy as np

    from swarm_tpu.parallel.mesh import make_mesh
    from swarm_tpu.parallel.sharded import ShardedMatcher

    devices = jax.devices()
    assert len(devices) == 8, [str(d) for d in devices]
    # the mesh spans both processes: 'data' crosses the process
    # boundary, and model×seq exercise psum + ppermute halos over DCN
    mesh = make_mesh((2, 2, 2), devices=devices)
    n_procs = {d.process_index for d in mesh.devices.flat}
    assert n_procs == {0, 1}, n_procs

    db, batch = build_world()
    matcher = ShardedMatcher(db, mesh)
    assert matcher.multiprocess
    tv, tu, ov = matcher.match(batch.streams, batch.lengths, batch.status)

    # the serving split (docs/SHARDING.md): dispatch launches the
    # split-phase compacted kernels across BOTH processes' devices,
    # collect gathers the fused plane host-local over the DCN stand-in
    pending = matcher.dispatch(
        batch.streams, batch.lengths, batch.status, full=True
    )
    planes = matcher.collect(pending)

    out_path = os.environ["SWARM_MH_OUT"]
    np.savez(
        f"{out_path}.rank{jax.process_index()}",
        t_value=np.asarray(tv),
        t_unc=np.asarray(tu),
        overflow=np.asarray(ov),
        **{
            f"full_{name}": np.asarray(p)
            for name, p in zip(
                ("t_value", "t_unc", "op_value", "op_unc", "m_unc",
                 "overflow"),
                planes,
            )
        },
    )
    print(f"rank {jax.process_index()} ok", flush=True)


if __name__ == "__main__":
    main()
