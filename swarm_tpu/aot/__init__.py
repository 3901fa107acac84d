"""AOT executable cache: compiled XLA kernels as distributable data.

Compile time is the fleet's worst cold-start cliff (6.4 s cold on the
CPU box, 124–133 s compile+first-call in round-5 chip records, since
deleted), paid per worker per shape class, exactly when the
autoscale advisor adds workers under load. This package serializes the
phase-A / phase-B-ladder / fused-twin executables
(``jax.jit(...).lower().compile()`` + executable serialization) and
ships them through the existing Redis/S3-role stores under the
``swarm_tpu/cache`` epoch + fencing-token discipline, so a joining
worker FETCHES and loads instead of compiling — falling back to a live
compile on any miss or deserialize failure (breaker-wrapped; the cache
is an accelerator, never a dependency). docs/AOT.md has the key
schema, invalidation rules and the operator runbook.
"""

from swarm_tpu.aot.store import (  # noqa: F401
    AotClient,
    AotStore,
    build_aot_client,
    jax_fingerprint,
    kernel_code_salt,
)
from swarm_tpu.aot.jitcache import AotJit, aval_signature  # noqa: F401
