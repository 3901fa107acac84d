"""Test harness: force an 8-device virtual CPU mesh before JAX imports.

Multi-chip behavior (dp/tp/sp shardings, halo exchange) is validated on
a virtual CPU mesh — the analog of the reference's multi-droplet setup
without a cluster (SURVEY.md §4f). Benchmarks run on real TPU separately.
"""

import os
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"  # the suite runs on the CPU
# hermetic corpus-compile cache: don't read/write ~/.cache during tests
# (lazy so a preset env var doesn't leak an orphan temp dir)
if "SWARM_DB_CACHE_DIR" not in os.environ:
    os.environ["SWARM_DB_CACHE_DIR"] = tempfile.mkdtemp(
        prefix="swarm_test_dbc_"
    )
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Persistent XLA compilation cache for the suite (utils/xlacache.py —
# the same corpus kernels are re-jitted by many test modules from
# fresh DeviceDB/MatchEngine instances; deserializing an identical
# program beats recompiling it, and the tier-1 wall stays inside its
# timeout). Content-keyed, so staleness is impossible; a second run on
# the same machine starts warm. A set JAX_COMPILATION_CACHE_DIR wins;
# SWARM_TEST_XLA_CACHE_DIR= (empty) disables.
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    from swarm_tpu.utils import xlacache  # noqa: E402

    # per-user default path: a fixed world-shared /tmp dir would be
    # unwritable (or poisonable) for the second user on a shared host
    xlacache.enable_compilation_cache(
        os.environ.get(
            "SWARM_TEST_XLA_CACHE_DIR",
            os.path.join(
                tempfile.gettempdir(),
                f"swarm_test_xla_cache_{os.getuid()}",
            ),
        )
    )
    # the suite compiles MANY sub-second kernels repeatedly across
    # modules (fresh jit closures per DeviceDB/engine instance) —
    # cache those too, not just the >1s production kernels
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.25)

import pytest  # noqa: E402


def pytest_configure(config):
    # jax warns at compile time when donated buffers can't alias into
    # outputs; EXPECTED on the split-phase dispatch (outputs are tiny
    # packed planes — donation buys early staged-buffer release, not
    # aliasing; docs/DEVICE_MATCH.md). ops/match.py filters it at
    # module scope for production processes; pytest re-applies its own
    # filters per test, so mirror the filter here.
    config.addinivalue_line(
        "filterwarnings",
        "ignore:Some donated buffers were not usable",
    )


@pytest.fixture
def tmp_stores(tmp_path):
    """Embedded stores rooted in a temp dir."""
    from swarm_tpu.config import Config
    from swarm_tpu.stores import build_stores

    cfg = Config(
        blob_root=str(tmp_path / "blobs"),
        doc_root=str(tmp_path / "docs"),
    )
    return build_stores(cfg)
