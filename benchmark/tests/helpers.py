"""A scratch tree that adds a configuration, a mix and a metric as new files."""

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"

TINY_METRIC = '''"""Rows per call (a test reader)."""


def read(run):
    return run.rows / run.calls if run.calls else None
'''


def tree_with_tiny_cell(tmp: Path) -> Path:
    """``tmp`` holding the benchmark's files plus, as new files only, the
    test-sized configuration ``tiny-full``, mix ``tiny-fresh``, metric
    ``test.rows_per_call`` and a ``tiny`` cell in BENCHMARK.json."""
    (tmp / "swarm_tpu").symlink_to(REPO / "swarm_tpu")
    for sub in ("configs", "traffic", "metrics", "lib", "drivers"):
        shutil.copytree(BENCH / sub, tmp / "benchmark" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(DATA / "tiny-full.json", tmp / "benchmark" / "configs" / "tiny-full.json")
    shutil.copy(DATA / "tiny-fresh.json", tmp / "benchmark" / "traffic" / "tiny-fresh.json")
    (tmp / "benchmark" / "metrics" / "test.rows_per_call.py").write_text(TINY_METRIC)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:  # the census at test size reads what census-fresh reads
        if "census-fresh" in m.get("workloads", ()):
            m["workloads"].append("tiny")
    bench["configs"].append({"name": "tiny-full", "source": "test",
                             "file": "benchmark/configs/tiny-full.json",
                             "reduced": [], "why": "test size"})
    bench["workloads"].append({"name": "tiny", "config": "tiny-full",
                               "traffic": "tiny-fresh", "chips": 1, "why": "test size"})
    bench["end_to_end"].append({"name": "test.rows_per_call", "unit": "rows",
                                "better": "higher", "bound": 0.25,
                                "source": "host_clock", "workloads": ["tiny"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
