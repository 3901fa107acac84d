"""Finds a cell's configuration, traffic mix, driver and metric readers by name.

Everything that belongs to one configuration, one mix or one metric is a
file of its own, found from the names in ``BENCHMARK.json``:

- ``benchmark/configs/<config>.json`` (the configuration's ``file``),
  whose ``driver`` names ``benchmark/drivers/<driver>.py``, the module
  that builds the deployment, makes its calls and holds its reference
  (run.py's docstring lists what a driver supplies);
- ``benchmark/traffic/<traffic>.json``, the mix's parameters;
- ``benchmark/metrics/<metric>.py``, a reader with ``read(run)`` that
  returns a number, or None where the run holds nothing to read.

A file that a configuration or mix names under ``<key>`` is pinned by
the sha256 under ``<key>_sha256`` beside it, so the yardstick cannot
move under a later change.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

PIN = "_sha256"


class SpecError(Exception):
    pass


@dataclass
class Cell:
    root: Path
    workload: dict
    config: dict
    mix: dict
    driver: ModuleType
    end_to_end: list   # metric entries this cell reports with --trace 0
    per_layer: list    # ... and with --trace 1


def _pinned(root: Path, rel: str, sha256: str) -> Path:
    path = root / rel
    if not path.is_file():
        raise SpecError(f"{rel} is missing")
    got = hashlib.sha256(path.read_bytes()).hexdigest()
    if got != sha256:
        raise SpecError(f"{rel} changed: sha256 {got}, pinned {sha256}")
    return path


def _pin_all(root: Path, doc: dict, where: str) -> None:
    """Check every ``<key>`` / ``<key>_sha256`` pair of ``doc``."""
    for key, sha256 in doc.items():
        if not key.endswith(PIN):
            continue
        name = key[: -len(PIN)]
        if name not in doc:
            raise SpecError(f"{where}: {key} pins no {name!r}")
        _pinned(root, doc[name], sha256)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _module(path: Path, prefix: str, name: str) -> ModuleType:
    """The module at ``path``, loaded by path (names hold dots)."""
    mod_name = f"{prefix}_{name.replace('.', '_').replace('-', '_')}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod  # dataclasses look their module up here
    spec.loader.exec_module(mod)
    return mod


def _driver(root: Path, config: dict, where: str) -> ModuleType:
    """``benchmark/drivers/<driver>.py`` of a configuration."""
    if "driver" not in config:
        raise SpecError(f"{where} names no driver")
    path = root / "benchmark" / "drivers" / f"{config['driver']}.py"
    if not path.is_file():
        raise SpecError(f"no driver {path.relative_to(root)}")
    mod = _module(path, "driver", config["driver"])
    if not isinstance(getattr(mod, "SPANS", None), tuple):
        raise SpecError(f"driver {path.relative_to(root)} names no SPANS tuple")
    return mod


def load(root: Path, workload: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg_entry = next((c for c in bench["configs"] if c["name"] == w["config"]), None)
    if cfg_entry is None:
        raise SpecError(f"workload {workload} names no known config {w['config']!r}")
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix_path = root / "benchmark" / "traffic" / f"{w['traffic']}.json"
    if not mix_path.is_file():
        raise SpecError(f"no traffic mix {mix_path.relative_to(root)}")
    mix = json.loads(mix_path.read_text())
    _pin_all(root, config, cfg_entry["file"])
    _pin_all(root, mix, str(mix_path.relative_to(root)))
    return Cell(
        root=root, workload=w, config=config, mix=mix,
        driver=_driver(root, config, cfg_entry["file"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
    )


def metric_module(root: Path, metric: str) -> ModuleType:
    """``benchmark/metrics/<metric>.py``."""
    path = root / "benchmark" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path.relative_to(root)}")
    return _module(path, "metric", metric)


def reader(root: Path, metric: str):
    """The ``read(run)`` function of the metric's reader."""
    return metric_module(root, metric).read
