"""chip_smoke.py's CPU rehearsal: the census through server -> worker ->
client on the bundled DB, every phase passing, and the last line
refusing to report a chip result without a TPU."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def repo_copy(tmp_path_factory):
    """A private copy: the smoke rebuilds native/ (make -B), which must
    not race the other test workers' loads of the same libraries."""
    copy = tmp_path_factory.mktemp("chip_smoke") / "repo"
    shutil.copytree(
        REPO, copy,
        ignore=shutil.ignore_patterns(
            ".git", ".xla_cache", "chiprun_out", "_archive", "__pycache__", "*.so"
        ),
    )
    return copy


def _run(copy: Path, *args: str):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "chip_smoke.py", *args],
        cwd=copy, env=env, capture_output=True, text=True, timeout=300,
    )
    lines = r.stdout.strip().splitlines()
    phases = {ln.split()[1].rstrip(":"): ": PASS" in ln
              for ln in lines if ln.startswith("phase ")}
    return r, phases, json.loads(lines[-1])


def test_chip_smoke_rehearsal_passes_every_phase_and_reports_no_chip(repo_copy):
    r, phases, last = _run(repo_copy, "--rehearsal")
    assert phases and all(phases.values()), r.stdout + r.stderr[-3000:]
    assert list(phases)[:6] == ["build", "banners", "server", "worker", "submit", "scan"]
    assert "oracle" in phases
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert r.returncode != 0


def test_chip_smoke_stops_when_the_worker_finds_no_tpu(repo_copy):
    """The full-width census is for the chip: on the CPU it stops once
    the worker reports its device, before any scan."""
    r, phases, last = _run(repo_copy)
    assert phases["worker"] and phases["tpu"] is False, r.stdout + r.stderr[-3000:]
    assert "submit" not in phases
    assert last == {"ok": False, "device": last["device"]}
    assert last["device"]["platform"] == "cpu"
    assert r.returncode != 0


def test_chip_smoke_alone_reports_nothing(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo it fails, and claims no result."""
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode != 0
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["device"] is None
