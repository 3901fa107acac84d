"""Driver entry points (__graft_entry__.py) — the round deliverables.

These run in-process on the conftest's 8-device virtual CPU mesh, the
same shapes the driver validates: entry() must jit-compile and run,
and dryrun_multichip must execute the FULL sharded step. A regression
here is a failed MULTICHIP/compile check for the whole round, so it
must be caught by the suite, not the driver.
"""

import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).parent.parent))

import __graft_entry__ as graft  # noqa: E402


def test_entry_compiles_and_runs():
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    shapes = [getattr(o, "shape", None) for o in out]
    assert shapes[0] is not None and shapes[0][0] == 64  # [B, NT] verdicts
    assert shapes[0] == shapes[1]  # uncertainty plane matches


def test_dryrun_multichip_runs_in_process(capsys):
    # backend is already up (conftest) with 8 virtual CPU devices, so
    # this takes the direct _dryrun_multichip_here path — including the
    # per-stream halo padding for narrow streams (width-1 OOB
    # placeholders broke this once)
    assert len(jax.devices()) >= 8
    graft.dryrun_multichip(8)
    out = capsys.readouterr().out
    assert "dryrun_multichip:" in out and "ok" in out


def test_dryrun_multichip_fails_short_of_devices_unless_cpu_selected(monkeypatch):
    """Too few devices is an error off the CPU: no silent move to a
    virtual CPU mesh on a chip host."""
    monkeypatch.setenv("JAX_PLATFORMS", "")
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        graft.dryrun_multichip(64)
