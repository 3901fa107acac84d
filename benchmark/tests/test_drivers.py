"""A deployment is a driver file: one other than the census runs through
run.py from new files alone, and a configuration whose driver is
missing is refused.

The second deployment's tree copies ``run.py``, ``lib/``, ``metrics/``
and ``drivers/`` verbatim and adds a test driver (the worker's template
path, a ``MatchEngine`` over the bundled demo corpus, with its CPU
oracle as the reference), its configuration, its mix and a cell."""

import dataclasses
import importlib.util
import io
import json
import shutil
import sys
from contextlib import redirect_stderr, redirect_stdout

import pytest

import run
from helpers import BENCH, DATA, REPO, tree_with_tiny_cell
from lib import spec


def _template_tree(tmp):
    (tmp / "swarm_tpu").symlink_to(REPO / "swarm_tpu")
    (tmp / "tests" / "data").mkdir(parents=True)
    (tmp / "tests" / "data" / "templates").symlink_to(REPO / "tests" / "data" / "templates")
    for sub in ("configs", "traffic", "metrics", "lib", "drivers"):
        shutil.copytree(BENCH / sub, tmp / "benchmark" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH / "run.py", tmp / "benchmark" / "run.py")
    shutil.copy(DATA / "template_scan.py", tmp / "benchmark" / "drivers" / "template_scan.py")
    shutil.copy(DATA / "tiny-templates.json", tmp / "benchmark" / "configs" / "tiny-templates.json")
    shutil.copy(DATA / "tiny-http.json", tmp / "benchmark" / "traffic" / "tiny-http.json")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-templates", "source": "test",
                             "file": "benchmark/configs/tiny-templates.json",
                             "reduced": [], "why": "test size"})
    bench["workloads"].append({"name": "templates", "config": "tiny-templates",
                               "traffic": "tiny-http", "chips": 1, "why": "test size"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def _size(r):
    return len(r.header) + len(r.body) + len(r.banner or b"")


def _longest_row_answered_wrong(engine):
    """The longest row of each call (always among the rows compared)
    gains a template it did not match."""
    fn = engine.match

    def match(rows):
        out = fn(rows)
        j = max(range(len(rows)), key=lambda i: _size(rows[i]))
        out[j] = dataclasses.replace(out[j], template_ids=[*out[j].template_ids, "planted"])
        return out

    engine.match = match


@pytest.mark.parametrize("plant, correct", [(None, True), (_longest_row_answered_wrong, False)],
                         ids=["sound", "one_answer_wrong"])
def test_a_second_deployment_runs_from_new_files(tmp_path, monkeypatch, plant, correct):
    root = _template_tree(tmp_path)
    monkeypatch.setattr(sys, "path", list(sys.path))
    for var in ("JAX_COMPILATION_CACHE_DIR", "SWARM_DB_CACHE_DIR"):
        monkeypatch.setenv(var, "")
    path = root / "benchmark" / "run.py"
    mod = importlib.util.module_from_spec(importlib.util.spec_from_file_location("run_copy", path))
    monkeypatch.setitem(sys.modules, "run_copy", mod)  # its dataclass looks it up there
    mod.__spec__.loader.exec_module(mod)
    assert mod.ROOT == root
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = mod.run_cell(["--workload", "templates", "--seed", str(2**31 + 21), "--seconds", "1"],
                          require_tpu=False, plant=plant)
    assert rc == 0, err.getvalue()
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"] is correct
    checks = res["checks"]
    assert checks["compared_rows"]["value"] > 0 and checks["missing_rows"]["value"] == 0
    assert (checks["mismatched_rows"]["value"] == 0) is correct
    assert res["metrics"]["fingerprints_per_s"]["value"] > 0


def test_the_traffic_fires_templates(tmp_path):
    root = _template_tree(tmp_path)
    cell = spec.load(root, "templates")
    check = cell.driver.reference(root, cell.config)
    fired = [check.expected(r) for r in cell.driver.rows(cell.mix, 5, "window", 0)]
    assert sum(1 for f in fired if f) >= len(fired) // 4
    assert len(set(fired)) >= 3


def test_a_missing_driver_is_refused(tmp_path):
    root = tree_with_tiny_cell(tmp_path)
    cfg = root / "benchmark" / "configs" / "tiny-full.json"
    c = json.loads(cfg.read_text())
    c["driver"] = "no_such_driver"
    cfg.write_text(json.dumps(c))
    err = io.StringIO()
    with redirect_stdout(io.StringIO()) as out, redirect_stderr(err):
        rc = run.run_cell(["--workload", "tiny", "--seed", "1", "--seconds", "1"],
                          require_tpu=False, root=root)
    assert rc == 2 and not out.getvalue()
    assert "no driver benchmark/drivers/no_such_driver.py" in err.getvalue()


@pytest.mark.parametrize("doc", ["configs/tiny-full.json", "traffic/tiny-fresh.json"])
def test_every_pinned_pair_is_checked(tmp_path, doc):
    root = tree_with_tiny_cell(tmp_path)
    path = root / "benchmark" / doc
    d = json.loads(path.read_text())
    d["extra"], d["extra_sha256"] = "BENCHMARK.json", "0" * 64
    path.write_text(json.dumps(d))
    with pytest.raises(spec.SpecError, match="BENCHMARK.json changed"):
        spec.load(root, "tiny")
    del d["extra"]
    path.write_text(json.dumps(d))
    with pytest.raises(spec.SpecError, match="extra_sha256 pins no 'extra'"):
        spec.load(root, "tiny")
