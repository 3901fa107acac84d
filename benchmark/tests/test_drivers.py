"""A deployment is a driver file: others than the census run through
run.py from new files alone, and a configuration whose driver is
missing is refused.

Each other deployment's tree copies ``run.py``, ``lib/``, ``metrics/``
and ``drivers/`` verbatim and adds a test driver, its configuration,
its mix and a cell: the worker's template path (a ``MatchEngine`` over
the bundled demo corpus, with its CPU oracle as the reference), and the
worker's JARM clustering step, which is no ``MatchEngine`` and whose
reference answers whole calls."""

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


#: the per-layer metrics that read the census's engine and spans
ENGINE_ONLY = {"classifier.veto_walk_ms", "classifier.exposed_ms", "engine.host_confirm_ms",
               "engine.memo_share", "engine.exposed_ms", "kernel.match_ms",
               "device.host_wait_ms"}


def _tree(tmp, driver, config, mix, cell):
    """``tmp`` holding the benchmark's files verbatim plus, as new files,
    ``driver``, ``config`` and ``mix`` from the tests' data and the cell."""
    (tmp / "swarm_tpu").symlink_to(REPO / "swarm_tpu")
    for sub in ("configs", "traffic", "metrics", "lib", "drivers"):
        shutil.copytree(BENCH / sub, tmp / "benchmark" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH / "run.py", tmp / "benchmark" / "run.py")
    shutil.copy(DATA / f"{driver}.py", tmp / "benchmark" / "drivers" / f"{driver}.py")
    shutil.copy(DATA / f"{config}.json", tmp / "benchmark" / "configs" / f"{config}.json")
    shutil.copy(DATA / f"{mix}.json", tmp / "benchmark" / "traffic" / f"{mix}.json")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": config, "source": "test",
                             "file": f"benchmark/configs/{config}.json",
                             "reduced": [], "why": "test size"})
    bench["workloads"].append({"name": cell, "config": config,
                               "traffic": mix, "chips": 1, "why": "test size"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def _template_tree(tmp):
    (tmp / "tests" / "data").mkdir(parents=True)
    (tmp / "tests" / "data" / "templates").symlink_to(REPO / "tests" / "data" / "templates")
    return _tree(tmp, "template_scan", "tiny-templates", "tiny-http", "templates")


def _cluster_tree(tmp):
    return _tree(tmp, "fingerprint_cluster", "tiny-jarm-cluster", "tiny-jarm", "jarm")


def _run_copy(root, monkeypatch, argv, plant=None):
    """One run of the tree's own copy of run.py: (exit code, result, stderr)."""
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
        rc = mod.run_cell(argv, require_tpu=False, plant=plant)
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]) if lines else None, err.getvalue()


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
    rc, res, err = _run_copy(
        root, monkeypatch, ["--workload", "templates", "--seed", str(2**31 + 21), "--seconds", "1"],
        plant)
    assert rc == 0, err
    assert "encoded batch shapes (streams, phase-B width): " in err
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


def _one_row_relabelled(clusterer):
    fn = clusterer.cluster

    def cluster(fps):
        labels = fn(fps)
        labels[len(labels) // 2] += 1
        return labels

    clusterer.cluster = cluster


def _previous_calls_labels(clusterer):
    fn, last = clusterer.cluster, []

    def cluster(fps):
        labels = fn(fps)
        out = last[0] if last else labels
        last[:] = [labels]
        return out

    clusterer.cluster = cluster


@pytest.mark.parametrize("plant, correct", [
    (None, True), (_one_row_relabelled, False), (_previous_calls_labels, False)],
    ids=["sound", "one_row_relabelled", "previous_calls_labels"])
def test_a_deployment_that_is_no_engine_compares_whole_calls(tmp_path, monkeypatch, plant,
                                                              correct):
    """The clustering step runs from new files, and the reference
    compares every row of the calls kept whole: call 0 and one more."""
    root = _cluster_tree(tmp_path)
    rc, res, err = _run_copy(
        root, monkeypatch, ["--workload", "jarm", "--seed", str(2**31 + 31), "--seconds", "1"],
        plant)
    assert rc == 0, err
    assert res["correct"] is correct, err
    checks = res["checks"]
    assert checks["compared_rows"]["value"] == 2 * 512
    assert checks["missing_rows"]["value"] == 0
    assert (checks["mismatched_rows"]["value"] == 0) is correct
    assert res["failed"] == 0 and res["attempted"] > 2 * 512
    assert "device_compiles_in_window: 0" in err
    assert res["metrics"]["fingerprints_per_s"]["value"] > 0


def test_the_engine_metrics_are_not_read_where_there_is_no_engine(tmp_path, monkeypatch):
    root = _cluster_tree(tmp_path)
    assert [m["name"] for m in spec.load(root, "jarm").per_layer] == ["device.idle_share"]
    read: list = []
    reader = spec.reader

    def noting(root_, name):
        read.append(name)
        return reader(root_, name)

    monkeypatch.setattr(spec, "reader", noting)
    rc, res, err = _run_copy(
        root, monkeypatch,
        ["--workload", "jarm", "--seed", str(2**31 + 32), "--seconds", "1", "--trace", "1"])
    assert rc == 0 and res["correct"] is True, err
    assert read == ["device.idle_share"] and not ENGINE_ONLY & set(res["metrics"])
    # no device plane on the CPU; no program span: gaps, if any, are the harness's
    assert all(name == "harness" for name, _s in res["breakdown"]["idle_gaps"])


def test_the_clusters_follow_the_mix(tmp_path):
    """The reference finds the mix's servers as clusters (the one-off
    servers alone, each variant with its server), and two calls of one
    seed hold the same fingerprints in another order."""
    root = _cluster_tree(tmp_path)
    cell = spec.load(root, "jarm")
    fps = cell.driver.fingerprints(cell.mix, 5, "window", 0)
    labels = cell.driver.reference(root, cell.config).expected_call(fps)
    noise = round(512 * cell.mix["noise_share"])
    assert len(set(labels)) == cell.mix["servers"] + noise
    again = cell.driver.fingerprints(cell.mix, 5, "window", 1)
    assert again != fps and len(set(fps) & set(again)) >= cell.mix["servers"]
