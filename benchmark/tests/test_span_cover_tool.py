"""``span_cover.py``: a traced run of the test-sized cell on the CPU
through the harness, with the cover of its window, and the span cost."""

import json

import span_cover
from helpers import tree_with_tiny_cell


def test_traced_run_prints_the_harness_line_and_its_cover(tmp_path):
    root = tree_with_tiny_cell(tmp_path)
    rc, out, cov = span_cover.traced(
        ["--workload", "tiny", "--seed", "2300000401", "--seconds", "1"],
        require_tpu=False, root=root)
    assert rc == 0
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True
    assert "classifier.veto_walk_ms" in result["metrics"]
    # the CPU trace has no TPU plane: spans counted, idle time not read
    assert cov["idle_ms_per_call"] is None
    per_call = {n: v["count"] for n, v in cov["per_name"].items()}
    assert per_call["classifier.classify"] >= 1
    assert per_call["engine.confirm_walk"] >= per_call["classifier.classify"]
    assert cov["spans_per_call"] >= 9


def test_span_cost_in_three_states():
    got = span_cover.cost(n=2000)
    assert got["spans"] == 2000
    for key in ("off_no_session_us", "session_us", "operator_on_us"):
        assert got[key] > 0, key
    assert got["operator_on_us"] > got["off_no_session_us"]
