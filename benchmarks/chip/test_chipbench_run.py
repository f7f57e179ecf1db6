"""Whole runs of the harness at a size the CPU holds: the result line's
schema, a cell added by files and entries alone, and the entry point's
refusal of a device it has no peaks for."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from chipbench import harness, testing

BENCH = Path(__file__).resolve().parent
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return testing.tiny_tree(tmp_path_factory.mktemp("bench"))


def run(tree, workload, trace=False, seconds=2.0, seed=2**33 + 7):
    root, bench = tree
    cell = harness.Cell.load(root, bench, workload)
    return harness.run(cell, seed, seconds, trace=trace,
                       t_start=time.perf_counter(), require_chip=False,
                       trace_dir=root / ".trace")


def test_result_line_schema(tree, capsys):
    out = run(tree, "tiny.tiny_chat")
    harness.print_result(out)
    stdout, stderr = capsys.readouterr()
    line = json.loads(stdout.strip().splitlines()[-1])
    assert list(line)[:5] == KEYS and list(line)[-1] == "checks"
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert line["metrics"]["tokens_per_s"]["unit"] == "tokens/s"
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert list(line["checks"]) == ["max_logit_gap", "mean_logit_gap",
                                    "wrong_length_answers"]
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]
    assert stderr.strip().splitlines()[-3].startswith(
        "check max_logit_gap value=")


@pytest.mark.parametrize("workload", ["tiny.tiny_closed", "tiny.tiny_chat"])
def test_traced_run_reports_per_layer_metrics(tree, workload):
    out = run(tree, workload, trace=True)
    line = out.result
    spec = json.loads((tree[0] / "BENCHMARK.json").read_text())
    per_layer = {m["name"] for m in spec["per_layer"]}
    assert line["correct"] is True
    assert set(line["metrics"]) <= per_layer
    assert "decode_step_ms" in line["metrics"]   # no device ops on a CPU
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert line["device"]["window_s"] == pytest.approx(2.0, rel=0.2)
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_cell_added_by_files_and_entries_alone(tree, tmp_path):
    root, bench = testing.tiny_tree(tmp_path)
    (bench / "metrics" / "decode_calls.py").write_text(
        "def read(run):\n"
        "    return float(len(run.calls_in('decode', run.window)))\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "decode_calls", "unit": "calls", "better": "higher",
        "source": "host_clock", "layer": "engine: serving/engine.py",
        "moves": "tokens_per_s", "workloads": ["tiny.tiny_closed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.Cell.load(root, bench, "tiny.tiny_closed")
    assert cell.conf["hidden_size"] == 64 and cell.mix["clients"] == 8
    assert "decode_calls" in cell.reported(trace=True)
    out = harness.run(cell, 5, 1.5, trace=True, t_start=time.perf_counter(),
                      require_chip=False, trace_dir=root / ".trace")
    assert out.result["metrics"]["decode_calls"]["value"] > 0


def test_entry_point_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "olmo-1b.decode_heavy", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no peaks for device kind" in p.stderr


def test_benchmark_json_names_existing_files():
    root = BENCH.parents[1]
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        conf = json.loads((root / c["file"]).read_text())
        assert conf["name"] == c["name"]
        for key in c["reduced"]:
            assert key in conf.get("source_values", {})
    for w in spec["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
        assert (BENCH / "cells" / f"{w['name']}.json").exists()
        harness.Cell.load(root, BENCH, w["name"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
