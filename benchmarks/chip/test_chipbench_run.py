"""Whole runs of the harness at a size the CPU holds: the result line's
schema, a cell and an architecture added by files and entries alone, and
the entry point's refusal of a device it has no peaks for."""

import json
import os
import re
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


# An architecture the benchmark has no family for: dense GQA with biases
# on q, k and v, which the program runs through ``qkv_bias`` and
# families/dense.py refuses.  It builds on the dense family and counts
# the biases' adds and bytes as work of its own.
QKV_BIAS_FAMILY = '''
"""Dense GQA with biases on q, k and v."""

import dataclasses
import functools
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, weights
from chipbench.reference import HIGHEST, _mm, _norm, _rope

dense = harness.family(Path(__file__).resolve().parents[1], "dense")
BIASES = ("bq", "bk", "bv")


def model_config(conf):
    if not conf["attention_bias"]:
        raise ValueError(f"{conf['name']} states no q/k/v biases")
    cfg = dense.model_config(dict(conf, attention_bias=False))
    return dataclasses.replace(cfg, qkv_bias=True)


class Shapes(dense.Shapes):
    @property
    def bias_params(self):
        """One layer's biases."""
        return (self.n_heads + 2 * self.n_kv_heads) * self.head_dim

    @property
    def weight_bytes(self):
        return (super().weight_bytes
                + self.n_layers * self.bias_params * self.itemsize)


def prefill_work(s, positions):
    flops, nbytes = dense.prefill_work(s, positions)
    return flops + len(positions) * s.n_layers * s.bias_params, nbytes


def decode_work(s, contexts):
    flops, nbytes = dense.decode_work(s, contexts)
    return flops + len(contexts) * s.n_layers * s.bias_params, nbytes


def weight_shapes(s):
    q, kv = s.n_heads * s.head_dim, s.n_kv_heads * s.head_dim
    return dict(dense.weight_shapes(s), bq=(s.n_layers, q),
                bk=(s.n_layers, kv), bv=(s.n_layers, kv))


def program_params(w, s, expected):
    attn = expected["blocks"]["attn"]
    unbiased = dict(expected, blocks=dict(expected["blocks"], attn={
        k: v for k, v in attn.items() if k not in BIASES}))
    tree = dense.program_params(w, s, unbiased)
    tree["blocks"]["attn"].update({k: w[k] for k in BIASES})
    return weights.checked(tree, expected)


def _layer(x, lw, conf, mode):
    B, S, D = x.shape
    H, Hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = D // H
    h = _norm(x, lw["attn_norm"], conf)
    q = (_mm(h, lw["wq"], mode) + lw["bq"]).reshape(B, S, H, hd)
    k = (_mm(h, lw["wk"], mode) + lw["bk"]).reshape(B, S, Hkv, hd)
    v = (_mm(h, lw["wv"], mode) + lw["bv"]).reshape(B, S, Hkv, hd)
    q, k = _rope(q, conf["rope_theta"]), _rope(k, conf["rope_theta"])
    k, v = jnp.repeat(k, H // Hkv, axis=2), jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
    s = jnp.where(np.tril(np.ones((S, S), bool)),
                  s * conf["attention_multiplier"], -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST)
    x = x + _mm(o.reshape(B, S, H * hd), lw["wo"], mode)
    h = _norm(x, lw["mlp_norm"], conf)
    m = jax.nn.silu(_mm(h, lw["w_gate"], mode)) * _mm(h, lw["w_up"], mode)
    return x + _mm(m, lw["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("conf_items", "mode"))
def _layer_jit(x, lw, conf_items, mode):
    return _layer(x, lw, dict(conf_items), mode)


def logits_at(w, conf, seqs, rows, *, pad_to, mode="f32"):
    toks = np.zeros((len(seqs), pad_to), np.int32)
    for i, seq in enumerate(seqs):
        toks[i, :len(seq)] = seq
    items = dense._conf_items(conf)
    x = jnp.take(w["embed"], jnp.asarray(toks), axis=0).astype(jnp.float32)
    for layer in range(conf["num_hidden_layers"]):
        keys = dense._LAYER_KEYS + BIASES
        x = _layer_jit(x, {k: w[k][layer] for k in keys}, items, mode)
    rb = jnp.asarray([r[0] for r in rows], jnp.int32)
    rs = jnp.asarray([r[1] for r in rows], jnp.int32)
    hw = {k: w[k] for k in ("final_norm", "head", "embed") if k in w}
    return dense._head_jit(x, rb, rs, hw, items, mode)
'''


@pytest.mark.parametrize("family_file", [True, False],
                         ids=["written", "missing"])
def test_an_architecture_added_by_files_alone(tmp_path, family_file):
    root, bench = testing.tiny_tree(tmp_path, families={
        "qkv_bias": (QKV_BIAS_FAMILY, {"attention_bias": True})})
    path = bench / "families" / "qkv_bias.py"
    workload = "tiny_qkv_bias.tiny_closed"
    if not family_file:
        path.unlink()
        with pytest.raises(FileNotFoundError, match=re.escape(str(path))):
            harness.Cell.load(root, bench, workload)
        return
    cell = harness.Cell.load(root, bench, workload)
    fam, dense = cell.family, harness.family(bench, "dense")
    assert fam.__file__ == str(path)
    with pytest.raises(ValueError, match="no q/k/v biases"):
        dense.model_config(cell.conf)
    out = harness.run(cell, 2**33 + 11, 2.0, trace=False,
                      t_start=time.perf_counter(), require_chip=False)
    assert out.result["correct"] is True, out.result["checks"]
    assert out.notes["window_calls"]["decode"] > 0
    s, ds = fam.Shapes.of(cell.conf), dense.Shapes.of(cell.conf)
    counts = {"prefill": (fam.prefill_work, dense.prefill_work),
              "decode": (fam.decode_work, dense.decode_work)}
    assert {c.kind for c in out.calls} == set(counts)
    for c in out.calls:
        own, dense_work = counts[c.kind]
        assert (c.flops, c.nbytes) == own(s, c.lengths)
        assert c.flops > dense_work(ds, c.lengths)[0]


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
