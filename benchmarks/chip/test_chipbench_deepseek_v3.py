"""The ``deepseek_v3`` family (Moonlight-16B-A3B): run through the
harness on the CPU at a tiny size as files and entries alone, its work
counts pinned to a hand count at the published sizes, its weights at
the cut, and its controls judged by the cell's limits."""

import dataclasses
import json
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import harness, testing

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
FAMILY = BENCH / "families" / "deepseek_v3.py"

# a reduced Moonlight: one dense layer and two expert layers, 8 of 16
# routed experts held, 4 a token, one shared expert
TINY_KEYS = {
    "arch": "moonlight-16b-a3b", "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "expert_first": 0, "num_experts_per_tok": 4,
    "n_shared_experts": 1, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.446,
    "rms_norm_eps": 1e-5, "rope_theta": 50000.0,
    "source_values": {"n_routed_experts": 16}}


def _tree(tmp_path, **keys):
    return testing.tiny_tree(tmp_path, families={
        "deepseek_v3": (FAMILY.read_text(), dict(TINY_KEYS, **keys))})


def _family():
    return harness.family(BENCH, "deepseek_v3")


def test_the_family_runs_through_the_harness_by_files_alone(tmp_path):
    """A tiny Moonlight served in float32 by ``harness.run``: every
    sampled token at the reference's best, each call's work the
    family's count, the pool one latent array."""
    root, bench = _tree(tmp_path)
    cell = harness.Cell.load(root, bench, "tiny_deepseek_v3.tiny_closed")
    fam = cell.family
    assert fam.__file__ == str(bench / "families" / "deepseek_v3.py")
    cfg = fam.model_config(cell.conf)
    assert (cfg.n_experts, cfg.experts_held, cfg.cache_spec()) == (
        16, 8, {"latent": (128,)})
    out = harness.run(cell, 2**33 + 17, 2.0, trace=False,
                      t_start=time.perf_counter(), require_chip=False)
    assert out.result["correct"] is True, out.result["checks"]
    assert out.notes["window_calls"]["decode"] > 0
    s = fam.Shapes.of(cell.conf)
    work = {"prefill": fam.prefill_work, "decode": fam.decode_work}
    assert {c.kind for c in out.calls} == set(work)
    for c in out.calls:
        assert (c.flops, c.nbytes) == work[c.kind](s, c.lengths)


def test_a_reference_without_the_bias_is_not_correct(tmp_path):
    """The correction bias matters to the check: a reference that
    routes without it disagrees with the program."""
    root, bench = _tree(tmp_path)
    path = bench / "families" / "deepseek_v3.py"
    path.write_text(path.read_text().replace(
        "s + bias.astype(jnp.float32)", "s + 0 * bias.astype(jnp.float32)"))
    cell = harness.Cell.load(root, bench, "tiny_deepseek_v3.tiny_closed")
    out = harness.run(cell, 2**33 + 17, 2.0, trace=False,
                      t_start=time.perf_counter(), require_chip=False)
    assert out.result["correct"] is False


@pytest.fixture(scope="module")
def published():
    conf = json.loads((BENCH / "configs" /
                       "moonlight-16b-a3b.json").read_text())
    return conf, _family().Shapes.of(conf)


def test_work_is_the_hand_count_at_published_sizes(published):
    """Multiply-adds a token, from Moonlight's published sizes: d 2048,
    16 heads, kv_lora 512, nope 128, rope 64, v 128, 27 layers of which
    1 dense (11,264 wide), 26 of 64 experts (1,408 wide, 6 a token, 8
    held here) and 2 shared; vocabulary 163,840."""
    _, s = published
    fam = _family()
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    mlp = 3 * 2048 * 11264 + 26 * (2048 * 64 + 2 * 3 * 2048 * 1408
                                   + 6 * 8 * 3 * 2048 * 1408 // 64)
    head = 2048 * 163840
    # every weight read once: 27 attention layers with their norms, the
    # dense MLP, 26 x (router, bias, 8 held + 2 shared experts), the
    # final norm and the head; 2 bytes each
    weights = 2 * (27 * (attn + 512 + 2 * 2048) + 3 * 2048 * 11264
                   + 26 * (2048 * 64 + 64 + 10 * 3 * 2048 * 1408)
                   + 2048 + head)
    assert weights == 6_058_141_952
    latent = 27 * 576 * 2            # bytes a token
    # decode, absorbed: scores over [c | k_pe] and the sum of c, a row
    ctx = [1000, 1500]
    flops = sum(2 * (27 * attn + mlp + head)
                + 2 * 27 * 16 * (512 + 64 + 512) * c for c in ctx)
    assert fam.decode_work(s, ctx) == (
        flops, weights + 2 * 2048 * 2 + 2500 * latent)
    # prefill, expanded: scores over [k_nope | k_pe] and the sum of v
    pos = range(512)
    flops = sum(2 * (27 * attn + mlp) + 2 * 27 * 16 * (192 + 128) * (p + 1)
                for p in pos) + 2 * head
    assert fam.prefill_work(s, pos) == (
        flops, weights + 512 * 2048 * 2 + 512 * latent)


def test_the_weights_at_the_cut(published):
    """3.36 B parameters (6.73 GB of bf16), in the program's layout with
    nothing copied."""
    conf, s = published
    fam = _family()
    shapes = fam.weight_shapes(s)
    assert sum(int(np.prod(v)) for v in shapes.values()) == 3_364_615_296
    from repro.models.model import build
    expected = jax.eval_shape(build(fam.model_config(conf)).init,
                              jax.random.PRNGKey(0))
    w = {k: jax.ShapeDtypeStruct(v, np.dtype("bfloat16"))
         for k, v in shapes.items()}
    tree = fam.program_params(w, s, expected)
    leaves = jax.tree_util.tree_leaves(tree)
    assert len(leaves) == len(w)
    assert {id(x) for x in leaves} == {id(x) for x in w.values()}


def test_the_fp8_control_fails_the_cells_limit(tmp_path):
    """At the tiny size served in bfloat16, the reference with its
    weights rounded to fp8 reads a widest gap above the Moonlight
    cell's limit."""
    limits = json.loads((BENCH / "cells" /
                         "moonlight-16b-a3b.decode_long.json").read_text())
    serving = dict(testing.TINY_CONF["serving"], dtype="bfloat16")
    root, bench = _tree(tmp_path, serving=serving)
    cell = harness.Cell.load(root, bench, "tiny_deepseek_v3.tiny_closed")
    cell = dataclasses.replace(cell, limits=limits)
    out = harness.run(cell, 2**33 + 19, 2.0, trace=False,
                      t_start=time.perf_counter(), require_chip=False,
                      keep_weights=True)
    gaps = harness.check(cell, out.weights, out.sample, mode="fp8")
    assert harness.judge(gaps, 0, limits)[0] is False, gaps
