"""The check that decides ``correct`` fails when the timed path is
broken underneath it, and when the control (the reference one precision
step down) takes the program's place."""

import time

import pytest

from chipbench import harness, reference, testing
from repro.serving import engine as engine_mod


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return testing.tiny_tree(tmp_path_factory.mktemp("bench"))


def run(tree, keep_weights=False):
    root, bench = tree
    cell = harness.Cell.load(root, bench, "tiny.tiny_chat")
    return harness.run(cell, 2**32 + 3, 2.0, trace=False,
                       t_start=time.perf_counter(), require_chip=False,
                       keep_weights=keep_weights)


def altered_token(orig):
    """Each decoded token is replaced by its neighbour where produced."""
    def step(self, ids):
        out = orig(self, ids)
        for rid in out:
            out[rid] = (out[rid] + 1) % self.cfg.vocab
            self.mgr.seqs[rid].out_tokens[-1] = out[rid]
        return out
    return step


def unchanged_state(orig):
    """The decode step hands back the KV pool it was given."""
    def logits(self, ids):
        kp, vp = self.pool.kpool, self.pool.vpool
        out = orig(self, ids)
        self.pool.kpool, self.pool.vpool = kp, vp
        return out
    return logits


@pytest.mark.parametrize("fault,method", [
    (altered_token, "_decode_step"), (unchanged_state, "_decode_logits")])
def test_a_broken_timed_path_is_not_correct(tree, monkeypatch, fault, method):
    orig = getattr(engine_mod.ServingEngine, method)
    monkeypatch.setattr(engine_mod.ServingEngine, method, fault(orig))
    out = run(tree)
    assert out.result["correct"] is False
    assert any(c["value"] > c["limit"]
               for k, c in out.result["checks"].items()
               if k.endswith("_gap"))


@pytest.mark.parametrize("seed", [1, 3])
def test_the_control_is_not_correct(tree, seed):
    root, bench = tree
    cell = harness.Cell.load(root, bench, "tiny.tiny_closed")
    out = harness.run(cell, seed, 4.0, trace=False,
                      t_start=time.perf_counter(), require_chip=False,
                      keep_weights=True)
    assert out.result["correct"] is True
    for mode in reference.CONTROL[harness.served_dtype(cell.conf)]:
        gaps = harness.check(cell, out.weights, out.sample, mode=mode)
        assert harness.judge(gaps, 0, cell.limits)[0] is False


@pytest.mark.parametrize("gaps,correct", [
    ({"max_logit_gap": 0.04, "mean_logit_gap": 0.001}, True),
    ({"max_logit_gap": 0.04, "mean_logit_gap": 0.01}, False),
    ({"max_logit_gap": 0.3, "mean_logit_gap": 0.001}, False),
    (None, False)])
def test_every_limited_gap_decides_correct(gaps, correct):
    """A control that reads under one limit (int8's widest gap, say)
    still fails on another (its mean gap)."""
    limits = {"max_logit_gap": 0.1, "mean_logit_gap": 0.005,
              "sample_requests": 3}
    ok, checks = harness.judge(gaps, 0, limits)
    assert ok is correct
    assert set(checks) == {"max_logit_gap", "mean_logit_gap",
                           "wrong_length_answers"}
    assert harness.judge({"max_logit_gap": 0.0, "mean_logit_gap": 0.0},
                         1, limits)[0] is False


def test_control_modes_round_coarser_than_the_reference():
    import jax
    import jax.numpy as jnp

    x = jax.random.normal(jax.random.PRNGKey(0), (8, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 64)) / 16
    ref = reference._mm(x, w, "f32")
    err = {m: float(jnp.abs(reference._mm(x, w, m) - ref).max())
           for m in ("bf16", "int8", "fp8")}
    assert 0 < err["bf16"] < err["int8"] < err["fp8"]
