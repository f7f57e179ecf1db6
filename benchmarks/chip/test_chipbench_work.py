"""Peaks and required work, against hand counts at the published widths
of granite-3-8b (20 layers) and olmo-1b; the dense family's weights and
reference, pinned."""

import hashlib
import json
from pathlib import Path

import numpy as np

import pytest

from chipbench import harness, reference, testing, weights, work

BENCH = Path(__file__).resolve().parent
dense = harness.family(BENCH, "dense")


def shapes(name):
    return dense.Shapes.of(json.loads(
        (BENCH / "configs" / f"{name}.json").read_text()))


def test_peaks_table_is_keyed_by_device_kind():
    p = work.peaks("TPU v5 lite")
    assert p["flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(KeyError):
            work.peaks(kind)


def test_granite_counts():
    s = shapes("granite-3-8b")
    # q, o: 4096 x 4096 each; k, v: 4096 x 1024 each; MLP: 3 x 4096 x 12800
    assert s.layer_matmul_params == 41_943_040 + 157_286_400
    assert s.head_params == 4096 * 49155 == 201_338_880
    # 20 layers with two RMSNorm gains each, the final gain, the head
    assert s.weight_bytes == 2 * (20 * (199_229_440 + 8192) + 4096
                                  + 201_338_880) == 8_372_191_232
    assert s.kv_bytes_per_token == 2 * 20 * 8 * 128 * 2 == 81_920


def test_olmo_counts():
    s = shapes("olmo-1b")
    assert s.layer_matmul_params == 4 * 2048 * 2048 + 3 * 2048 * 8192
    # 1.18 B parameters, no norm gains, the tied head read once
    assert s.weight_bytes == 2 * (16 * 67_108_864 + 2048 * 50304)
    assert s.weight_bytes == 2_353_528_832
    assert s.kv_bytes_per_token == 2 * 16 * 16 * 128 * 2 == 131_072


def test_decode_work_at_true_lengths():
    s = shapes("granite-3-8b")
    flops, nbytes = dense.decode_work(s, [100, 300])
    per_row = 2 * (20 * 199_229_440 + 201_338_880)
    attn = 4 * 20 * 32 * 128 * (100 + 300)
    assert flops == 2 * per_row + attn
    assert nbytes == 8_372_191_232 + 2 * 4096 * 2 + 400 * 81_920
    # a padded row adds nothing: work is counted from the real rows
    assert dense.decode_work(s, [100]) < (flops, nbytes)


def test_prefill_work_counts_only_computed_tokens():
    s = shapes("olmo-1b")
    all32, _ = dense.prefill_work(s, range(32))
    per_tok = 2 * 16 * 67_108_864
    attn = sum(4 * 16 * 16 * 128 * (p + 1) for p in range(32))
    assert all32 == 32 * per_tok + attn + 2 * 2048 * 50304
    # a prefix hit on the first 16 tokens: only the last 16 are computed,
    # and the cached 16 are read
    f, b = dense.prefill_work(s, range(16, 32))
    assert f == 16 * per_tok + sum(4 * 16 * 16 * 128 * (p + 1)
                                   for p in range(16, 32)) + 2 * 2048 * 50304
    assert b == 2_353_528_832 + 16 * 2048 * 2 + 32 * 131_072


def test_least_seconds_is_the_larger_bound():
    p = work.peaks("TPU v5 lite")
    assert work.least_seconds(197e12, 1, p) == pytest.approx(1.0)
    assert work.least_seconds(1, 819e9, p) == pytest.approx(1.0)
    s = shapes("granite-3-8b")
    # one decode row of granite is bound by its 8.37 GB of weights
    t = work.least_seconds(*dense.decode_work(s, [512]), p)
    assert t == pytest.approx((8_372_191_232 + 8192 + 512 * 81_920) / 819e9)


# Recorded from the weights and reference as they stood before the dense
# code moved into families/dense.py: the sha256 of the weights (names and
# bytes, in name order) and, for each mode, the row maxima and row sums
# of the logits at ROWS.  The second case has granite's widths and two
# layers; its vocabulary is cut to 512 because ``weights.make`` peaks at
# about 12 bytes a weight on the CPU (9.5 GB at 49,155).
PINNED = {
    "tiny": (
        testing.TINY_CONF,
        "d3d990c6fbfd624596c75f75e9ef6959f671c2c90aa729c8e3001d4b47c25746",
        {"f32": ([4.06008, 4.05835, 4.01351, 3.71374, 4.08886],
                 [-274.5865, -313.0739, -232.8591, 76.6749, -136.3337]),
         "bf16": ([4.06402, 4.04339, 4.01458, 3.71726, 4.08158],
                  [-275.8947, -312.0422, -230.5509, 78.3013, -136.5542])}),
    "granite_width_2_layers": (
        {"num_hidden_layers": 2, "vocab_size": 512},
        "cbb2f5d3a122dbb0a1497ecbc4365a2533e7fb3474bd4f1a874dfa9a60ab8dd1",
        {"f32": ([2.89159, 3.23964, 2.78604, 3.30297, 3.29019],
                 [10.7362, 28.5202, 13.4811, -36.2116, -10.4477]),
         "int8": ([2.88285, 3.23021, 2.76076, 3.30709, 3.25682],
                  [10.89, 28.9518, 12.97, -36.5972, -9.9706]),
         "fp8": ([2.90261, 3.17312, 2.85504, 3.32359, 3.36424],
                 [10.9599, 28.4017, 15.0608, -35.8513, -8.0151])}),
}
ROWS = [(0, 0), (0, 17), (0, 39), (1, 5), (1, 22)]


@pytest.mark.parametrize("case", sorted(PINNED))
def test_dense_weights_and_reference_logits_are_pinned(case):
    keys, digest, logits = PINNED[case]
    conf = json.loads((BENCH / "configs" / "granite-3-8b.json").read_text())
    conf = dict(conf, **keys)
    w = weights.make(dense.weight_shapes(dense.Shapes.of(conf)), 2**33 + 5,
                     conf["serving"]["dtype"])
    h = hashlib.sha256()
    for name, x in sorted(w.items()):
        h.update(name.encode())
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == digest
    rng = np.random.default_rng(0)
    seqs = [rng.integers(1, conf["vocab_size"], n).astype(np.int32)
            for n in (40, 23)]
    modes = ("f32",) + reference.CONTROL[conf["serving"]["dtype"]]
    assert sorted(logits) == sorted(modes)
    for mode in modes:
        lg = np.asarray(dense.logits_at(w, conf, seqs, ROWS, pad_to=48,
                                        mode=mode), np.float64)
        np.testing.assert_allclose(lg.max(-1), logits[mode][0], rtol=1e-5)
        np.testing.assert_allclose(lg.sum(-1), logits[mode][1], rtol=1e-5)
