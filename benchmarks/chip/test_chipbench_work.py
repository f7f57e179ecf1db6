"""Peaks and required work, against hand counts at the published widths
of granite-3-8b (20 layers) and olmo-1b."""

import json
from pathlib import Path

import pytest

from chipbench import work

BENCH = Path(__file__).resolve().parent


def shapes(name):
    return work.Shapes.of(json.loads(
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
    flops, nbytes = work.decode_work(s, [100, 300])
    per_row = 2 * (20 * 199_229_440 + 201_338_880)
    attn = 4 * 20 * 32 * 128 * (100 + 300)
    assert flops == 2 * per_row + attn
    assert nbytes == 8_372_191_232 + 2 * 4096 * 2 + 400 * 81_920
    # a padded row adds nothing: work is counted from the real rows
    assert work.decode_work(s, [100]) < (flops, nbytes)


def test_prefill_work_counts_only_computed_tokens():
    s = shapes("olmo-1b")
    all32, _ = work.prefill_work(s, range(32))
    per_tok = 2 * 16 * 67_108_864
    attn = sum(4 * 16 * 16 * 128 * (p + 1) for p in range(32))
    assert all32 == 32 * per_tok + attn + 2 * 2048 * 50304
    # a prefix hit on the first 16 tokens: only the last 16 are computed,
    # and the cached 16 are read
    f, b = work.prefill_work(s, range(16, 32))
    assert f == 16 * per_tok + sum(4 * 16 * 16 * 128 * (p + 1)
                                   for p in range(16, 32)) + 2 * 2048 * 50304
    assert b == 2_353_528_832 + 16 * 2048 * 2 + 32 * 131_072


def test_least_seconds_is_the_larger_bound():
    p = work.peaks("TPU v5 lite")
    assert work.least_seconds(197e12, 1, p) == pytest.approx(1.0)
    assert work.least_seconds(1, 819e9, p) == pytest.approx(1.0)
    s = shapes("granite-3-8b")
    # one decode row of granite is bound by its 8.37 GB of weights
    t = work.least_seconds(*work.decode_work(s, [512]), p)
    assert t == pytest.approx((8_372_191_232 + 8192 + 512 * 81_920) / 819e9)
