"""The traffic generator: deterministic per seed, the same sizes in the
same order for every seed, and within each mix's stated parameters."""

import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from chipbench import traffic

BENCH = Path(__file__).resolve().parent
BLOCK = 16


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def gen(name, seed):
    return traffic.generate(mix(name), vocab=49155, seed=seed, block=BLOCK)


def padded(n):
    return -(-n // BLOCK) * BLOCK


def flat(clients):
    return sorted((r for c in clients for r in c), key=lambda r: r.rid)


def sizes(r):
    return (r.rid, len(r.prompt), r.max_new, r.think_s, r.fill, r.session)


@pytest.mark.parametrize("name", ["sessions", "decode_heavy"])
def test_same_seed_same_requests(name):
    a, b = flat(gen(name, 2**33 + 5)), flat(gen(name, 2**33 + 5))
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert sizes(x) == sizes(y)


@pytest.mark.parametrize("name", ["sessions", "decode_heavy"])
def test_closed_loop_seeds_share_sizes_in_another_order(name):
    """Seeds share every request's sizes, think time and place in the
    order (drawn from the mix's ``order_seed``); only the token ids
    differ.  Another ``order_seed`` draws another order (of the same
    sizes, where no chat turn is cut short by the token cap)."""
    a, b = flat(gen(name, 11)), flat(gen(name, 2**40 + 12))
    assert [sizes(r) for r in a] == [sizes(r) for r in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    other = flat(traffic.generate(dict(mix(name), order_seed=7), vocab=49155,
                                  seed=11, block=BLOCK))
    assert [r.max_new for r in other] != [r.max_new for r in a]
    if name == "decode_heavy":
        assert Counter(r.max_new for r in other) == \
            Counter(r.max_new for r in a)


def test_sessions_keep_to_the_mix():
    m = mix("sessions")
    clients = gen("sessions", 7)
    reqs = flat(clients)
    assert len(clients) == m["clients"]
    assert {padded(len(r.prompt)) for r in reqs} <= set(m["buckets"])
    assert len(m["buckets"]) <= 6
    assert all(len(r.prompt) + r.max_new <= m["max_tokens"] for r in reqs)
    assert all(m["answer"]["min"] <= r.max_new <= m["answer"]["max"]
               for r in reqs)
    assert all(r.think_s > 0 for r in reqs)
    for c in clients:
        assert [r.fill for r in c[:m["fill_turns"] + 1]] == \
            [True] * m["fill_turns"] + [False]
    # a session's turns share its system prompt, and a turn without a
    # document extends the prompt of the turn before it by its history
    by_session = {}
    for r in reqs:
        by_session.setdefault(r.session, []).append(r)
    extended = 0
    for turns in by_session.values():
        for a, b in zip(turns, turns[1:]):
            assert np.array_equal(a.prompt[:256], b.prompt[:256])
            extended += np.array_equal(b.prompt[:len(a.prompt)], a.prompt)
    assert extended > 0
    # system prompts are shared: the 256-token heads come from 8 prompts
    heads = Counter(r.prompt[:m["system_prompts"]["tokens"]].tobytes()
                    for r in reqs)
    assert len(heads) == m["system_prompts"]["count"]


def test_documents_are_about_a_quarter_of_first_turns():
    m = mix("sessions")
    reqs = flat(gen("sessions", 9))
    first = {}
    for r in reqs:
        first.setdefault(r.session, r)
    sys_len = m["system_prompts"]["tokens"]
    long_first = sum(len(r.prompt) - sys_len > m["message"]["max"]
                     for r in first.values())
    assert 0.1 < long_first / len(first) < 0.4


def test_decode_heavy_keeps_to_the_mix():
    m = mix("decode_heavy")
    clients = gen("decode_heavy", 3)
    reqs = flat(clients)
    assert len(clients) == m["clients"]
    assert len(m["buckets"]) <= 4
    assert {padded(len(r.prompt)) for r in reqs} <= set(m["buckets"])
    assert all(m["answer"]["min"] <= r.max_new <= m["answer"]["max"]
               for r in reqs)
    assert all(len(r.prompt) >= m["prompt"]["min"] for r in reqs)
    # no two prompts share a first block
    firsts = Counter(r.prompt[:BLOCK].tobytes() for r in reqs)
    assert max(firsts.values()) == 1


def test_quantiles_are_the_distribution_shuffled():
    rng = np.random.default_rng(0)
    v = traffic.quantiles({"dist": "uniform", "min": 1, "max": 4}, 8, rng)
    assert sorted(v.tolist()) == [1, 1, 2, 2, 3, 3, 4, 4]
    e = traffic.quantiles({"dist": "exponential", "mean": 2.0}, 4000, rng)
    assert abs(e.mean() - 2.0) < 0.05
    g = traffic.quantiles({"dist": "geometric", "mean": 4}, 4000, rng)
    assert abs(g.mean() - 4.0) < 0.1 and g.min() == 1
    ln = traffic.quantiles({"dist": "lognormal", "median": 48, "sigma": 0.8,
                            "min": 16, "max": 256}, 1001, rng)
    assert np.median(ln) == 48 and ln.min() >= 16 and ln.max() <= 256


def test_bucketed_pads_to_a_bucket():
    for length in range(1, 193):
        for tail in range(BLOCK):
            n = traffic.bucketed(length, [64, 128, 192], tail)
            assert n >= length and padded(n) in (64, 128, 192)
    assert traffic.bucketed(300, [64, 128], 0) == 0
