"""The one traffic generator.  Every mix is a JSON file of parameters that
``generate`` reads; a new mix is a new file, never new code.

A mix is a closed loop: ``clients`` clients each send their next request
once the previous one has completed, at once with unshared random
prompts, or, where the mix has ``turns``, after a think time, as the next
turn of the client's chat session.

Every size, gap and choice is drawn as a fixed grid of quantiles of its
distribution, put in an order drawn from the mix's own ``order_seed``, in
which every run of ``STRATUM`` draws covers the distribution.  The run's
seed draws only the token ids.  So every seed offers the same requests of
the same sizes in the same order: the seed changes the tokens (and, in
the harness, the weights), never how much work there is or which request
waits behind which.

Prompt lengths land on the mix's ``buckets``: a prompt is lengthened so
that padded to the block size it is one of them.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

STRATUM = 8     # draws per run of the order that cover the distribution


@dataclasses.dataclass
class Req:
    """One request as the harness offers it."""

    rid: int
    prompt: np.ndarray     # int32 token ids
    max_new: int
    think_s: float = 0.0   # wait after the client's previous request ended
    fill: bool = False     # history that set-up prefills into the pool
    session: int = -1      # the chat session it is a turn of


def spread(values: np.ndarray, rng: np.random.Generator,
           k: int = STRATUM) -> np.ndarray:
    """``values`` in an order drawn from ``rng`` in which every run of
    ``k`` consecutive entries holds one value from each of ``k`` strata
    of the sorted values, so that sums over a stretch of the order vary
    little."""
    v = np.sort(values)
    groups = -(-len(v) // k)
    # stratum i runs forward for even i and backward for odd i, so a
    # group's large draw in one stratum meets a small one in the next
    idx = np.arange(groups * k).reshape(k, groups)
    idx[1::2] = idx[1::2, ::-1]
    parts = [rng.permutation(v[[i for i in idx[:, g] if i < len(v)]])
             for g in range(groups)]
    return np.concatenate([parts[g] for g in rng.permutation(groups)]) \
        if parts else v


def quantiles(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` draws of ``spec``'s distribution: the quantiles at
    (i + 0.5) / n, in an order from ``spread``."""
    if n <= 0:
        return np.zeros(0)
    p = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "uniform":
        lo, hi = spec["min"], spec["max"]
        v = np.floor(lo + p * (hi - lo + 1))
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in p])
        v = np.round(np.exp(math.log(spec["median"]) + spec["sigma"] * z))
    elif kind == "exponential":
        v = -spec["mean"] * np.log1p(-p)
    elif kind == "geometric":
        q = 1.0 / spec["mean"]
        v = np.maximum(1, np.ceil(np.log1p(-p) / math.log1p(-q))) \
            if q < 1 else np.ones(n)
    elif kind == "const":
        v = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in spec:
        v = np.maximum(v, spec["min"])
    if "max" in spec:
        v = np.minimum(v, spec["max"])
    return spread(v, rng)


def zipf_choices(n_items: int, s: float, n: int,
                 rng: np.random.Generator) -> np.ndarray:
    """``n`` choices among ``n_items`` with Zipf(s) shares, each item
    taken its quantile share of times, shuffled."""
    w = 1.0 / np.arange(1, n_items + 1) ** s
    cdf = np.cumsum(w / w.sum())
    p = (np.arange(n) + 0.5) / n
    return spread(np.searchsorted(cdf, p), rng)


def bucketed(length: int, buckets: List[int], tail: int) -> int:
    """The prompt length at or above ``length`` that pads to a bucket,
    ``tail`` (0..block-1) tokens short of it; 0 if no bucket fits."""
    for b in buckets:
        if b >= length:
            return max(length, b - tail)
    return 0


def _tokens(rng: np.random.Generator, vocab: int, n: int) -> np.ndarray:
    return rng.integers(1, vocab, int(n), dtype=np.int32)


class _Chat:
    """Chat turns built from the mix's draws, taken in order.  A session
    is [system prompt, history, turns left]; a turn's prompt is the system
    prompt, the history (earlier user messages and drawn answers, so the
    schedule does not depend on model output), an optional one-off
    document (never kept in the history) and the new message.  Sizes and
    choices come from ``order``, token ids from ``tok``."""

    def __init__(self, mix: Dict, vocab: int, order: np.random.Generator,
                 tok: np.random.Generator, block: int, m: int):
        self.mix, self.vocab, self.tok = mix, vocab, tok
        self.turns = quantiles(mix["turns"], m, order).astype(int)
        self.think = quantiles(mix["think_s"], m, order)
        self.msg = quantiles(mix["message"], m, order).astype(int)
        self.ans = quantiles(mix["answer"], m, order).astype(int)
        doc = mix["document"]
        self.has_doc = spread(np.arange(m) >= m - round(doc["share"] * m),
                              order)
        self.doc_len = quantiles(doc["tokens"], m, order).astype(int)
        self.tails = quantiles({"dist": "uniform", "min": 0, "max": block - 1},
                               m, order).astype(int)
        sp = mix["system_prompts"]
        self.choice = zipf_choices(sp["count"], sp["zipf_s"], m, order)
        self.systems = [_tokens(tok, vocab, sp["tokens"])
                        for _ in range(sp["count"])]
        self.j = 0
        self.n_sessions = 0

    def session(self) -> List:
        k = self.n_sessions
        self.n_sessions += 1
        return [self.systems[self.choice[k]], [], self.turns[k], k]

    def turn(self, sess: List):
        """(prompt, max_new, think time before it), or None where the turn
        would not fit: the session then ends."""
        j, tok = self.j, self.tok
        self.j += 1
        system, history, left, _ = sess
        doc = _tokens(tok, self.vocab, self.doc_len[j]) if self.has_doc[j] \
            else np.zeros(0, np.int32)
        head = np.concatenate([system, *history, doc])
        length = bucketed(len(head) + self.msg[j], self.mix["buckets"],
                          self.tails[j])
        if not length or length + self.ans[j] > self.mix["max_tokens"]:
            return None
        user = _tokens(tok, self.vocab, length - len(head))
        history += [user, _tokens(tok, self.vocab, self.ans[j])]
        sess[2] = left - 1
        return (np.concatenate([head, user]), int(self.ans[j]),
                float(self.think[j]))


def generate(mix: Dict, *, vocab: int, seed: int,
             block: int) -> List[List[Req]]:
    """Each client's requests of one run of ``mix`` for ``seed``, in the
    order it sends them.  With ``turns`` in the mix a client runs chat
    sessions back to back, each request waiting its think time after the
    one before, and its first ``fill_turns`` requests are history that
    set-up prefills.  Without, prompts are unshared random tokens sent at
    once."""
    order = np.random.default_rng(mix["order_seed"])
    tok = np.random.default_rng(seed)
    n_c, per = mix["clients"], mix["requests_per_client"]
    n = n_c * per
    clients: List[List[Req]] = [[] for _ in range(n_c)]
    if "turns" in mix:
        chat = _Chat(mix, vocab, order, tok, block, 2 * n)
        current: List = [None] * n_c
        for i in range(n):
            c = i % n_c
            while True:
                if current[c] is None or current[c][2] <= 0:
                    current[c] = chat.session()
                t = chat.turn(current[c])
                if t is not None:
                    break
                current[c] = None
            prompt, max_new, think = t
            clients[c].append(Req(i, prompt, max_new, think_s=think,
                                  fill=len(clients[c]) < mix["fill_turns"],
                                  session=current[c][3]))
        return clients
    plen = quantiles(mix["prompt"], n, order).astype(int)
    ans = quantiles(mix["answer"], n, order).astype(int)
    tails = quantiles({"dist": "uniform", "min": 0, "max": block - 1},
                      n, order).astype(int)
    for i in range(n):
        length = bucketed(int(plen[i]), mix["buckets"], int(tails[i]))
        if not length or length + ans[i] > mix["max_tokens"]:
            raise ValueError(f"mix draws a prompt of {plen[i]} tokens that "
                             "no bucket holds")
        clients[i % n_c].append(Req(i, _tokens(tok, vocab, length),
                                    int(ans[i])))
    return clients
