"""The chip's peaks, and the work a served step requires.

Required work is counted from the configuration's shapes and the true
lengths of the real rows, never from how the program moves bytes: the
padded rows of a batch, the padded positions of a prefill bucket and
copies of the KV pool are not required, so a program that stops making
them reaches a larger share of the roofline while the yardstick stays.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Tuple

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, HBM at
# 819 GB/s.  Keyed by JAX's ``device_kind``.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; an unknown device is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclasses.dataclass(frozen=True)
class Shapes:
    """The sizes required work is counted from."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    norm_params: int        # parameters of one norm (0: non-parametric)
    itemsize: int = 2       # bytes of one weight or KV element

    @classmethod
    def of(cls, conf: Dict) -> "Shapes":
        """From a configuration file's keys."""
        d = conf["hidden_size"]
        heads = conf["num_attention_heads"]
        return cls(n_layers=conf["num_hidden_layers"], d_model=d,
                   n_heads=heads, n_kv_heads=conf["num_key_value_heads"],
                   head_dim=conf.get("head_dim") or d // heads,
                   d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
                   tied=conf["tie_word_embeddings"],
                   norm_params=0 if conf["norm"] == "nonparam_ln" else d,
                   itemsize=ITEMSIZE[conf["serving"]["dtype"]])

    @property
    def layer_matmul_params(self) -> int:
        """Weights of one layer's matrix products: q, k, v, o and the
        gated MLP's three."""
        d, hd = self.d_model, self.head_dim
        attn = 2 * d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
        return attn + 3 * d * self.d_ff

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    @property
    def weight_bytes(self) -> int:
        """Every weight a step reads once: the layers, the final norm and
        the output head (the embedding table itself when tied)."""
        n = (self.n_layers * (self.layer_matmul_params + 2 * self.norm_params)
             + self.norm_params + self.head_params)
        return n * self.itemsize

    @property
    def kv_bytes_per_token(self) -> int:
        return (2 * self.n_layers * self.n_kv_heads * self.head_dim
                * self.itemsize)

    def attn_flops(self, ctx: int) -> int:
        """Scores and weighted values of one query over ``ctx`` keys."""
        return 4 * self.n_layers * self.n_heads * self.head_dim * ctx


def prefill_work(s: Shapes, positions: Iterable[int]) -> Tuple[int, int]:
    """(FLOPs, bytes) a prefill requires when the KV of the token at each
    of ``positions`` has to be computed and written, and the logits of the
    last prompt token (always among them) are needed.  The cached context
    those tokens attend to is read once."""
    pos = sorted(positions)
    flops = sum(2 * s.n_layers * s.layer_matmul_params
                + s.attn_flops(p + 1) for p in pos)
    flops += 2 * s.head_params
    ctx = pos[-1] + 1 if pos else 0
    nbytes = (s.weight_bytes + len(pos) * s.d_model * s.itemsize
              + ctx * s.kv_bytes_per_token)
    return flops, nbytes


def decode_work(s: Shapes, contexts: Iterable[int]) -> Tuple[int, int]:
    """(FLOPs, bytes) one decode step requires for real rows whose new
    token attends to ``contexts`` positions (its own included): every
    weight once, each row's context KV read once, its new KV written."""
    ctx = list(contexts)
    flops = sum(2 * (s.n_layers * s.layer_matmul_params + s.head_params)
                + s.attn_flops(c) for c in ctx)
    nbytes = (s.weight_bytes + len(ctx) * s.d_model * s.itemsize
              + sum(ctx) * s.kv_bytes_per_token)
    return flops, nbytes


def least_seconds(flops: int, nbytes: int, peak: Dict[str, float]) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peak["flops"], nbytes / peak["hbm_bytes_per_s"])
