"""What every family's plain reference shares.  A family's ``logits_at``
(``families/<family>.py``) is the configuration's forward pass in
float32 with matrix products at full precision (``_mm``),
straightforward ``jax.numpy``, one layer at a time over whole
sequences.  No KV cache, no paging, no batching across requests beyond
padding.  It imports nothing of the program and reads only the
benchmark's own weights.

The controls are the same pass one precision step below the one the
configuration serves in (``CONTROL``), which a cell's limits are set to
judge not correct: for bfloat16, every weight matrix rounded per output
channel to int8 (``mode="int8"``) or to float8 e4m3 (``mode="fp8"``),
with activations in bfloat16; for float32, matrix products in bfloat16
(``mode="bf16"``).
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
CONTROL = {"bfloat16": ("int8", "fp8"), "float32": ("bf16",)}


def _mm(x, w, mode: str):
    """``x @ w`` over the last axis of ``x`` and the first of ``w``."""
    if mode == "f32":
        return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                          precision=HIGHEST)
    if mode == "bf16":
        return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    w32 = w.astype(jnp.float32)
    top = 127.0 if mode == "int8" else 448.0   # largest int8 / e4m3 value
    scale = jnp.max(jnp.abs(w32), axis=0, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    if mode == "int8":
        rounded = jnp.clip(jnp.round(w32 / scale), -127, 127)
    else:
        rounded = (w32 / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return jnp.matmul(x.astype(jnp.bfloat16),
                      (rounded * scale).astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _norm(x, gain, conf: Dict):
    if conf["norm"] == "rmsnorm":
        h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                              + conf["rms_norm_eps"])
        return h * gain.astype(jnp.float32)
    if conf["norm"] == "nonparam_ln":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + conf["layer_norm_eps"])
    raise ValueError(conf["norm"])


def _rope(x, theta: float):
    """Rotary embedding, half-split (GPT-NeoX / Hugging Face Llama)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def served_rows(prompts: Sequence[np.ndarray], served: Sequence[List[int]]
                ) -> Tuple[List[np.ndarray], List[Tuple[int, int]],
                           np.ndarray]:
    """The reference's inputs for served requests: each prompt followed
    by its served tokens but the last, the (sequence, position) that
    produced each served token, and the served tokens in row order."""
    seqs, rows, toks = [], [], []
    for b, (p, out) in enumerate(zip(prompts, served)):
        seqs.append(np.concatenate([p, np.asarray(out[:-1], np.int32)]))
        rows += [(b, len(p) - 1 + i) for i in range(len(out))]
        toks += list(out)
    return seqs, rows, np.asarray(toks, np.int32)


GAP_NAMES = ("max_logit_gap", "mean_logit_gap", "off_best_share")


def gaps(ref_logits: jax.Array, tokens: np.ndarray) -> Dict[str, float]:
    """How far the tokens' reference logits lie below the reference's
    best at their rows: the widest gap, the mean gap, and the share of
    tokens that are not the reference's best."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, jnp.asarray(tokens)[:, None],
                              axis=-1)[:, 0]
    d = best - got
    return {"max_logit_gap": float(jnp.max(d)),
            "mean_logit_gap": float(jnp.mean(d)),
            "off_best_share": float(jnp.mean(d > 0))}
