"""Shared neural-net building blocks (pure JAX, param dicts, no framework)."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def pdtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# -- initialisers -------------------------------------------------------------

def dense_init(rng, shape, dtype, scale: float = 1.0):
    fan_in = shape[0] if len(shape) >= 2 else max(1, shape[0])
    std = scale / np.sqrt(fan_in)
    return (jax.random.normal(rng, shape, jnp.float32) * std).astype(dtype)


def embed_init(rng, shape, dtype):
    return (jax.random.normal(rng, shape, jnp.float32) * 0.02).astype(dtype)


# -- norms -----------------------------------------------------------------------

def rmsnorm(x, w, eps: float = 1e-6):
    h = x.astype(jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return (h * w.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, w, b, eps: float = 1e-5):
    h = x.astype(jnp.float32)
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(h - mu), axis=-1, keepdims=True)
    h = (h - mu) * jax.lax.rsqrt(var + eps)
    if w is not None:
        h = h * w.astype(jnp.float32)
    if b is not None:
        h = h + b.astype(jnp.float32)
    return h.astype(x.dtype)


def nonparam_ln(x, eps: float = 1e-5):
    """OLMo's non-parametric LayerNorm (no affine parameters)."""
    return layernorm(x, None, None, eps)


def make_norm(cfg: ModelConfig):
    if cfg.norm == "rmsnorm":
        return lambda x, p: rmsnorm(x, p["w"], cfg.norm_eps)
    if cfg.norm == "layernorm":
        return lambda x, p: layernorm(x, p["w"], p["b"])
    if cfg.norm == "nonparam_ln":
        return lambda x, p: nonparam_ln(x)
    raise ValueError(cfg.norm)


def norm_params(cfg: ModelConfig, rng) -> Dict:
    if cfg.norm == "rmsnorm":
        return {"w": jnp.ones((cfg.d_model,), pdtype_of(cfg))}
    if cfg.norm == "layernorm":
        return {"w": jnp.ones((cfg.d_model,), pdtype_of(cfg)),
                "b": jnp.zeros((cfg.d_model,), pdtype_of(cfg))}
    return {"_": jnp.zeros((1,), pdtype_of(cfg))}  # placeholder leaf


# -- rotary embeddings ----------------------------------------------------------

def rope_freqs(hd_rot: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, hd_rot, 2, dtype=np.float64) / hd_rot))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               frac: float = 1.0) -> jnp.ndarray:
    """x: (B, S, H, hd); positions: (B, S).  Rotates the first
    ``frac * hd`` dims (neox half-split style); the rest pass through
    (partial rotary, as in ChatGLM's 2d-RoPE backbone)."""
    hd = x.shape[-1]
    hd_rot = int(hd * frac)
    hd_rot -= hd_rot % 2
    if hd_rot == 0:
        return x
    freqs = jnp.asarray(rope_freqs(hd_rot, theta), jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (B,S,hd_rot/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    xr = x[..., :hd_rot].astype(jnp.float32)
    x1, x2 = jnp.split(xr, 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1)
    return jnp.concatenate([rotated.astype(x.dtype), x[..., hd_rot:]], axis=-1)


# -- attention ----------------------------------------------------------------------

def repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def _maybe_shard_scores(scores: jnp.ndarray) -> jnp.ndarray:
    """Hillclimb 1a (EXPERIMENTS.md §Perf): keep decode attention scores
    sharded along the KV-sequence axis so GSPMD computes partial softmax
    with small all-reduces instead of all-gathering the cache per layer."""
    from repro.models.opt_flags import FLAGS
    if not FLAGS.decode_shard_scores or scores.shape[2] != 1:
        return scores
    from jax.sharding import PartitionSpec as P
    try:
        return jax.lax.with_sharding_constraint(
            scores, P(None, None, None, FLAGS.decode_seq_axis))
    except (ValueError, RuntimeError):
        return scores  # no mesh context (plain CPU tests)


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              causal: bool, q_offset=0,
              kv_len: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """q: (B,Sq,H,hd), k/v: (B,Skv,Hkv,hd).  fp32 softmax.

    ``q_offset``: absolute position of q[0] (decode: cache length).
    ``kv_len``: optional valid kv length for masking a padded cache.
    """
    B, Sq, H, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    k = repeat_kv(k, H // Hkv)
    v = repeat_kv(v, H // Hkv)
    scale = 1.0 / np.sqrt(hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = _maybe_shard_scores(scores)
    mask = None  # broadcastable against (B, H, Sq, Skv)
    if causal and Sq > 1:
        qpos = jax.lax.broadcasted_iota(jnp.int32, (Sq, Skv), 0) + q_offset
        kpos = jax.lax.broadcasted_iota(jnp.int32, (Sq, Skv), 1)
        mask = (kpos <= qpos)[None, None]
    if kv_len is not None:
        kv_len = jnp.asarray(kv_len)
        kpos = jnp.arange(Skv, dtype=jnp.int32)
        if kv_len.ndim == 0:
            valid = (kpos < kv_len)[None, None, None, :]
        else:  # per-sequence lengths (B,)
            valid = (kpos[None] < kv_len[:, None])[:, None, None, :]
        mask = valid if mask is None else (mask & valid)
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attention_partial(q, k, v, kv_len=None):
    """Unnormalized attention partial for online-softmax merging:
    returns (o_un (B,Sq,H,hd) f32, m (B,H,Sq) f32, l (B,H,Sq) f32)."""
    B, Sq, H, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    k = repeat_kv(k, H // Hkv)
    v = repeat_kv(v, H // Hkv)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / np.sqrt(hd)
    s = _maybe_shard_scores(s)
    if kv_len is not None:
        kv_len = jnp.asarray(kv_len)
        kpos = jnp.arange(Skv, dtype=jnp.int32)
        if kv_len.ndim == 0:
            valid = (kpos < kv_len)[None, None, None, :]
        else:
            valid = (kpos[None] < kv_len[:, None])[:, None, None, :]
        s = jnp.where(valid, s, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o_un = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)
    return o_un.astype(jnp.float32), m, l


def attention_partial_hs(q, k_hs, v_hs, kv_len=None):
    """Like attention_partial but with head-major (B,Hkv,S,hd) K/V layout
    (no transpose on read) and grouped-query einsums (no materialized
    repeat_kv) — hillclimb 1 iterations 2+3."""
    B, Sq, H, hd = q.shape
    Hkv, Skv = k_hs.shape[1], k_hs.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Sq, Hkv, G, hd)
    s = jnp.einsum("bqhgd,bhkd->bhgqk", qg, k_hs,
                   preferred_element_type=jnp.float32) / np.sqrt(hd)
    s = s.reshape(B, H, Sq, Skv)
    s = _maybe_shard_scores(s)
    if kv_len is not None:
        kv_len = jnp.asarray(kv_len)
        kpos = jnp.arange(Skv, dtype=jnp.int32)
        if kv_len.ndim == 0:
            valid = (kpos < kv_len)[None, None, None, :]
        else:
            valid = (kpos[None] < kv_len[:, None])[:, None, None, :]
        s = jnp.where(valid, s, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    pg = p.reshape(B, Hkv, G, Sq, Skv)
    l = jnp.sum(p, axis=-1)
    o_un = jnp.einsum("bhgqk,bhkd->bqhgd", pg.astype(q.dtype), v_hs)
    return o_un.reshape(B, Sq, H, hd).astype(jnp.float32), m, l


def merge_partials(parts):
    """Merge [(o_un, m, l), ...] online-softmax partials -> (B,Sq,H,hd)."""
    m = parts[0][1]
    for _, mi, _ in parts[1:]:
        m = jnp.maximum(m, mi)
    num = 0.0
    den = 0.0
    for o_un, mi, li in parts:
        a = jnp.exp(mi - m)                       # (B,H,Sq)
        num = num + o_un * a.transpose(0, 2, 1)[..., None]
        den = den + (li * a).transpose(0, 2, 1)[..., None]
    return num / jnp.maximum(den, 1e-30)


# -- MLPs --------------------------------------------------------------------------------

def mlp_apply(cfg: ModelConfig, p: Dict, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.act == "swiglu":
        gate = jnp.einsum("...d,df->...f", x, p["w_gate"])
        up = jnp.einsum("...d,df->...f", x, p["w_up"])
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    else:  # gelu
        h = jnp.einsum("...d,df->...f", x, p["w_up"])
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("...f,fd->...d", h, p["w_down"])


def mlp_params(cfg: ModelConfig, rng, d_ff: Optional[int] = None) -> Dict:
    d_ff = d_ff or cfg.d_ff
    D, pd = cfg.d_model, pdtype_of(cfg)
    ks = jax.random.split(rng, 3)
    p = {"w_up": dense_init(ks[0], (D, d_ff), pd),
         "w_down": dense_init(ks[1], (d_ff, D), pd)}
    if cfg.act == "swiglu":
        p["w_gate"] = dense_init(ks[2], (D, d_ff), pd)
    return p


# -- attention block params -------------------------------------------------------------------

def attn_params(cfg: ModelConfig, rng) -> Dict:
    D, hd, pd = cfg.d_model, cfg.hd, pdtype_of(cfg)
    ks = jax.random.split(rng, 4)
    p = {"wq": dense_init(ks[0], (D, cfg.n_heads * hd), pd),
         "wk": dense_init(ks[1], (D, cfg.n_kv_heads * hd), pd),
         "wv": dense_init(ks[2], (D, cfg.n_kv_heads * hd), pd),
         "wo": dense_init(ks[3], (cfg.n_heads * hd, D), pd)}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), pd)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), pd)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), pd)
    return p


def qkv_proj(cfg: ModelConfig, p: Dict, x: jnp.ndarray
             ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    B, S, _ = x.shape
    hd = cfg.hd
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    return q, k, v
