"""Multi-head latent attention (MLA; DeepSeek-V2/V3, Moonlight).

One layer, with ``R`` = ``kv_lora_rank`` and ``P`` = ``qk_rope_head_dim``:

    q  = h Wq -> (S, H, nope + P) = [q_nope | q_pe]
    a  = h Wkv_a -> (S, R + P) = [c | k_pe]      k_pe: one per token
    c  = RMSNorm(c)
    kv = c Wkv_b -> (S, H, nope + v) = [k_nope | v]
    q_pe, k_pe rotated (half-split RoPE)
    softmax([q_nope|q_pe] . [k_nope|k_pe] / sqrt(nope + P)) . v -> Wo

What a token leaves in the cache is its latent row ``[c | k_pe]``, R + P
values a layer, padded with zeros to the pool's row width
(``ModelConfig.cache_spec``).  Prefill and training expand ``Wkv_b``
over the whole sequence; paged decode absorbs it into the query and the
output instead
(``Wkv_b`` per head is ``[W_UK | W_UV]``): ``q_lat = q_nope W_UK^T``
scores the latent rows directly, and the weighted sum of latent rows goes
through ``W_UV``.  Both compute the same attention.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models.config import ModelConfig


def mla_params(cfg: ModelConfig, rng) -> Dict:
    D, H, pd = cfg.d_model, cfg.n_heads, L.pdtype_of(cfg)
    R, P = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    ks = jax.random.split(rng, 4)
    return {"wq": L.dense_init(ks[0], (D, H * (nope + P)), pd),
            "wkv_a": L.dense_init(ks[1], (D, R + P), pd),
            "kv_norm": jnp.ones((R,), pd),
            "wkv_b": L.dense_init(ks[2], (R, H * (nope + vd)), pd),
            "wo": L.dense_init(ks[3], (H * vd, D), pd)}


def _scale(cfg: ModelConfig) -> float:
    return 1.0 / np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def project(cfg: ModelConfig, p: Dict, h: jnp.ndarray, positions
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(q_nope (B,S,H,nope), q_pe (B,S,H,P) rotated, latent (B,S,R+P)
    = [RMSNorm(c) | rotated k_pe]) of ``h`` at ``positions`` (B,S)."""
    B, S, _ = h.shape
    H, R, nope = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q = jnp.einsum("bsd,dx->bsx", h, p["wq"]).reshape(B, S, H, -1)
    q_pe = L.apply_rope(q[..., nope:], positions, cfg.rope_theta)
    a = jnp.einsum("bsd,dx->bsx", h, p["wkv_a"])
    c = L.rmsnorm(a[..., :R], p["kv_norm"], cfg.norm_eps)
    k_pe = L.apply_rope(a[..., None, R:], positions, cfg.rope_theta)[:, :, 0]
    return q[..., :nope], q_pe, jnp.concatenate([c, k_pe], axis=-1)


def pool_row(cfg: ModelConfig, latent: jnp.ndarray) -> jnp.ndarray:
    """``latent`` (..., R+P) padded with zeros to the pool's row width."""
    (width,) = cfg.cache_spec()["latent"]
    pad = [(0, 0)] * (latent.ndim - 1) + [(0, width - latent.shape[-1])]
    return jnp.pad(latent, pad)


def attend_full(cfg: ModelConfig, p: Dict, h: jnp.ndarray, positions,
                causal: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expanded attention over the whole sequence.  Returns (out
    (B,S,D), the latent rows as the pool holds them (B,S,width))."""
    B, S, _ = h.shape
    H, R, nope = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q_nope, q_pe, latent = project(cfg, p, h, positions)
    kv = jnp.einsum("bsr,rx->bsx", latent[..., :R],
                    p["wkv_b"]).reshape(B, S, H, -1)
    k_pe = jnp.broadcast_to(latent[:, :, None, R:],
                            (B, S, H, cfg.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    o = L.attention(q, k, kv[..., nope:], causal=causal)
    o = jnp.einsum("bsx,xd->bsd", o.reshape(B, S, -1), p["wo"])
    return o, pool_row(cfg, latent)


def attend_paged(cfg: ModelConfig, p: Dict, h: jnp.ndarray, positions,
                 pool: jnp.ndarray, block_tables: jnp.ndarray,
                 lengths: jnp.ndarray, slot_ids: jnp.ndarray,
                 slot_offs: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Absorbed one-token attention over latent blocks.  ``pool`` is one
    layer's (N, bs, width); the new token's latent row is written at
    (``slot_ids``, ``slot_offs``) and each row attends to its first
    ``lengths + 1`` positions.  Returns (out (B,1,D), pool)."""
    B = h.shape[0]
    H, R, nope = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q_nope, q_pe, latent = project(cfg, p, h, positions)
    pool = pool.at[slot_ids, slot_offs].set(pool_row(cfg, latent[:, 0]))
    ctx = pool[block_tables].reshape(B, -1, pool.shape[-1])   # (B, T, width)
    w_kv = p["wkv_b"].reshape(R, H, -1)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_kv[..., :nope])
    s = (jnp.einsum("bhr,btr->bht", q_lat, ctx[..., :R],
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhe,bte->bht", q_pe[:, 0],
                      ctx[..., R:R + cfg.qk_rope_head_dim],
                      preferred_element_type=jnp.float32)) * _scale(cfg)
    valid = jnp.arange(ctx.shape[1])[None, None, :] < (lengths + 1)[:, None,
                                                                     None]
    probs = jax.nn.softmax(jnp.where(valid, s, -1e30), axis=-1)
    o_lat = jnp.einsum("bht,btr->bhr", probs.astype(ctx.dtype), ctx[..., :R])
    o = jnp.einsum("bhr,rhv->bhv", o_lat, w_kv[..., nope:])
    o = jnp.einsum("bx,xd->bd", o.reshape(B, -1), p["wo"])
    return o[:, None], pool
