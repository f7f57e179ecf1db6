"""Decoder-only transformer (dense / VLM / MoE) with scanned layers.

Three entry points per model family:
  * ``forward_train``  — full-sequence causal forward, returns logits.
  * ``forward_prefill``— like train but also returns the KV cache.
  * ``forward_decode`` — one token with a KV cache (write-at-position).

KV cache layout: k/v as (L, B, S_max, H_kv, hd); sharded (None, "data",
"model", None, None) at scale so a 32k/500k cache divides across the pod
without replicating GQA heads (DESIGN.md §6).
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models.config import ModelConfig


class KVCache(NamedTuple):
    k: jnp.ndarray          # (L, B, S_max, H_kv, hd)
    v: jnp.ndarray
    length: jnp.ndarray     # scalar int32: #valid positions


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  n_layers: Optional[int] = None) -> KVCache:
    nl = cfg.n_layers if n_layers is None else n_layers
    shape = (nl, batch, max_len, cfg.n_kv_heads, cfg.hd)
    z = jnp.zeros(shape, L.dtype_of(cfg))
    return KVCache(z, z, jnp.int32(0))


# -- per-block params -----------------------------------------------------------

def block_params(cfg: ModelConfig, rng) -> Dict:
    ks = jax.random.split(rng, 4)
    p = {"ln1": L.norm_params(cfg, ks[0]),
         "attn": L.attn_params(cfg, ks[1]),
         "ln2": L.norm_params(cfg, ks[2])}
    if cfg.family == "moe":
        p["moe"] = moe_lib.moe_params(cfg, ks[3])
    else:
        p["mlp"] = L.mlp_params(cfg, ks[3])
    return p


def stacked_block_params(cfg: ModelConfig, rng) -> Dict:
    """Per-layer params stacked on a leading layer axis.  ``lax.map``
    (not ``vmap``) draws one layer at a time, so the float32 draws that
    ``dense_init`` casts down never exist for all layers at once."""
    rngs = jax.random.split(rng, cfg.n_layers)
    return jax.lax.map(lambda r: block_params(cfg, r), rngs)


# -- block application -------------------------------------------------------------

def _mix(cfg: ModelConfig, p: Dict, x: jnp.ndarray,
         decode: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The channel-mixing half (MLP or MoE). Returns (out, aux_loss)."""
    if cfg.family == "moe":
        return moe_lib.moe_apply(cfg, p["moe"], x, decode=decode)
    return L.mlp_apply(cfg, p["mlp"], x), jnp.float32(0.0)


def block_full(cfg: ModelConfig, p: Dict, x: jnp.ndarray,
               positions: jnp.ndarray, causal: bool = True):
    """Full-sequence block. Returns (x, (k, v), aux)."""
    norm = L.make_norm(cfg)
    h = norm(x, p["ln1"])
    q, k, v = L.qkv_proj(cfg, p["attn"], h)
    q = L.apply_rope(q, positions, cfg.rope_theta, cfg.rope_frac)
    k = L.apply_rope(k, positions, cfg.rope_theta, cfg.rope_frac)
    o = L.attention(q, k, v, causal=causal)
    o = jnp.einsum("bqx,xd->bqd", o.reshape(*o.shape[:2], -1), p["attn"]["wo"])
    x = x + o
    h = norm(x, p["ln2"])
    m, aux = _mix(cfg, p, h)
    return x + m, (k, v), aux


def block_decode(cfg: ModelConfig, p: Dict, x: jnp.ndarray,
                 pos: jnp.ndarray, kc: jnp.ndarray, vc: jnp.ndarray):
    """One-token block; kc/vc: (B, S_max, H_kv, hd); pos: scalar cache len."""
    norm = L.make_norm(cfg)
    B = x.shape[0]
    h = norm(x, p["ln1"])
    q, k, v = L.qkv_proj(cfg, p["attn"], h)
    posb = jnp.full((B, 1), pos, jnp.int32)
    q = L.apply_rope(q, posb, cfg.rope_theta, cfg.rope_frac)
    k = L.apply_rope(k, posb, cfg.rope_theta, cfg.rope_frac)
    kc = jax.lax.dynamic_update_slice(kc, k, (0, pos, 0, 0))
    vc = jax.lax.dynamic_update_slice(vc, v, (0, pos, 0, 0))
    o = L.attention(q, kc, vc, causal=False, kv_len=pos + 1)
    o = jnp.einsum("bqx,xd->bqd", o.reshape(B, 1, -1), p["attn"]["wo"])
    x = x + o
    h = norm(x, p["ln2"])
    m, _ = _mix(cfg, p, h, decode=True)
    return x + m, kc, vc


# -- embedding / head -----------------------------------------------------------------

def embed_params(cfg: ModelConfig, rng) -> Dict:
    ks = jax.random.split(rng, 3)
    p = {"tok": L.embed_init(ks[0], (cfg.vocab, cfg.d_model), L.pdtype_of(cfg)),
         "final_norm": L.norm_params(cfg, ks[1])}
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(ks[2], (cfg.d_model, cfg.vocab),
                                    L.pdtype_of(cfg))
    if cfg.frontend == "patch_stub":
        p["mm_proj"] = L.dense_init(ks[2], (cfg.d_model, cfg.d_model),
                                    L.pdtype_of(cfg))
    return p


def embed_tokens(cfg: ModelConfig, p: Dict, tokens: jnp.ndarray) -> jnp.ndarray:
    return p["tok"][tokens].astype(L.dtype_of(cfg))


def lm_logits(cfg: ModelConfig, p: Dict, x: jnp.ndarray) -> jnp.ndarray:
    norm = L.make_norm(cfg)
    x = norm(x, p["final_norm"])
    head = p["tok"].T if cfg.tie_embeddings else p["lm_head"]
    return jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))


def embed_inputs(cfg: ModelConfig, p: Dict, batch: Dict) -> jnp.ndarray:
    """Token embedding, with stub-frontend embeddings prepended for VLM
    (precomputed patch embeddings through a learned projector)."""
    x = embed_tokens(cfg, p, batch["tokens"])
    if cfg.frontend == "patch_stub" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(L.dtype_of(cfg))
        pe = jnp.einsum("bpd,de->bpe", pe, p["mm_proj"])
        x = jnp.concatenate([pe, x], axis=1)
    return x


# -- model params ------------------------------------------------------------------------

def init_params(cfg: ModelConfig, rng) -> Dict:
    k1, k2 = jax.random.split(rng)
    return {"embed": embed_params(cfg, k1),
            "blocks": stacked_block_params(cfg, k2)}


# -- forward passes ----------------------------------------------------------------------

def forward_train(cfg: ModelConfig, params: Dict, batch: Dict,
                  remat: bool = True):
    """Returns (logits, aux_loss)."""
    x = embed_inputs(cfg, params["embed"], batch)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def body(carry, p):
        x, aux = carry
        x, _, a = block_full(cfg, p, x, positions)
        return (x, aux + a), None

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), params["blocks"])
    return lm_logits(cfg, params["embed"], x), aux


def forward_prefill(cfg: ModelConfig, params: Dict, batch: Dict,
                    max_len: Optional[int] = None,
                    full_logits: bool = False):
    """Returns (logits, KVCache); logits cover the last position only
    unless ``full_logits`` (used by the serving engine's length-bucketed
    prefill, where the "last real token" is not the last position)."""
    x = embed_inputs(cfg, params["embed"], batch)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    max_len = max_len or S

    def body(x, p):
        x, (k, v), _ = block_full(cfg, p, x, positions)
        if max_len > S:
            pad = [(0, 0), (0, max_len - S), (0, 0), (0, 0)]
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        return x, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
    logits = lm_logits(cfg, params["embed"],
                       x if full_logits else x[:, -1:, :])
    return logits, KVCache(ks, vs, jnp.int32(S))


def forward_decode_paged(cfg: ModelConfig, params: Dict, tokens: jnp.ndarray,
                         kpool: jnp.ndarray, vpool: jnp.ndarray,
                         block_tables: jnp.ndarray, lengths: jnp.ndarray,
                         slot_ids: jnp.ndarray, slot_offs: jnp.ndarray):
    """Paged decode: gather K/V through block tables (vLLM-style).

    kpool/vpool: (L, N, bs, H_kv, hd); block_tables: (B, nb);
    lengths: (B,) current context length; slot_ids/slot_offs: (B,) where
    this step's k/v are written in the pool.  Returns (logits, kpool,
    vpool).  The jnp gather here is the reference semantics of the
    kernels/paged_attention Pallas kernel.
    """
    x = embed_tokens(cfg, params["embed"], tokens)
    B = tokens.shape[0]
    bs = kpool.shape[2]
    norm = L.make_norm(cfg)
    posb = lengths[:, None].astype(jnp.int32)  # (B,1) rope positions

    def body(x, inp):
        p, kp, vp = inp
        h = norm(x, p["ln1"])
        q, k, v = L.qkv_proj(cfg, p["attn"], h)
        q = L.apply_rope(q, posb, cfg.rope_theta, cfg.rope_frac)
        k = L.apply_rope(k, posb, cfg.rope_theta, cfg.rope_frac)
        # write this token's k/v into its pool slot
        kp = kp.at[slot_ids, slot_offs].set(k[:, 0])
        vp = vp.at[slot_ids, slot_offs].set(v[:, 0])
        # gather the sequence's blocks: (B, nb, bs, H, hd) -> (B, S', H, hd)
        kc = kp[block_tables].reshape(B, -1, kp.shape[-2], kp.shape[-1])
        vc = vp[block_tables].reshape(B, -1, vp.shape[-2], vp.shape[-1])
        o = L.attention(q, kc, vc, causal=False, kv_len=lengths + 1)
        o = jnp.einsum("bqx,xd->bqd", o.reshape(B, 1, -1), p["attn"]["wo"])
        x = x + o
        h = norm(x, p["ln2"])
        m, _ = _mix(cfg, p, h, decode=True)
        return x + m, (kp, vp)

    x, (kpool, vpool) = jax.lax.scan(body, x, (params["blocks"], kpool, vpool))
    logits = lm_logits(cfg, params["embed"], x)
    return logits, kpool, vpool


class BufferedKVCache(NamedTuple):
    """Hillclimb 1b/2/3: frozen S-sharded base (head-major layout: no
    transpose on read, grouped-query einsum: no materialized repeat_kv) +
    small replicated append ring.

    Per-step writes hit only the ring (cheap replicated DUS); the sharded
    base is touched by the amortized ``commit_buffer`` every R steps —
    eliminating the per-layer full-shard select/convert that a sharded
    one-token DUS lowers to."""
    k: jnp.ndarray        # (L, B, H_kv, S_max, hd)  -- sharded base
    v: jnp.ndarray
    bk: jnp.ndarray       # (L, B, R, H_kv, hd)      -- replicated ring
    bv: jnp.ndarray
    base_len: jnp.ndarray  # valid positions in base
    buf_len: jnp.ndarray   # valid positions in ring


def init_buffered_cache(cfg: ModelConfig, batch: int, max_len: int,
                        buf_len: int = 256) -> BufferedKVCache:
    dt = L.dtype_of(cfg)
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, max_len, cfg.hd)
    bshape = (cfg.n_layers, batch, buf_len, cfg.n_kv_heads, cfg.hd)
    z = jnp.zeros(shape, dt)
    bz = jnp.zeros(bshape, dt)
    return BufferedKVCache(z, z, bz, bz, jnp.int32(0), jnp.int32(0))


def forward_decode_buffered(cfg: ModelConfig, params: Dict,
                            tokens: jnp.ndarray, cache: BufferedKVCache):
    """One decode token against base+ring (online-softmax merge)."""
    x = embed_tokens(cfg, params["embed"], tokens)
    B = tokens.shape[0]
    pos = cache.base_len + cache.buf_len
    norm = L.make_norm(cfg)

    def body(x, inp):
        p, kc, vc, bk, bv = inp
        h = norm(x, p["ln1"])
        q, k, v = L.qkv_proj(cfg, p["attn"], h)
        posb = jnp.full((B, 1), pos, jnp.int32)
        q = L.apply_rope(q, posb, cfg.rope_theta, cfg.rope_frac)
        k = L.apply_rope(k, posb, cfg.rope_theta, cfg.rope_frac)
        bk = jax.lax.dynamic_update_slice(bk, k, (0, cache.buf_len, 0, 0))
        bv = jax.lax.dynamic_update_slice(bv, v, (0, cache.buf_len, 0, 0))
        p_base = L.attention_partial_hs(q, kc, vc, kv_len=cache.base_len)
        p_buf = L.attention_partial(q, bk, bv, kv_len=cache.buf_len + 1)
        o = L.merge_partials([p_base, p_buf]).astype(x.dtype)
        o = jnp.einsum("bqx,xd->bqd", o.reshape(B, 1, -1), p["attn"]["wo"])
        x = x + o
        h = norm(x, p["ln2"])
        m, _ = _mix(cfg, p, h, decode=True)
        return x + m, (bk, bv)

    x, (bks, bvs) = jax.lax.scan(
        body, x, (params["blocks"], cache.k, cache.v, cache.bk, cache.bv))
    logits = lm_logits(cfg, params["embed"], x)
    return logits, cache._replace(bk=bks, bv=bvs,
                                  buf_len=cache.buf_len + 1)


def commit_buffer(cfg: ModelConfig, cache: BufferedKVCache) -> BufferedKVCache:
    """Amortized ring->base flush (run every R steps); the ring is
    transposed into the base's head-major layout here, once per R steps."""
    bk = cache.bk.transpose(0, 1, 3, 2, 4)  # (L,B,R,H,hd)->(L,B,H,R,hd)
    bv = cache.bv.transpose(0, 1, 3, 2, 4)
    k = jax.lax.dynamic_update_slice(
        cache.k, bk, (0, 0, 0, cache.base_len, 0))
    v = jax.lax.dynamic_update_slice(
        cache.v, bv, (0, 0, 0, cache.base_len, 0))
    return cache._replace(k=k, v=v,
                          base_len=cache.base_len + cache.bk.shape[2],
                          buf_len=jnp.int32(0))


def forward_decode(cfg: ModelConfig, params: Dict, tokens: jnp.ndarray,
                   cache: KVCache):
    """tokens: (B, 1). Returns (logits (B,1,V), updated cache)."""
    x = embed_tokens(cfg, params["embed"], tokens)
    pos = cache.length

    def body(x, inp):
        p, kc, vc = inp
        x, kc, vc = block_decode(cfg, p, x, pos, kc, vc)
        return x, (kc, vc)

    x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], cache.k, cache.v))
    logits = lm_logits(cfg, params["embed"], x)
    return logits, KVCache(ks, vs, pos + 1)
