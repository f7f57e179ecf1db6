"""Decoder-only transformer (dense / VLM / MoE) with scanned layers.

Three entry points per model family:
  * ``forward_train``  — full-sequence causal forward, returns logits.
  * ``forward_prefill``— like train but also returns the KV cache.
  * ``forward_decode`` — one token with a KV cache (write-at-position).

KV cache layout: k/v as (L, B, S_max, H_kv, hd); sharded (None, "data",
"model", None, None) at scale so a 32k/500k cache divides across the pod
without replicating GQA heads (DESIGN.md §6).  A model with latent
attention (``kv_lora_rank``, ``models/mla.py``) caches one latent row a
token a layer instead, and decodes only through the paged pool.

A model with leading dense layers (``n_dense_layers``) keeps attention
and norms in one stack over every layer (``blocks``) and its MLPs in two,
``dense`` and ``moe``; the layer scan picks each layer's from its index.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import mla
from repro.models import moe as moe_lib
from repro.models.config import ModelConfig


class KVCache(NamedTuple):
    k: jnp.ndarray          # (L, B, S_max, H_kv, hd)
    v: jnp.ndarray
    length: jnp.ndarray     # scalar int32: #valid positions


class LatentCache(NamedTuple):
    latent: jnp.ndarray     # (L, B, S_max, cache_spec()["latent"] width)
    length: jnp.ndarray


def _contiguous_kv(cfg: ModelConfig) -> None:
    if cfg.kv_lora_rank or cfg.n_dense_layers:
        raise NotImplementedError(
            f"{cfg.name}: latent attention and split MLP stacks decode "
            "through the paged pool (forward_decode_paged), not a "
            "contiguous K/V cache")


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  n_layers: Optional[int] = None) -> KVCache:
    _contiguous_kv(cfg)
    nl = cfg.n_layers if n_layers is None else n_layers
    shape = (nl, batch, max_len, cfg.n_kv_heads, cfg.hd)
    z = jnp.zeros(shape, L.dtype_of(cfg))
    return KVCache(z, z, jnp.int32(0))


# -- per-block params -----------------------------------------------------------

def block_params(cfg: ModelConfig, rng) -> Dict:
    ks = jax.random.split(rng, 4)
    p = {"ln1": L.norm_params(cfg, ks[0]),
         "attn": mla.mla_params(cfg, ks[1]) if cfg.kv_lora_rank
         else L.attn_params(cfg, ks[1]),
         "ln2": L.norm_params(cfg, ks[2])}
    if cfg.n_dense_layers:
        return p            # the MLPs live in the dense and moe stacks
    if cfg.family == "moe":
        p["moe"] = moe_lib.moe_params(cfg, ks[3])
    else:
        p["mlp"] = L.mlp_params(cfg, ks[3])
    return p


def _stacked(make, rng, n: int) -> Dict:
    """``n`` draws of ``make`` stacked on a leading layer axis.
    ``lax.map`` (not ``vmap``) draws one layer at a time, so the float32
    draws that ``dense_init`` casts down never exist for all layers at
    once."""
    return jax.lax.map(make, jax.random.split(rng, n))


def stacked_block_params(cfg: ModelConfig, rng) -> Dict:
    """Per-layer params stacked on a leading layer axis."""
    return _stacked(lambda r: block_params(cfg, r), rng, cfg.n_layers)


# -- block application -------------------------------------------------------------

def _scan_xs(cfg: ModelConfig, params: Dict):
    """What the layer scan walks: each layer's block, and, where the
    MLPs are in stacks of their own, the layer's index."""
    idx = jnp.arange(cfg.n_layers) if cfg.n_dense_layers else None
    return params["blocks"], idx


def _mix_at(cfg: ModelConfig, params: Dict, p: Dict, i, h: jnp.ndarray,
            decode: bool, valid=None):
    """Layer ``i``'s channel mixing (MLP or MoE): (out, aux_loss, expert
    counts, None for a dense MLP; see ``moe.moe_apply``).  ``params`` and
    ``i`` are read only where the MLPs are in stacks of their own."""
    if not cfg.n_dense_layers:
        if cfg.family == "moe":
            return moe_lib.moe_apply(cfg, p["moe"], h, decode, valid)
        return L.mlp_apply(cfg, p["mlp"], h), jnp.float32(0.0), None
    k = cfg.n_dense_layers

    def at(tree, j):
        return jax.tree.map(lambda a: a[j], tree)

    def dense(h):
        m = L.mlp_apply(cfg, at(params["dense"], jnp.minimum(i, k - 1)), h)
        return m, jnp.float32(0.0), jnp.zeros((cfg.n_held + 1,), jnp.int32)

    def expert(h):
        return moe_lib.moe_apply(cfg, at(params["moe"], jnp.maximum(i - k, 0)),
                                 h, decode, valid)

    return jax.lax.cond(i < k, dense, expert, h)


def block_full(cfg: ModelConfig, params: Dict, xs, x: jnp.ndarray,
               positions: jnp.ndarray, causal: bool = True, valid=None):
    """Full-sequence block; ``xs`` is one step of ``_scan_xs``.  Returns
    (x, cache arrays in ``cache_spec`` order, aux, expert counts)."""
    p, i = xs
    norm = L.make_norm(cfg)
    h = norm(x, p["ln1"])
    if cfg.kv_lora_rank:
        o, latent = mla.attend_full(cfg, p["attn"], h, positions, causal)
        cache = (latent,)
    else:
        q, k, v = L.qkv_proj(cfg, p["attn"], h)
        q = L.apply_rope(q, positions, cfg.rope_theta, cfg.rope_frac)
        k = L.apply_rope(k, positions, cfg.rope_theta, cfg.rope_frac)
        o = L.attention(q, k, v, causal=causal)
        o = jnp.einsum("bqx,xd->bqd", o.reshape(*o.shape[:2], -1),
                       p["attn"]["wo"])
        cache = (k, v)
    x = x + o
    h = norm(x, p["ln2"])
    m, aux, counts = _mix_at(cfg, params, p, i, h, False, valid)
    return x + m, cache, aux, counts


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
    m = _mix_at(cfg, None, p, None, h, True)[0]
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
    p = {"embed": embed_params(cfg, k1),
         "blocks": stacked_block_params(cfg, k2)}
    if cfg.n_dense_layers:
        kd, km = jax.random.split(jax.random.fold_in(k2, 1))
        p["dense"] = _stacked(lambda r: L.mlp_params(cfg, r), kd,
                              cfg.n_dense_layers)
        p["moe"] = _stacked(lambda r: moe_lib.moe_params(cfg, r), km,
                            cfg.n_layers - cfg.n_dense_layers)
    return p


# -- forward passes ----------------------------------------------------------------------

def forward_train(cfg: ModelConfig, params: Dict, batch: Dict,
                  remat: bool = True):
    """Returns (logits, aux_loss)."""
    x = embed_inputs(cfg, params["embed"], batch)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def body(carry, xs):
        x, aux = carry
        x, _, a, _ = block_full(cfg, params, xs, x, positions)
        return (x, aux + a), None

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)),
                               _scan_xs(cfg, params))
    return lm_logits(cfg, params["embed"], x), aux


def _prefill(cfg: ModelConfig, params: Dict, batch: Dict,
             max_len: Optional[int], full_logits: bool, valid=None):
    """(logits, cache arrays (L, B, max_len, ...) in ``cache_spec``
    order, expert counts (L, n_held + 1) or None, the positions
    computed)."""
    x = embed_inputs(cfg, params["embed"], batch)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    max_len = max_len or S

    def body(x, xs):
        x, cache, _, counts = block_full(cfg, params, xs, x, positions,
                                         valid=valid)
        if max_len > S:
            cache = tuple(jnp.pad(a, [(0, 0), (0, max_len - S)]
                                  + [(0, 0)] * (a.ndim - 2)) for a in cache)
        return x, (cache, counts)

    x, (caches, counts) = jax.lax.scan(body, x, _scan_xs(cfg, params))
    logits = lm_logits(cfg, params["embed"],
                       x if full_logits else x[:, -1:, :])
    return logits, caches, counts, S


def forward_prefill(cfg: ModelConfig, params: Dict, batch: Dict,
                    max_len: Optional[int] = None,
                    full_logits: bool = False):
    """Returns (logits, KVCache, or LatentCache with latent attention);
    logits cover the last position only unless ``full_logits``."""
    logits, caches, _, S = _prefill(cfg, params, batch, max_len, full_logits)
    make = LatentCache if cfg.kv_lora_rank else KVCache
    return logits, make(*caches, jnp.int32(S))


def prefill_paged(cfg: ModelConfig, params: Dict, tokens: jnp.ndarray,
                  n_valid=None):
    """The serving engine's prefill of ``tokens`` (1, S), padded past
    its real tokens to a length bucket.  Returns (logits (1, S, V), the
    pool arrays' contents (L, 1, S, ...) in ``cache_spec`` order, and
    for an expert model given ``n_valid`` the token-expert counts
    (L, n_held + 1) of the first ``n_valid`` tokens, else None)."""
    counting = cfg.family == "moe" and n_valid is not None
    valid = jnp.arange(tokens.shape[1])[None] < n_valid if counting else None
    logits, caches, counts, _ = _prefill(cfg, params, {"tokens": tokens},
                                         None, True, valid)
    return logits, caches, counts if counting else None


def forward_decode_paged(cfg: ModelConfig, params: Dict, tokens: jnp.ndarray,
                         pools: Tuple[jnp.ndarray, ...],
                         block_tables: jnp.ndarray, lengths: jnp.ndarray,
                         slot_ids: jnp.ndarray, slot_offs: jnp.ndarray,
                         n_valid=None):
    """Paged decode: gather the cache through block tables (vLLM-style).

    pools: the pool's arrays in ``cache_spec`` order, (L, N, bs, ...)
    each: K and V, or the latent rows; block_tables: (B, nb);
    lengths: (B,) current context length; slot_ids/slot_offs: (B,) where
    this step's cache entries are written in the pool.  Returns (logits,
    pools, and for an expert model given ``n_valid`` the token-expert
    counts (L, n_held + 1) of the first ``n_valid`` rows, else None).
    The jnp gather here is the reference semantics of the
    kernels/paged_attention Pallas kernel.
    """
    x = embed_tokens(cfg, params["embed"], tokens)
    B = tokens.shape[0]
    norm = L.make_norm(cfg)
    posb = lengths[:, None].astype(jnp.int32)  # (B,1) rope positions
    counting = cfg.family == "moe" and n_valid is not None
    valid = (jnp.arange(B) < n_valid)[:, None] if counting else None

    def body(x, inp):
        xs, pool = inp
        p = xs[0]
        h = norm(x, p["ln1"])
        if cfg.kv_lora_rank:
            o, lp = mla.attend_paged(cfg, p["attn"], h, posb, pool[0],
                                     block_tables, lengths, slot_ids,
                                     slot_offs)
            pool = (lp,)
        else:
            kp, vp = pool
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
            pool = (kp, vp)
        x = x + o
        h = norm(x, p["ln2"])
        m, _, counts = _mix_at(cfg, params, p, xs[1], h, True, valid)
        return x + m, (pool, counts)

    x, (pools, counts) = jax.lax.scan(
        body, x, (_scan_xs(cfg, params), tuple(pools)))
    logits = lm_logits(cfg, params["embed"], x)
    return logits, pools, counts if counting else None


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
    _contiguous_kv(cfg)
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
        m = _mix_at(cfg, None, p, None, h, True)[0]
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
    _contiguous_kv(cfg)
    x = embed_tokens(cfg, params["embed"], tokens)
    pos = cache.length

    def body(x, inp):
        p, kc, vc = inp
        x, kc, vc = block_decode(cfg, p, x, pos, kc, vc)
        return x, (kc, vc)

    x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], cache.k, cache.v))
    logits = lm_logits(cfg, params["embed"], x)
    return logits, KVCache(ks, vs, pos + 1)
