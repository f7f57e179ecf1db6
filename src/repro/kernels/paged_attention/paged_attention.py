"""Pallas TPU paged-attention decode kernel.

One query token per sequence attends to its KV scattered across pool
blocks, addressed through a block table.  The block table and per-
sequence lengths ride in scalar-prefetch (SMEM) so the K/V BlockSpec
index_map can dereference physical block ids while the grid walks logical
block indices — the TPU-idiomatic replacement for vLLM's gather (the pool
never moves; only block-table metadata, which is exactly the structure
Clock2Q+ manages, changes).

Shapes: q (B, H, d); kpool/vpool (N, bs, Hkv, d); block_tables (B, nb);
lengths (B,).  GQA handled by reshaping q to (Hkv, G, d) inside the
kernel.  Online softmax across the nb (arbitrary) grid dimension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, bs: int, n_q: int, n_kv: int,
            scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)
    g = n_q // n_kv

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    run = j * bs < length  # skip blocks past this sequence's length

    @pl.when(run)
    def _compute():
        d = q_ref.shape[-1]
        q = q_ref[0].astype(jnp.float32)                  # (H, d)
        k = k_ref[0].astype(jnp.float32)                  # (bs, Hkv, d)
        v = v_ref[0].astype(jnp.float32)
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
        valid = pos < length
        k = jnp.where(valid[:, :, None] if k.ndim == 3 else valid, k, 0.0)
        v = jnp.where(valid[:, :, None], v, 0.0)
        qg = q.reshape(n_kv, g, d)
        # scores: (Hkv, G, bs)
        s = jax.lax.dot_general(
            qg, k.transpose(1, 2, 0), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[:, 0][None, None, :], s, NEG_INF)
        m_prev = m_ref[...]                               # (Hkv, G)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
        p = jnp.exp(s - m_new[..., None])                 # (Hkv, G, bs)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=2)
        pv = jax.lax.dot_general(
            p, v.transpose(1, 0, 2), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # (Hkv, G, d)
        acc_ref[...] = acc_ref[...] * alpha[..., None] + pv
        m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = (acc_ref[...] / denom).reshape(n_q, d_of(o_ref)) \
            .astype(o_ref.dtype)


def d_of(ref):
    return ref.shape[-1]


def paged_attention_raw(q, kpool, vpool, block_tables, lengths, *,
                        interpret: bool = False):
    """q: (B, H, d); kpool/vpool: (N, bs, Hkv, d);
    block_tables: (B, nb) int32; lengths: (B,) int32 -> (B, H, d)."""
    B, H, d = q.shape
    N, bs, Hkv, _ = kpool.shape
    nb = block_tables.shape[1]
    scale = 1.0 / (d ** 0.5)
    kern = functools.partial(_kernel, bs=bs, n_q=H, n_kv=Hkv, scale=scale)

    def kv_map(b, j, bt_ref, len_ref):
        return (bt_ref[b, j], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, H, d), lambda b, j, bt, ln: (b, 0, 0)),
            pl.BlockSpec((1, bs, Hkv, d), kv_map),
            pl.BlockSpec((1, bs, Hkv, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, H, d), lambda b, j, bt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, H // Hkv, d), jnp.float32),
            pltpu.VMEM((Hkv, H // Hkv), jnp.float32),
            pltpu.VMEM((Hkv, H // Hkv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, lengths, q, kpool, vpool)
