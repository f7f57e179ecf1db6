"""Pallas TPU flash attention (causal / full), online-softmax over KV
blocks.

Grid: (batch*heads, n_q_blocks, n_kv_blocks) with the KV dimension
"arbitrary" (sequential) so the f32 accumulator/max/sum scratch persists
across KV blocks in VMEM.  Block shapes are (block_q, head_dim) /
(block_kv, head_dim); head_dim is MXU-lane aligned by the ops.py wrapper.
Causal q-blocks skip fully-masked KV blocks via @pl.when.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            causal: bool, block_q: int, block_kv: int, scale: float,
            kv_seq_len: int):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    run = (~causal) | (j * block_kv <= i * block_q + (block_q - 1))

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0].astype(jnp.float32)          # (bkv, d)
        v = v_ref[0].astype(jnp.float32)
        # ragged tail: zero padded kv rows (OOB block reads are undefined)
        krow = j * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_kv, 1), 0)
        kvalid = krow < kv_seq_len
        k = jnp.where(kvalid, k, 0.0)
        v = jnp.where(kvalid, v, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        kpos = j * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        mask = kpos < kv_seq_len
        if causal:
            mask = mask & (kpos <= qpos)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_bh(q, k, v, *, causal: bool = True, block_q: int = 128,
                       block_kv: int = 128, interpret: bool = False):
    """q/k/v: (BH, S, d) with BH = batch*heads (kv already repeated)."""
    BH, Sq, d = q.shape
    Skv = k.shape[1]
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Skv, block_kv)
    scale = 1.0 / (d ** 0.5)
    kern = functools.partial(_kernel, causal=causal, block_q=block_q,
                             block_kv=block_kv, scale=scale,
                             kv_seq_len=Skv)
    return pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
