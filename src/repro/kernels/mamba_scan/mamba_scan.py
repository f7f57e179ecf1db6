"""Pallas TPU selective-scan (Mamba1 core) kernel.

Grid: (batch, d_inner blocks, chunks); the chunk dimension is sequential
("arbitrary") and carries the recurrent state h (d_blk, N) in VMEM
scratch — the TPU-native replacement for the CUDA parallel-scan kernel:
HBM traffic is one read of (u, dt, B, C) and one write of y per element,
with the state never leaving VMEM.  Inside a chunk the recurrence runs as
a fori_loop of VPU vector ops over (d_blk, N) tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(u_ref, dt_ref, b_ref, c_ref, alog_ref, y_ref, h_ref, *,
            chunk: int, seq_len: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = -jnp.exp(alog_ref[...].astype(jnp.float32))      # (d_blk, N)

    def step(t, h):
        # global position for ragged tails: identity update when past end
        valid = (c * chunk + t) < seq_len
        dt = dt_ref[0, t].astype(jnp.float32)            # (d_blk,)
        dt = jnp.where(valid, dt, 0.0)
        u = u_ref[0, t].astype(jnp.float32)              # (d_blk,)
        bb = b_ref[0, t].astype(jnp.float32)             # (N,)
        cc = c_ref[0, t].astype(jnp.float32)             # (N,)
        dA = jnp.exp(dt[:, None] * A)                    # (d_blk, N)
        h = dA * h + (dt * u)[:, None] * bb[None, :]
        y = jnp.sum(h * cc[None, :], axis=1)             # (d_blk,)
        y_ref[0, pl.ds(t, 1), :] = y.astype(y_ref.dtype)[None, :]
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


def mamba_scan_raw(u, dt, Bc, Cc, A_log, *, d_block: int = 512,
                   chunk: int = 64, interpret: bool = False):
    """u/dt: (B, S, din); Bc/Cc: (B, S, N); A_log: (din, N) -> y (B, S, din)."""
    B, S, din = u.shape
    N = Bc.shape[-1]
    d_block = min(d_block, din)
    chunk = min(chunk, S)
    nd = pl.cdiv(din, d_block)
    nc = pl.cdiv(S, chunk)
    kern = functools.partial(_kernel, chunk=chunk, seq_len=S)
    return pl.pallas_call(
        kern,
        grid=(B, nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda b, i, c: (b, c, i)),
            pl.BlockSpec((1, chunk, d_block), lambda b, i, c: (b, c, i)),
            pl.BlockSpec((1, chunk, N), lambda b, i, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, i, c: (b, c, 0)),
            pl.BlockSpec((d_block, N), lambda b, i, c: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d_block), lambda b, i, c: (b, c, i)),
        out_shape=jax.ShapeDtypeStruct((B, S, din), u.dtype),
        scratch_shapes=[pltpu.VMEM((d_block, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(u, dt, Bc, Cc, A_log)
