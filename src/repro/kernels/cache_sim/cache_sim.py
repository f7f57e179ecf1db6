"""Pallas TPU Clock2Q+ trace-replay kernel (lane-parallel simulation).

The paper's hot path — per-access hash lookup + ref-bit update — is
pointer-chasing on CPU.  The TPU adaptation (DESIGN.md §3): many
independent simulations run as VPU lanes, and lookup is a brute-force
vector compare of the requested key against the resident-key arrays held
entirely in VMEM (for the parameter sweeps cache research needs, C <= a
few thousand, compare-all beats emulating a hash).  Eviction clock sweeps
are bounded masked fori_loops (<= 2M iterations), so the kernel has no
data-dependent control flow.  It runs in interpret mode only: Mosaic (the
TPU compiler) refuses it, first at the bool ``argmax`` in ``_lookup``
("Only float32 is supported"), then at the dynamic lane index
``trace_ref[:, t]`` (not provably a multiple of 128), then at the bool
``fori_loop`` carry of ``sweep_insert`` ("failed to legalize operation
'scf.for'").  No entry point calls it; the device sweep is
``repro.tuning.sweep`` on the XLA engine step.

State layout per lane block (LANES x slots, int32):
  skey/sref/sseq + spos/seqctr   — Small FIFO ring + correlation window
  mkey/mref + hand               — Main Clock
  gkey + gpos                    — Ghost ring
Trace: (LANES, T) int32; output: hits (LANES, T) int32 + final state
(aliased).  Semantics bit-match repro.core.jax_engine c2qp (skip_limit=0)
and therefore the pure-Python reference zoo.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _onehot_put(arr, rows_mask, col_idx, values):
    """arr: (L, C); write values (L,) at [l, col_idx[l]] where rows_mask."""
    L, C = arr.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (L, C), 1)
    sel = rows_mask[:, None] & (cols == col_idx[:, None])
    if values.ndim == 1:
        values = values[:, None]
    return jnp.where(sel, values, arr)


def _lookup(keys, key):
    """keys: (L, C), key: (L,) -> (found (L,), slot (L,))."""
    eq = keys == key[:, None]
    return jnp.any(eq, axis=1), jnp.argmax(eq, axis=1).astype(jnp.int32)


def _kernel(trace_ref, skey_ref, sref_ref, sseq_ref, mkey_ref, mref_ref,
            gkey_ref, scal_ref, hits_ref, skey_o, sref_o, sseq_o, mkey_o,
            mref_o, gkey_o, scal_o, *, T: int, window: int):
    Lb, S = skey_ref.shape
    M = mkey_ref.shape[1]
    G = gkey_ref.shape[1]

    def sweep_insert(mkey, mref, hand, ins_key, active):
        """Masked clock sweep + insert for lanes with active; returns
        updated (mkey, mref, hand)."""
        def body(_, carry):
            mkey, mref, hand, done = carry
            cur_key = jnp.take_along_axis(mkey, hand[:, None], axis=1)[:, 0]
            cur_ref = jnp.take_along_axis(mref, hand[:, None], axis=1)[:, 0]
            skip = active & ~done & (cur_key >= 0) & (cur_ref > 0)
            take = active & ~done & ~skip
            mref = _onehot_put(mref, skip, hand, jnp.zeros((Lb,), jnp.int32))
            # take: write new key at hand, clear ref
            mkey = _onehot_put(mkey, take, hand, ins_key)
            mref = _onehot_put(mref, take, hand, jnp.zeros((Lb,), jnp.int32))
            hand = jnp.where(active & ~done, (hand + 1) % M, hand)
            done = done | take
            return mkey, mref, hand, done

        done0 = ~active
        mkey, mref, hand, _ = jax.lax.fori_loop(
            0, 2 * M + 1, body, (mkey, mref, hand, done0))
        return mkey, mref, hand

    def step(t, carry):
        (skey, sref, sseq, mkey, mref, gkey,
         spos, seqctr, hand, gpos) = carry
        key = trace_ref[:, t]

        in_s, s_slot = _lookup(skey, key)
        in_m, m_slot = _lookup(mkey, key)
        in_g, g_slot = _lookup(gkey, key)
        hit = in_s | in_m
        hits_ref[:, pl.ds(t, 1)] = hit.astype(jnp.int32)[:, None]

        # case small-hit: set ref if aged past the correlation window
        age = seqctr - jnp.take_along_axis(sseq, s_slot[:, None], axis=1)[:, 0]
        sref = _onehot_put(sref, in_s & (age >= window), s_slot,
                           jnp.ones((Lb,), jnp.int32))
        # case main-hit: set ref
        mref = _onehot_put(mref, in_m, m_slot, jnp.ones((Lb,), jnp.int32))

        # case ghost-hit: tombstone + insert straight into Main Clock
        ghost_case = ~hit & in_g
        gkey = _onehot_put(gkey, ghost_case, g_slot,
                           jnp.full((Lb,), -1, jnp.int32))

        # case new: displace the small-ring slot at the cursor
        new_case = ~hit & ~in_g
        displaced = jnp.take_along_axis(skey, spos[:, None], axis=1)[:, 0]
        disp_ref = jnp.take_along_axis(sref, spos[:, None], axis=1)[:, 0]
        has_disp = new_case & (displaced >= 0)
        promote = has_disp & (disp_ref > 0)
        demote = has_disp & (disp_ref == 0)

        # one main insert per lane (ghost-hit XOR promotion)
        ins_active = ghost_case | promote
        ins_key = jnp.where(ghost_case, key, displaced)
        mkey, mref, hand = sweep_insert(mkey, mref, hand, ins_key,
                                        ins_active)

        # ghost ring push for demotions
        old_g = jnp.take_along_axis(gkey, gpos[:, None], axis=1)[:, 0]
        gkey = _onehot_put(gkey, demote, gpos, displaced)
        gpos = jnp.where(demote, (gpos + 1) % G, gpos)

        # write the new key into the small ring
        skey = _onehot_put(skey, new_case, spos, key)
        sref = _onehot_put(sref, new_case, spos, jnp.zeros((Lb,), jnp.int32))
        sseq = _onehot_put(sseq, new_case, spos, seqctr)
        spos = jnp.where(new_case, (spos + 1) % S, spos)
        seqctr = jnp.where(new_case, seqctr + 1, seqctr)

        return (skey, sref, sseq, mkey, mref, gkey,
                spos, seqctr, hand, gpos)

    spos = scal_ref[:, 0]
    seqctr = scal_ref[:, 1]
    hand = scal_ref[:, 2]
    gpos = scal_ref[:, 3]
    carry = (skey_ref[...], sref_ref[...], sseq_ref[...], mkey_ref[...],
             mref_ref[...], gkey_ref[...], spos, seqctr, hand, gpos)
    carry = jax.lax.fori_loop(0, T, step, carry)
    (skey, sref, sseq, mkey, mref, gkey, spos, seqctr, hand, gpos) = carry
    skey_o[...] = skey
    sref_o[...] = sref
    sseq_o[...] = sseq
    mkey_o[...] = mkey
    mref_o[...] = mref
    gkey_o[...] = gkey
    scal_o[...] = jnp.stack([spos, seqctr, hand, gpos], axis=1)


def cache_sim_raw(trace, skey, sref, sseq, mkey, mref, gkey, scal, *,
                  window: int, interpret: bool = False):
    """All state (LANES, ·) int32; trace (LANES, T).  Returns
    (hits (LANES, T) int32, skey, sref, sseq, mkey, mref, gkey, scal)."""
    L, T = trace.shape
    kern = functools.partial(_kernel, T=T, window=window)
    state = (skey, sref, sseq, mkey, mref, gkey, scal)
    blk = lambda shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    outs = pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=[blk(trace.shape)] + [blk(a.shape) for a in state],
        out_specs=[blk((L, T))] + [blk(a.shape) for a in state],
        out_shape=[jax.ShapeDtypeStruct((L, T), jnp.int32)]
        + [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in state],
        interpret=interpret,
    )(trace, *state)
    return outs
