"""The flusher's batched device->host copy (``BlockPool.run_flusher`` on
the unhardened path) against key-by-key ``BlockPool.flush``: the same
host-tier contents, counters and dirty bits, pass for pass, bit for bit;
one program for passes of 1 to ``max_blocks`` copies; and the hardened
path (``faults=``) still one ``swap_out`` per block."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.faults import NullPlan
from repro.kvcache import pool as pool_mod
from repro.kvcache.pool import BlockPool

BLOCK = 8
CFG = reduced(get_config("granite-3-8b"))
SHAPE = (CFG.n_layers, BLOCK, CFG.n_kv_heads, CFG.hd)
COUNTERS = ('pool_swaps_total{dir="out"}', "pool_flushes_total",
            'pool_swaps_total{dir="in"}', "pool_drops_total")


def _per_key_flusher(pool, max_blocks=4):
    """The flusher as key-by-key ``flush`` calls (the reference)."""
    dirty = pool.policy.dirty_keys()[:max_blocks]
    for k in dirty:
        pool.flush(k)
    return len(dirty)


def _write(pool, key, rng, dtype):
    """Look ``key`` up and, on a fill miss, write seeded contents."""
    slot, needs_fill = pool.lookup(key, pin=False)
    k = rng.standard_normal(SHAPE).astype(np.float32)
    v = rng.standard_normal(SHAPE).astype(np.float32)
    if needs_fill:
        pool.write_block(slot, jnp.asarray(k, dtype), jnp.asarray(v, dtype),
                         key=key)


def _state(pool):
    snap = pool.obs_snapshot()
    return ([snap.counters.get(c, 0) for c in COUNTERS],
            pool.policy.dirty_keys(), list(pool.host))


def _assert_same(batched, ref):
    assert _state(batched) == _state(ref)
    for key, (k, v) in ref.host.items():
        bk, bv = batched.host[key]
        for got, want in ((bk, k), (bv, v)):
            assert got.shape == want.shape and got.dtype == want.dtype
            assert got.flags.c_contiguous
            assert np.array_equal(got.view(np.uint8), want.view(np.uint8))


def _pools(**kw):
    return BlockPool(CFG, BLOCK, BLOCK, **kw), BlockPool(CFG, BLOCK, BLOCK,
                                                         **kw)


def _scenario_padding(step):
    # one, two and three dirty blocks: fewer than the cap of 4
    for n in (1, 2, 3):
        step(writes=range(10 * n, 10 * n + n), dirty=(), max_blocks=4)


def _scenario_mirrored(step):
    step(writes=(1, 2, 3, 4), dirty=(), max_blocks=4)
    # rewritten and mirrored blocks are cleaned without a copy, beside
    # new ones that are copied
    step(writes=(5, 6), dirty=(1, 3), max_blocks=4)
    step(writes=(), dirty=(2, 4, 5), max_blocks=2)
    step(writes=(), dirty=(), max_blocks=4)


def _scenario_host_fills(step):
    # a host tier of 6 blocks: the second pass fills it after two of
    # its four copies, and the rest are cleaned without one
    step(writes=(1, 2, 3, 4), dirty=(), max_blocks=4)
    step(writes=(5, 6, 7, 8), dirty=(), max_blocks=4)
    step(writes=(), dirty=(1, 2), max_blocks=4)


def _scenario_evictions(step):
    # more keys than blocks: evictions copy out or drop between passes
    rng = np.random.default_rng(5)
    for _ in range(12):
        keys = [int(k) for k in rng.integers(0, 24, 5)]
        step(writes=keys, dirty=keys[:2], max_blocks=int(rng.integers(1, 5)))


SCENARIOS = {
    "padding": (_scenario_padding, {}),
    "mirrored": (_scenario_mirrored, {}),
    "host_fills_mid_pass": (_scenario_host_fills, {"n_host_blocks": 6}),
    "evictions": (_scenario_evictions, {}),
    "sharded": (_scenario_evictions, {"n_shards": 4}),
    "bfloat16": (_scenario_mirrored, {"dtype": jnp.bfloat16}),
}


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_batched_flusher_matches_per_key_flush(name):
    scenario, kw = SCENARIOS[name]
    dtype = kw.get("dtype", jnp.float32)
    batched, ref = _pools(**kw)
    passes = []

    def step(writes, dirty, max_blocks):
        for pool in (batched, ref):
            rng = np.random.default_rng(len(passes))
            for key in writes:
                _write(pool, key, rng, dtype)
            for key in dirty:
                if pool.policy.slot_of(key) != pool_mod.EMPTY:
                    pool.policy.set_dirty(key)
        n = batched.run_flusher(max_blocks)
        assert n == _per_key_flusher(ref, max_blocks)
        passes.append(n)
        _assert_same(batched, ref)

    scenario(step)
    assert sum(passes) > 0
    assert batched.obs_snapshot().counters.get(
        "pool_flush_batches_total", 0) > 0


def test_flush_batches_count_one_transfer_per_copying_pass():
    pool = BlockPool(CFG, BLOCK, BLOCK)
    rng = np.random.default_rng(0)
    for key in (1, 2, 3):
        _write(pool, key, rng, jnp.float32)

    def batches():
        return pool.obs_snapshot().counters.get("pool_flush_batches_total")

    assert pool.run_flusher() == 3 and batches() == 1
    # the host rows keep the three real rows alive, not the padded fourth
    k, _ = pool.host[1]
    assert k.base.nbytes == 3 * k.nbytes
    # a pass that only cleans a mirrored block makes no transfer
    pool.policy.set_dirty(2)
    assert pool.run_flusher() == 1 and batches() == 1
    assert pool.run_flusher() == 0 and batches() == 1
    assert pool.obs_snapshot().counters["pool_flushes_total"] == 3


def test_gather_compiles_once_for_passes_of_one_to_four_blocks():
    # a pool shape no other test uses, so its first pass compiles
    pool = BlockPool(CFG, 11, BLOCK)
    rng = np.random.default_rng(0)
    before = pool_mod._gather_slots._cache_size()
    sizes = []
    for n in (1, 4, 2, 3):
        for key in range(100 * n, 100 * n + n):
            _write(pool, key, rng, jnp.float32)
        assert pool.run_flusher(max_blocks=4) == n
        sizes.append(pool_mod._gather_slots._cache_size() - before)
    assert sizes == [1, 1, 1, 1]
    assert len(pool.host) == 10


def test_hardened_flusher_makes_one_swap_out_per_block(monkeypatch):
    pool = BlockPool(CFG, BLOCK, BLOCK, faults=NullPlan())
    rng = np.random.default_rng(0)
    for key in (1, 2, 3):
        _write(pool, key, rng, jnp.float32)
    ops = []
    run = pool._io.run

    def counting_run(op, key, fn=None):
        ops.append((op, key))
        return run(op, key, fn)

    monkeypatch.setattr(pool._io, "run", counting_run)
    monkeypatch.setattr(pool_mod, "_gather_slots", None)  # never called
    assert pool.run_flusher() == 3
    assert ops == [("swap_out", k) for k in (1, 2, 3)]
    snap = pool.obs_snapshot()
    assert snap.counters["pool_flushes_total"] == 3
    assert snap.counters.get("pool_flush_batches_total", 0) == 0
    assert pool.policy.dirty_keys() == []
