"""Host spans (``repro.obs.spans``): the disabled path, the spans one
prefill and one decode emit through the scheduler, the collector's span,
and the pool's block-write and flush counters."""

import gc

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.kvcache.pool import BlockPool
from repro.models.model import build
from repro.obs import spans
from repro.serving.admission import SchedRequest
from repro.serving.engine import Request, ServingEngine

BLOCK = 8


class Recorder:
    """A span factory that records (depth, name, meta) as spans open
    and checks that they close in the order they opened."""

    def __init__(self):
        self.opened = []
        self.stack = []

    def __call__(self, name, **meta):
        return _Span(self, name, meta)


class _Span:
    def __init__(self, rec, name, meta):
        self.rec, self.name, self.meta = rec, name, meta

    def __enter__(self):
        self.rec.opened.append((len(self.rec.stack), self.name, self.meta))
        self.rec.stack.append(self)
        return self

    def __exit__(self, *exc):
        assert self.rec.stack.pop() is self
        return False


@pytest.fixture(autouse=True)
def spans_off():
    spans.disable()
    yield
    spans.disable()


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced(get_config("granite-3-8b"))
    api = build(cfg)
    return api, api.init(jax.random.PRNGKey(0))


def _counter(snap, name):
    return snap.counters.get(name, 0)


def _serve_one(eng, rid, prompt, max_new):
    """Submit one request and tick until it completes."""
    sched = eng.make_scheduler()
    sched.submit(SchedRequest(req_id=rid, prompt_len=len(prompt),
                              max_new=max_new,
                              payload=Request(rid, prompt, max_new)))
    while sched.queue or sched.active:
        sched.tick()
    return sched


def test_disabled_span_is_the_shared_noop_and_never_calls_the_factory(
        small_model):
    calls = []
    spans.enable(lambda name, **meta: calls.append(name) or spans.NOOP)
    spans.disable()
    assert spans.span("repro.engine.decode", n=3) is spans.NOOP
    assert spans.span("repro.kv.flush") is spans.NOOP
    with spans.span("x") as s:
        assert s is spans.NOOP
    api, params = small_model
    eng = ServingEngine(api, params, block_size=BLOCK, hbm_blocks=16,
                        max_batch=2)
    _serve_one(eng, 0, list(range(1, 21)), 3)
    gc.collect()
    assert calls == []


def test_prefill_and_decode_emit_the_span_tree_in_order(small_model):
    api, params = small_model
    eng = ServingEngine(api, params, block_size=BLOCK, hbm_blocks=16,
                        max_batch=2)
    rec = Recorder()
    gc.disable()           # a collection would add repro.host.gc spans
    try:
        spans.enable(rec)
        # 20 tokens: two full blocks and a tail; the first token comes
        # from prefill, the next two from two decode steps
        _serve_one(eng, 7, list(range(1, 21)), 3)
        spans.disable()
    finally:
        gc.enable()
    assert rec.stack == []
    decode = [(0, "repro.sched.tick", {}),
              (1, "repro.engine.decode", {"n": 1}),
              (2, "repro.engine.decode_inputs", {}),
              (2, "repro.model.decode", {}),
              (2, "repro.engine.sample", {}),
              (2, "repro.kv.flush", {})]
    assert rec.opened == [
        (0, "repro.sched.tick", {}),
        (1, "repro.engine.prefill", {"rid": 7}),
        (2, "repro.kv.admit", {"rid": 7}),
        (2, "repro.model.prefill", {}),
        (2, "repro.kv.write_blocks", {}),
        (2, "repro.engine.sample", {}),
    ] + decode + decode


def test_swap_in_is_a_span_inside_admit(small_model):
    api, params = small_model
    # a pool of 4 blocks: the first prompt's blocks are flushed to the
    # host by decode, evicted by the second prompt and swapped back in
    # by the third
    eng = ServingEngine(api, params, block_size=BLOCK, hbm_blocks=4,
                        max_batch=1)
    first = list(range(1, 17))
    _serve_one(eng, 0, first, 2)
    _serve_one(eng, 1, list(range(100, 124)), 2)
    rec = Recorder()
    spans.enable(rec)
    _serve_one(eng, 2, first + [5], 2)
    spans.disable()
    names = [(d, n) for d, n, _ in rec.opened if n != spans.GC_SPAN]
    admit = names.index((2, "repro.kv.admit"))
    assert names[admit + 1] == (3, "repro.kv.swap_in")
    assert eng.pool.stats.swap_in >= 1


def test_gc_collect_records_a_gc_span():
    rec = Recorder()
    spans.enable(rec)
    gc.collect()
    spans.disable()
    assert (0, spans.GC_SPAN, {"gen": 2}) in rec.opened
    assert rec.stack == []
    n = len(rec.opened)
    gc.collect()
    assert len(rec.opened) == n


def test_block_write_and_flush_counters_count_exactly():
    cfg = reduced(get_config("granite-3-8b"))
    pool = BlockPool(cfg, 8, BLOCK)
    shape = (cfg.n_layers, BLOCK, cfg.n_kv_heads, cfg.hd)
    for key in (11, 12, 13):
        slot, needs_fill = pool.lookup(key, pin=False)
        assert needs_fill
        pool.write_block(slot, np.ones(shape, np.float32),
                         np.ones(shape, np.float32), key=key)
    snap = pool.obs_snapshot()
    assert _counter(snap, "pool_block_writes_total") == 3
    assert _counter(snap, "pool_flushes_total") == 0
    # three dirty blocks: two flushed, then the third, then none
    assert [pool.run_flusher(max_blocks=2) for _ in range(3)] == [2, 1, 0]
    snap = pool.obs_snapshot()
    assert _counter(snap, "pool_flushes_total") == 3
    assert _counter(snap, 'pool_swaps_total{dir="out"}') == 3
    # a block whose host copy exists is cleaned without a copy
    pool.policy.set_dirty(11)
    assert pool.run_flusher() == 1
    assert _counter(pool.obs_snapshot(), "pool_flushes_total") == 3
    assert _counter(pool.obs_snapshot(), "pool_block_writes_total") == 3


def test_engine_counts_the_blocks_prefill_writes(small_model):
    api, params = small_model
    eng = ServingEngine(api, params, block_size=BLOCK, hbm_blocks=16,
                        max_batch=2)
    prefix = list(range(1, 17))        # two full blocks
    _serve_one(eng, 0, prefix + [1, 2, 3], 2)
    # the first prompt writes its three blocks; the second hits the two
    # shared ones and writes its tail alone
    assert _counter(eng.obs_snapshot(), "pool_block_writes_total") == 3
    _serve_one(eng, 1, prefix + [9], 2)
    snap = eng.obs_snapshot()
    assert _counter(snap, "pool_block_writes_total") == 4
    assert _counter(snap, 'pool_lookups_total{result="hit"}') == 2
