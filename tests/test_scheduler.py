"""Deterministic simulation harness for the continuous-batching serving
scheduler (repro.serving.scheduler).

Everything in the fast tier here runs the model-free ``SimExecutor`` on
the virtual tick clock — no JAX, no wall clock, no unseeded RNG — so
each property is checked against the *exact* decision stream
(``schedule_log``), not a statistical summary:

  * bit-identical schedules per seed, across the registered arrival
    scenarios (poisson / burst / adversarial);
  * no starvation of the batch class under sustained overload (aging);
  * shed-before-deadline-miss: a completed request never misses its
    SLO, and a deadline shed happens at or before the deadline;
  * greedy-token equality across batch compositions (the sim analogue
    of scheduler-vs-``run_sync`` on the real engine, locked slow below);
  * bounded admission with displacement, the block watermark, degraded-
    mode backpressure, and multi-tenant fair share;
  * a hypothesis sweep of the structural invariants (terminal
    trichotomy, queue bound, deadline safety) over random workloads.

The slow tier drives the real ``ServingEngine`` through the same
scheduler: token equality against the old synchronous loop, and a
FaultPlan chaos run (IO_ERROR storm + SHARD_LOSS mid-batch) checking
degraded shedding, recovery, and fault-oblivious completed tokens.
"""

import numpy as np
import pytest

from repro.core import traces
from repro.faults.io import Clock
from repro.serving.admission import (
    R_DEADLINE, R_DEGRADED, R_DISPLACED, R_OVERSIZE, R_QUEUE_FULL,
    ST_COMPLETED, ST_REJECTED, ST_SHED, AdmissionConfig, AdmissionQueue,
    SchedRequest,
)
from repro.serving.scheduler import (
    SchedConfig, Scheduler, SimExecutor, simulate_sync,
)

ARRIVAL_SCENARIOS = ("arrivals-poisson", "arrivals-burst",
                     "arrivals-adversarial")


def _mk_requests(n, seed=0, *, prompt=24, max_new=5, n_classes=3,
                 tenants=("a", "b"), deadline_slack=0):
    """A deterministic request mix: class/tenant round-robin keyed off
    the index (pure function of its arguments)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        reqs.append(SchedRequest(
            req_id=i, prompt_len=prompt + int(rng.integers(0, 8)),
            max_new=max_new, priority=i % n_classes,
            tenant=tenants[i % len(tenants)],
            deadline=0))
    return reqs


def _run(reqs, arrivals, *, seed=0, cfg=None, x=None):
    cfg = cfg or SchedConfig(token_budget=256, max_batch=4)
    clock = Clock()
    x = x or SimExecutor(n_blocks=4096, block_size=16, clock=clock)
    s = Scheduler(x, config=cfg, clock=clock, seed=seed)
    outs = s.run(reqs, arrivals)
    return s, outs


# =============================================================================
# bit-reproducibility over the arrival-scenario registry
# =============================================================================

@pytest.mark.parametrize("scenario", ARRIVAL_SCENARIOS)
def test_bit_identical_schedule_per_seed(scenario):
    arrivals = traces.make_trace(scenario, n=120, seed=9).tolist()
    logs, outs = [], []
    for _ in range(2):  # two full independent replays
        s, o = _run(_mk_requests(120, seed=4), arrivals, seed=17)
        logs.append(list(s.schedule_log))
        outs.append([(x.req_id, x.status, x.finish, x.reason,
                      tuple(x.tokens)) for x in o])
    assert logs[0] == logs[1]
    assert outs[0] == outs[1]
    # the log is the full decision stream: every request admits or
    # rejects exactly once, and terminals cover the whole input
    assert len(outs[0]) == 120
    kinds = {e[0] for e in logs[0]}
    assert "admit" in kinds and "batch" in kinds


def test_seed_changes_tiebreaks_not_outcomes():
    # same workload, different scheduler seed: the tie-break hash moves,
    # but the set of terminal statuses stays a function of the workload
    arrivals = traces.make_trace("arrivals-burst", n=80, seed=2).tolist()
    _, o1 = _run(_mk_requests(80, seed=1), arrivals, seed=0)
    _, o2 = _run(_mk_requests(80, seed=1), arrivals, seed=999)
    assert {x.req_id for x in o1 if x.status == ST_COMPLETED} \
        == {x.req_id for x in o2 if x.status == ST_COMPLETED}


# =============================================================================
# no starvation under sustained overload (anti-starvation aging)
# =============================================================================

def test_batch_class_not_starved_under_overload():
    # 2 requests/tick of interactive work against ~1.33 seqs/tick of
    # capacity: without aging the batch-class stragglers drain dead last;
    # with aging they promote and interleave
    hot = [SchedRequest(req_id=i, prompt_len=8, max_new=4, priority=0)
           for i in range(60)]
    cold = [SchedRequest(req_id=1000 + i, prompt_len=8, max_new=4,
                         priority=2) for i in range(4)]
    # cold arrives at tick 4, once the interactive backlog has built up
    arrivals = [i // 2 for i in range(60)] + [4, 4, 4, 4]
    adm = AdmissionConfig(queue_bound=256, age_ticks=8)
    cfg = SchedConfig(token_budget=256, max_batch=4, admission=adm)
    s, outs = _run(hot + cold, arrivals, cfg=cfg)
    assert all(o.status == ST_COMPLETED for o in outs)

    def start_ticks(sched, pred):
        return [e[1] for e in sched.schedule_log
                if e[0] == "start" and pred(e[2])]

    # every aged batch request is dispatched before the interactive
    # stream drains — it was not parked behind 60 class-0 requests
    assert max(start_ticks(s, lambda r: r >= 1000)) \
        < max(start_ticks(s, lambda r: r < 1000))

    # control: aging off -> batch work starts only once every
    # interactive request has been dispatched (starved to the end)
    adm0 = AdmissionConfig(queue_bound=256, age_ticks=0)
    s0, outs0 = _run(hot + cold, arrivals,
                     cfg=SchedConfig(token_budget=256, max_batch=4,
                                     admission=adm0))
    assert min(start_ticks(s0, lambda r: r >= 1000)) \
        >= max(start_ticks(s0, lambda r: r < 1000))


# =============================================================================
# shed-before-deadline-miss
# =============================================================================

def test_shed_before_deadline_miss():
    # more deadline work than capacity: some requests must be shed, and
    # the scheduler sheds them BEFORE their deadline instead of letting
    # them run and miss
    reqs = [SchedRequest(req_id=i, prompt_len=16, max_new=6, priority=0,
                         deadline=12) for i in range(24)]
    s, outs = _run(reqs, [0] * 24,
                   cfg=SchedConfig(token_budget=64, max_batch=3))
    by_status = {}
    for o in outs:
        by_status.setdefault(o.status, []).append(o)
    assert by_status.get(ST_SHED), "overload must shed"
    for o in by_status.get(ST_COMPLETED, ()):
        assert o.finish <= 12, "a completed request never misses its SLO"
        assert len(o.tokens) == 6
    for o in by_status[ST_SHED]:
        assert o.reason == R_DEADLINE
        assert o.finish <= 12, "shed happens before the miss, not after"


def test_feasible_deadlines_all_met():
    # plenty of capacity and feasible SLOs: nothing sheds, all deadlines met
    reqs = [SchedRequest(req_id=i, prompt_len=8, max_new=4, priority=0,
                         deadline=8 + i * 4) for i in range(6)]
    s, outs = _run(reqs, [i * 4 for i in range(6)],
                   cfg=SchedConfig(token_budget=256, max_batch=4))
    assert all(o.status == ST_COMPLETED for o in outs)
    assert all(o.finish <= r.deadline for o, r in
               zip(sorted(outs, key=lambda o: o.req_id), reqs))


# =============================================================================
# greedy-token equality across batch compositions
# =============================================================================

def test_tokens_independent_of_batch_composition():
    # the same request set through wildly different schedules (batch
    # size, budget, priorities shuffled by seed) produces identical
    # completed tokens — greedy decode depends only on the sequence
    arrivals = traces.make_trace("arrivals-poisson", n=40, seed=5).tolist()
    reference = None
    for max_batch, budget in ((1, 32), (4, 128), (8, 512)):
        s, outs = _run(_mk_requests(40, seed=7), arrivals,
                       cfg=SchedConfig(token_budget=budget,
                                       max_batch=max_batch))
        toks = {o.req_id: o.tokens for o in outs
                if o.status == ST_COMPLETED}
        assert toks, "workload must complete something"
        if reference is None:
            reference = toks
        else:
            for rid in toks.keys() & reference.keys():
                assert toks[rid] == reference[rid]


def test_scheduler_matches_sync_throughput_when_unconstrained():
    # no deadlines, one class, budget never binding: the scheduler
    # degenerates to the old FIFO loop's makespan on the same trace
    reqs = [SchedRequest(req_id=i, prompt_len=8, max_new=4, priority=0)
            for i in range(20)]
    arrivals = [i // 4 for i in range(20)]
    s, outs = _run(reqs, arrivals,
                   cfg=SchedConfig(token_budget=1 << 20, max_batch=4))
    sync_fin = simulate_sync(
        [SchedRequest(req_id=i, prompt_len=8, max_new=4, priority=0)
         for i in range(20)], arrivals, max_batch=4)
    assert max(o.finish for o in outs) == max(sync_fin.values())


# =============================================================================
# bounded admission: queue bound, displacement, oversize
# =============================================================================

def test_queue_bound_displacement_and_rejects():
    adm = AdmissionConfig(queue_bound=4, age_ticks=0)
    x = SimExecutor(n_blocks=4096, block_size=16)
    s = Scheduler(x, config=SchedConfig(max_batch=1, admission=adm), seed=3)
    # fill the queue with batch-class work
    for i in range(4):
        assert s.submit(SchedRequest(req_id=i, prompt_len=8, priority=2))
    assert len(s.queue) == 4
    # equal class on a full queue: rejected, never displaces
    assert not s.submit(SchedRequest(req_id=10, prompt_len=8, priority=2))
    assert s.outcomes[10].status == ST_REJECTED
    assert s.outcomes[10].reason == R_QUEUE_FULL
    # strictly-better class displaces the worst batch entry
    assert s.submit(SchedRequest(req_id=11, prompt_len=8, priority=0))
    assert len(s.queue) == 4
    displaced = [o for o in s.outcomes.values()
                 if o.status == ST_SHED and o.reason == R_DISPLACED]
    assert len(displaced) == 1 and displaced[0].priority == 2


def test_oversize_rejected_up_front():
    x = SimExecutor(n_blocks=8, block_size=16)  # 128-token pool
    s = Scheduler(x, seed=0)
    assert not s.submit(SchedRequest(req_id=0, prompt_len=500, max_new=8))
    assert s.outcomes[0].status == ST_REJECTED
    assert s.outcomes[0].reason == R_OVERSIZE
    # a feasible request on the same scheduler still completes
    assert s.submit(SchedRequest(req_id=1, prompt_len=16, max_new=2))
    outs = s.run([], [])
    assert s.outcomes[1].status == ST_COMPLETED


def test_block_watermark_never_overcommits():
    # tiny pool, fat sequences: prefills must throttle so pinned blocks
    # never exceed capacity, yet everything eventually completes
    x = SimExecutor(n_blocks=8, block_size=16)
    peak = 0
    orig = x.prefill

    def spying_prefill(r):
        nonlocal peak
        tok = orig(r)
        peak = max(peak, x.used)
        return tok
    x.prefill = spying_prefill
    reqs = [SchedRequest(req_id=i, prompt_len=30, max_new=2, priority=0)
            for i in range(10)]
    s, outs = _run(reqs, [0] * 10, x=x,
                   cfg=SchedConfig(token_budget=1 << 20, max_batch=8))
    assert all(o.status == ST_COMPLETED for o in outs)
    assert peak <= x.n_blocks


class _PoolExecutor(SimExecutor):
    """The engine's surface: ``n_blocks`` is the per-sequence cap, the
    pool holds ``pool_blocks``, and a sequence pins its blocks as its
    tokens arrive (prompt at prefill, one more each block boundary)."""

    def __init__(self, pool_blocks, per_seq, block_size=16):
        super().__init__(n_blocks=per_seq, block_size=block_size)
        self.pool_blocks = pool_blocks
        self._len = {}
        self.peak = 0

    def _pin(self):
        self.used = sum(-(-n // self.block_size) for n in self._len.values())
        self.peak = max(self.peak, self.used)

    def free_fraction(self):
        return 1.0 - self.used / self.pool_blocks

    def prefill(self, r):
        tok = super().prefill(r)
        self._len[r.req_id] = r.prompt_len
        self._pin()
        return tok

    def decode(self, ids):
        for rid in ids:
            self._len[rid] += 1
        self._pin()
        return super().decode(ids)

    def release(self, req_id):
        super().release(req_id)
        self._len.pop(req_id)
        self._pin()


def test_block_watermark_counts_the_pools_blocks():
    # each request may pin most of the per-sequence cap, but the pool
    # holds many sequences: the batch fills in the first tick
    x = _PoolExecutor(pool_blocks=4096, per_seq=8)
    s = Scheduler(x, config=SchedConfig(token_budget=1 << 20, max_batch=8))
    for i in range(8):
        assert s.submit(SchedRequest(req_id=i, prompt_len=100, max_new=20))
    s.tick()
    assert len(s.active) == 8


def test_block_watermark_reserves_what_live_sequences_will_pin():
    # pins grow as answers decode: admission keeps room for the blocks
    # live sequences have yet to pin, so a full pool is never over-pinned
    x = _PoolExecutor(pool_blocks=8, per_seq=8)
    reqs = [SchedRequest(req_id=i, prompt_len=16, max_new=60, priority=0)
            for i in range(6)]
    s, outs = _run(reqs, [0] * 6, x=x,
                   cfg=SchedConfig(token_budget=1 << 20, max_batch=4))
    assert all(o.status == ST_COMPLETED for o in outs)
    assert x.peak <= x.pool_blocks


# =============================================================================
# degraded-mode backpressure (sim chaos)
# =============================================================================

def test_degraded_mode_sheds_lowest_and_recovers():
    clock = Clock()
    x = SimExecutor(n_blocks=4096, block_size=16, clock=clock,
                    degraded_ticks=range(2, 8))
    s = Scheduler(x, config=SchedConfig(token_budget=256, max_batch=2),
                  clock=clock, seed=1)
    reqs = (
        [SchedRequest(req_id=i, prompt_len=8, max_new=3, priority=0)
         for i in range(4)]
        + [SchedRequest(req_id=10 + i, prompt_len=8, max_new=3, priority=1)
           for i in range(4)]
        + [SchedRequest(req_id=20 + i, prompt_len=8, max_new=3, priority=2)
           for i in range(4)])
    outs = s.run(reqs, [0, 0, 3, 3, 0, 0, 3, 3, 0, 0, 3, 3])
    by_id = {o.req_id: o for o in outs}
    # batch-class work queued while degraded is shed with the degraded code
    degraded_sheds = [o for o in outs
                      if o.status == ST_SHED and o.reason == R_DEGRADED]
    assert degraded_sheds and all(o.priority == 2 for o in degraded_sheds)
    # standard-class work is paused (not shed) and completes after recovery
    mids = [by_id[10 + i] for i in range(4)]
    assert all(o.status == ST_COMPLETED for o in mids)
    assert all(o.finish >= 8 or o.finish <= 2 for o in mids)
    # interactive work keeps flowing throughout
    assert all(by_id[i].status == ST_COMPLETED for i in range(4))
    # recovery restores admission for new batch-class work
    x2 = SimExecutor(n_blocks=4096, block_size=16, clock=clock)
    s.x = x2
    late = SchedRequest(req_id=99, prompt_len=8, max_new=2, priority=2)
    assert s.submit(late)
    s.run([], [])
    assert s.outcomes[99].status == ST_COMPLETED


# =============================================================================
# multi-tenant fair share
# =============================================================================

def test_tenant_fair_share_band():
    # two tenants, equal weight, saturating equal demand at one priority:
    # completed tokens stay within a fairness band at every prefix
    reqs = []
    for i in range(60):
        reqs.append(SchedRequest(req_id=i, prompt_len=16, max_new=4,
                                 priority=1,
                                 tenant="a" if i % 2 == 0 else "b"))
    s, outs = _run(reqs, [0] * 60,
                   cfg=SchedConfig(token_budget=64, max_batch=2))
    starts = [e for e in s.schedule_log if e[0] == "start"]
    a = b = 0
    for e in starts:
        if e[2] % 2 == 0:
            a += 1
        else:
            b += 1
        assert abs(a - b) <= 2, "dispatch order must interleave tenants"


def test_tenant_weights_skew_share():
    adm = AdmissionConfig(queue_bound=256,
                          tenant_weights={"big": 3.0, "small": 1.0})
    reqs = [SchedRequest(req_id=i, prompt_len=16, max_new=4, priority=1,
                         tenant="big" if i % 2 == 0 else "small")
            for i in range(40)]
    cfg = SchedConfig(token_budget=32, max_batch=1, admission=adm)
    s, _ = _run(reqs, [0] * 40, cfg=cfg)
    first = [e[2] % 2 == 0 for e in s.schedule_log
             if e[0] == "start"][:16]
    big_share = sum(first) / len(first)
    assert big_share >= 0.6, f"weighted tenant got {big_share:.2f}"


# =============================================================================
# hypothesis: structural invariants over random workloads
# =============================================================================

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # optional dev dependency
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:
    req_strategy = st.lists(
        st.tuples(st.integers(1, 64),     # prompt_len
                  st.integers(1, 8),      # max_new
                  st.integers(0, 2),      # priority
                  st.integers(0, 40),     # deadline slack (0 = none)
                  st.sampled_from(("a", "b", "c")),   # tenant
                  st.integers(0, 20)),    # arrival tick
        min_size=1, max_size=60)

    @settings(max_examples=40, deadline=None)
    @given(spec=req_strategy, seed=st.integers(0, 3),
           queue_bound=st.integers(2, 16), max_batch=st.integers(1, 6))
    def test_invariants_random_workloads(spec, seed, queue_bound,
                                         max_batch):
        reqs, arrivals = [], []
        for i, (plen, mnew, pri, slack, tenant, arr) in enumerate(spec):
            reqs.append(SchedRequest(
                req_id=i, prompt_len=plen, max_new=mnew, priority=pri,
                deadline=(arr + slack) if slack else 0, tenant=tenant))
            arrivals.append(arr)
        adm = AdmissionConfig(queue_bound=queue_bound, age_ticks=16)
        cfg = SchedConfig(token_budget=128, max_batch=max_batch,
                          admission=adm)
        clock = Clock()
        x = SimExecutor(n_blocks=1 << 14, block_size=16, clock=clock)
        s = Scheduler(x, config=cfg, clock=clock, seed=seed)
        # drive submit/tick by hand so the queue bound is checked per tick
        order = sorted(range(len(reqs)), key=lambda i: (arrivals[i], i))
        pos = 0
        for _ in range(2000):
            while pos < len(order) and \
                    arrivals[order[pos]] <= clock.now:
                s.submit(reqs[order[pos]])
                pos += 1
            s.tick()
            assert len(s.queue) <= queue_bound
            assert len(s.active) <= max_batch
            if pos == len(order) and not s.queue and not s.active:
                break
        # terminal trichotomy: every request reaches exactly one end state
        assert len(s.outcomes) == len(reqs)
        assert len(s.order) == len(set(s.order)) == len(reqs)
        for r in reqs:
            o = s.outcomes[r.req_id]
            assert o.status in (ST_COMPLETED, ST_SHED, ST_REJECTED)
            if o.status == ST_COMPLETED:
                assert len(o.tokens) == r.max_new
                if r.deadline:
                    assert o.finish <= r.deadline
            else:
                assert o.reason != 0 and not o.tokens
        assert x.used == 0  # all blocks released


# =============================================================================
# admission-queue unit behaviour
# =============================================================================

def test_aging_promotes_ordering_only():
    adm = AdmissionConfig(age_ticks=4)
    q = AdmissionQueue(adm, seed=0)
    old = SchedRequest(req_id=0, prompt_len=1, priority=2, arrival=0)
    new = SchedRequest(req_id=1, prompt_len=1, priority=1, arrival=10)
    q.offer(old, 0)
    q.offer(new, 10)
    # at t=10 the old batch request has aged 2 classes: effective 0
    assert q.effective_class(old, 10) == 0
    assert q.peek_best(10) is old
    # ...but its declared class (metrics identity) is untouched
    assert old.priority == 2


def test_shed_expired_is_exact():
    q = AdmissionQueue(AdmissionConfig(), seed=0)
    # service_ticks(max_new=4) == 3: at t=0 a deadline of 3 is feasible,
    # 2 is not
    ok = SchedRequest(req_id=0, prompt_len=1, max_new=4, deadline=3)
    late = SchedRequest(req_id=1, prompt_len=1, max_new=4, deadline=2)
    q.offer(ok, 0)
    q.offer(late, 0)
    expired = q.shed_expired(0)
    assert [r.req_id for r in expired] == [1]
    assert len(q) == 1


# =============================================================================
# slow tier: the real engine through the same scheduler
# =============================================================================

@pytest.fixture(scope="module")
def small_model():
    import jax
    from repro.configs import get_config, reduced
    from repro.models.model import build
    cfg = reduced(get_config("granite-3-8b"))
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    return api, params


@pytest.mark.slow
def test_engine_scheduler_tokens_match_sync(small_model):
    from repro.serving.engine import Request, ServingEngine
    api, params = small_model
    rng = np.random.default_rng(11)
    reqs = [Request(i, list(rng.integers(0, api.cfg.vocab, 20)), max_new=4,
                    priority=i % 2, tenant=f"t{i % 2}")
            for i in range(5)]
    eng = ServingEngine(api, params, block_size=8, hbm_blocks=32,
                        max_batch=2)
    sync = {c.req_id: c.tokens for c in eng.run_sync(
        [Request(r.req_id, list(r.prompt), max_new=r.max_new)
         for r in reqs])}
    outs = eng.run(reqs, arrivals=[0, 0, 1, 2, 3], seed=5)
    assert all(c.status == ST_COMPLETED for c in outs)
    for c in outs:
        assert c.tokens == sync[c.req_id], f"req {c.req_id}"
    # the scheduler's decision stream lands in the engine's obs sink
    snap = eng.obs_snapshot()
    assert sum(v for k, v in snap.counters.items()
               if k.startswith("sched_admitted_total")) == 5
    assert {e["kind"] for e in snap.events} >= {"admit", "batch"}
    # per-tenant kvcache attribution rode along with the lookups
    assert any(k.startswith("pool_tenant_lookups_total")
               for k in snap.counters)


@pytest.mark.slow
def test_engine_chaos_degraded_shed_and_recovery(small_model):
    from repro.faults import (
        IO_ERROR, SHARD_LOSS, FaultPlan, FaultSpec, RetryPolicy,
    )
    from repro.serving.engine import Request, ServingEngine
    api, params = small_model
    rng = np.random.default_rng(13)
    prompts = [list(rng.integers(0, api.cfg.vocab, 24)) for _ in range(8)]

    def mk_reqs():
        return [Request(i, list(p), max_new=3, priority=(2 if i >= 6 else 0))
                for i, p in enumerate(prompts)]

    # fault-free reference
    # 12 blocks/shard: worst-case key-hash skew (2 active seqs x 4
    # blocks all in one shard) still leaves evictable slots — per-shard
    # pinned exhaustion would spin the allocator, which is exactly the
    # oversize hazard the scheduler can only police globally
    eng0 = ServingEngine(api, params, block_size=8, hbm_blocks=24,
                         max_batch=2, n_shards=2)
    ref = {c.req_id: c.tokens for c in eng0.run_sync(mk_reqs())}

    # chaos: an IO_ERROR storm trips the breaker mid-run (the pool swaps
    # under hbm pressure), a SHARD_LOSS lands mid-batch, retries off
    plan = FaultPlan(7, [
        FaultSpec(SHARD_LOSS, at=(6,), shard=0),
        FaultSpec(IO_ERROR, prob=1.0),
    ])
    eng = ServingEngine(api, params, block_size=8, hbm_blocks=24,
                        max_batch=2, n_shards=2, faults=plan,
                        io_retry=RetryPolicy(max_retries=0))
    outs = eng.run(mk_reqs(), arrivals=list(range(8)), seed=2)
    by_id = {c.req_id: c for c in outs}
    assert len(outs) == 8
    # completed tokens are fault-oblivious (read-through refills from
    # prefill; greedy decode is unaffected)
    completed = [c for c in outs if c.status == ST_COMPLETED]
    assert completed
    for c in completed:
        assert c.tokens == ref[c.req_id], f"req {c.req_id}"
    # the breaker opened at some point: the incident trail has the
    # degraded transition, and if batch-class work was queued while
    # degraded it was shed with the degraded reason
    snap = eng.obs_snapshot()
    kinds = {e["kind"] for e in snap.events}
    assert "degraded" in kinds
    sched = eng._last_scheduler
    for o in sched.outcomes.values():
        if o.status == ST_SHED:
            assert o.reason in (R_DEGRADED, R_DEADLINE, R_DISPLACED)
    # recovery restores admission: a fresh batch-class request completes
    # once the breaker probes back to healthy
    if not eng.degraded:
        late = eng.run([Request(100, prompts[0], max_new=2, priority=2)])
        assert late[0].status == ST_COMPLETED
        assert late[0].tokens == ref[0][:2]
