"""Serving runtime: continuous batching over the Clock2Q+-paged KV pool.

Flow per request:
  submit -> admission control (bounded queue, priority classes, SLO
  deadlines — repro.serving.scheduler) -> prefix-cache lookup (shared
  full blocks hit; correlated references!) -> prefill only the blocks
  that missed -> decode loop with paged attention (block-table gather)
  -> release (blocks stay cached, unpinned, for future prefix hits).

``run()`` is a thin client of the ``Scheduler``: batch formation,
backpressure (free-block watermarks + the faults ``degraded`` flag) and
shedding all live there; this module only knows how to execute a
prefill/decode/release against the model (``EngineExecutor``).  The old
synchronous loop survives as ``run_sync`` — a compat shim and the
reference the scheduler's greedy tokens are locked against.

Under HBM pressure the Clock2Q+ policy evicts cold blocks to the host
tier; dirty (HBM-only) blocks are flushed by the watermark flusher before
they become evictable, exactly as §4.1.3 prescribes.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obs_mod
from repro.kvcache.manager import PagedKVManager
from repro.kvcache.pool import BlockPool
from repro.models import transformer as T
from repro.models.model import ModelAPI
from repro.obs.spans import span
from repro.serving.admission import ST_COMPLETED, SchedRequest
from repro.serving.scheduler import SchedConfig, Scheduler


@dataclasses.dataclass
class Request:
    """One serving request.  ``priority``/``deadline``/``tenant`` feed
    the scheduler (deadline in virtual ticks from submission; 0 = no
    SLO); the defaults reproduce the pre-scheduler behaviour."""

    req_id: int
    prompt: List[int]
    max_new: int = 16
    priority: int = 1
    deadline: int = 0
    tenant: str = "default"


@dataclasses.dataclass
class Completion:
    """Terminal record: ``status`` is completed / shed / rejected (only
    completed carries tokens).  Oversized prompts — more blocks than the
    pool could ever pin — are now an explicit ``rejected`` completion
    instead of a silent drop."""

    req_id: int
    tokens: List[int]
    status: str = ST_COMPLETED


class ServingEngine:
    """Single-host engine for the dense/vlm/moe families (the paged-KV
    families); greedy sampling."""

    def __init__(self, api: ModelAPI, params, *, block_size: int = 16,
                 hbm_blocks: int = 64, max_batch: int = 8,
                 max_blocks_per_seq: int = 64, n_shards: int = 0,
                 max_hbm_blocks: int = 0, rebalance_headroom: float = 1.0,
                 autotune=False, faults=None, io_retry=None,
                 replicate: bool = False, journal_dir=None, obs=None):
        assert api.cfg.family in ("dense", "vlm", "moe"), \
            "paged serving targets the attention-KV families"
        self.api = api
        self.cfg = api.cfg
        self.params = params
        # rebalance_headroom > 1 (or max_hbm_blocks slack) is what lets
        # the sharded policy actually move capacity between shards — at
        # the cost of preallocating that many more HBM blocks
        # autotune=True/dict turns on the OnlineTuner backend: the block
        # pool's replacement knobs (correlation window, queue fractions)
        # then track the serving workload online (repro.tuning).
        # faults= threads a repro.faults FaultPlan through the pool's
        # host-IO swap path; under sustained IO failure the pool sheds to
        # read-through and the engine keeps answering (misses refill from
        # prefill), with queue depth still bounded by max_batch.
        # replicate= arms per-shard write-ahead journaling + hot-standby
        # replication (journal_dir=None keeps it in memory): shard loss
        # then promotes the standby instead of cold-rewarming.
        self.pool = BlockPool(api.cfg, hbm_blocks, block_size,
                              dtype=jnp.dtype(api.cfg.dtype),
                              n_shards=n_shards,
                              max_hbm_blocks=max_hbm_blocks,
                              rebalance_headroom=rebalance_headroom,
                              autotune=autotune, faults=faults,
                              io_retry=io_retry, replicate=replicate,
                              journal_dir=journal_dir)
        self.mgr = PagedKVManager(api.cfg, self.pool)
        self.max_batch = max_batch
        self.max_blocks = max_blocks_per_seq
        # engine-tier telemetry (pool/policy/tuner keep their own sinks;
        # obs_snapshot() merges the whole stack)
        self.obs = obs_mod.ObsSink(src="serving") if obs is None else obs
        self._c_requests = self.obs.counter(
            "serve_requests_total", (), "requests completed").labels()
        self._c_tokens = self.obs.counter(
            "serve_tokens_total", (), "tokens generated (incl. the "
            "prefill token)").labels()
        self._h_latency = self.obs.histogram(
            "serve_request_latency_seconds", (),
            "admit -> completion wall time per request").labels()
        self._h_decode = self.obs.histogram(
            "serve_decode_step_seconds", (),
            "one batched decode step, wall time").labels()
        depth_fam = self.obs.gauge(
            "serve_queue_depth", ("stage",),
            "requests pending admission / actively decoding")
        self._g_pending = depth_fam.labels("pending")
        self._g_active = depth_fam.labels("active")
        self._admit_ts: Dict[int, float] = {}
        # an expert model's programs also count the token-expert pairs of
        # the real rows (moe.moe_apply), fetched with the logits; others
        # take no row count and return no counts
        self._experts = api.cfg.family == "moe"
        self._step_counts = None
        if self._experts:
            self._init_expert_counters()

        # named, so the device trace shows jit_decode_step/jit_prefill_step
        def decode_step(params, toks, pools, bt, lens, sid, soff, *n_valid):
            return T.forward_decode_paged(api.cfg, params, toks, pools, bt,
                                          lens, sid, soff, *n_valid)

        # prompts are padded to block_size buckets so prefill compiles
        # once per bucket, not once per prompt length
        def prefill_step(params, toks, *n_valid):
            return T.prefill_paged(api.cfg, params, toks, *n_valid)

        self._decode_fn = jax.jit(decode_step)
        self._prefill_fn = jax.jit(prefill_step)

    def _init_expert_counters(self) -> None:
        cfg = self.cfg
        routed = self.obs.counter(
            "serve_moe_routed_total", ("where",), "token-expert pairs of "
            "served tokens routed to experts held here or elsewhere")
        self._c_held = routed.labels("held")
        self._c_away = routed.labels("away")
        per = self.obs.counter(
            "serve_moe_expert_tokens_total", ("expert",), "token-expert "
            "pairs routed to each held expert, summed over layers")
        self._c_expert = [per.labels(str(cfg.expert_first + e))
                          for e in range(cfg.n_held)]
        self._g_expert_max = self.obs.gauge(
            "serve_moe_expert_tokens_max", (), "the most tokens one held "
            "expert took in one layer of the last decode step").labels()

    def _count_experts(self, counts: np.ndarray, decode: bool) -> None:
        """Add a program's (layers, n_held + 1) token-expert counts."""
        per = counts.sum(axis=0)
        for c, n in zip(self._c_expert, per[:-1]):
            c.value += int(n)
        self._c_held.value += int(per[:-1].sum())
        self._c_away.value += int(per[-1])
        if decode:
            self._g_expert_max.set(float(counts[:, :-1].max()))

    def _fetch(self, x, counts, decode: bool) -> np.ndarray:
        """``x`` on the host, with the step's expert counts where the
        program made them (one transfer)."""
        if counts is None:
            return np.asarray(x)
        x, counts = jax.device_get((x, counts))
        self._count_experts(counts, decode)
        return x

    # -- prefill ------------------------------------------------------------------
    def _prefill_into_pool(self, st, fill_blocks: List[int]) -> int:
        """Run the dense prefill, write the missing blocks' cache entries,
        and return the first generated token (greedy).  NOTE: prefix-cache
        hits avoid block WRITES and deduplicate HBM (two sequences share
        physical blocks); logits still require the full forward here —
        suffix-only chunked prefill is future work."""
        n_real = len(st.tokens)
        pad = (-n_real) % self.pool.bs  # length bucketing (one compile
        toks = list(st.tokens) + [0] * pad  # per bucket, not per length)
        with span("repro.model.prefill"):
            toks = jnp.asarray(toks, jnp.int32)[None]
            n_valid = (jnp.int32(n_real),) if self._experts else ()
            logits, caches, counts = self._prefill_fn(self.params, toks,
                                                      *n_valid)
        bs = self.pool.bs
        with span("repro.kv.write_blocks"):
            arrays = [a[:, 0] for a in caches]  # (L, S, ...) each
            for b in fill_blocks:
                lo, hi = b * bs, min((b + 1) * bs, len(st.tokens))
                blocks = []
                for a in arrays:
                    blk = jnp.zeros((self.cfg.n_layers, bs) + a.shape[2:],
                                    a.dtype)
                    blocks.append(blk.at[:, :hi - lo].set(a[:, lo:hi]))
                self.pool.write_block(st.slots[b], *blocks,
                                      key=st.block_keys[b])
        with span("repro.engine.sample"):
            return int(self._fetch(jnp.argmax(logits[0, n_real - 1]), counts,
                                   decode=False))

    # -- execution primitives (what the scheduler drives) ------------------------
    def _max_seq_blocks(self) -> int:
        """Blocks one sequence may ever hold: pool capacity, bounded by
        the block-table width the decode kernel was compiled for."""
        return min(self.pool.n_blocks, self.max_blocks)

    def _oversize(self, r: Request) -> bool:
        """A prompt + decode tail needing more blocks than the pool can
        pin can never be served — the old loop silently wedged on these;
        they are now rejected explicitly."""
        need = -(-(len(r.prompt) + r.max_new) // self.pool.bs)
        return need > self._max_seq_blocks()

    def _start(self, r: Request, tenant: str = "default") -> int:
        """Admit + prefill one request; returns its first token."""
        self._admit_ts[r.req_id] = time.perf_counter()
        with span("repro.engine.prefill", rid=r.req_id):
            st, fill = self.mgr.admit(r.req_id, r.prompt, tenant=tenant)
            first = self._prefill_into_pool(st, fill)
        st.out_tokens.append(first)  # from prefill logits
        return first

    def _decode_logits(self, ids: List[int]) -> jnp.ndarray:
        """One paged decode step for the sequences in ``ids`` (<=
        max_batch): each sequence's newest token (at position pos) writes
        its KV at pos and attends to [0, pos].  Returns the next-token
        logits, (len(ids), vocab)."""
        with span("repro.engine.decode_inputs"):
            toks, poss, bts, sids, soffs = [], [], [], [], []
            for rid in ids:
                st = self.mgr.seqs[rid]
                pos = st.length - 1       # position of the token processed
                toks.append(st.out_tokens[-1])
                poss.append(pos)
                slot, off = self.mgr.slot_for_pos(rid, pos)
                sids.append(slot)
                soffs.append(off)
                bts.append(self.mgr.block_table(rid, self.max_blocks))
            # pad to max_batch (one compile for all batch sizes); padded
            # rows duplicate the last row — they rewrite identical values
            while len(toks) < self.max_batch:
                toks.append(toks[-1])
                poss.append(poss[-1])
                sids.append(sids[-1])
                soffs.append(soffs[-1])
                bts.append(bts[-1])
            args = (jnp.asarray(toks, jnp.int32)[:, None],
                    self.pool.arrays,
                    jnp.asarray(np.stack(bts)), jnp.asarray(poss, jnp.int32),
                    jnp.asarray(sids, jnp.int32),
                    jnp.asarray(soffs, jnp.int32))
            if self._experts:
                args += (jnp.int32(len(ids)),)
        with span("repro.model.decode"):
            logits, pools, self._step_counts = self._decode_fn(self.params,
                                                               *args)
        self.pool.arrays = pools
        return logits[:len(ids), 0]

    def _decode_step(self, ids: List[int]) -> Dict[int, int]:
        """One greedy decode step for ``ids``.  Returns {req_id: next
        token}."""
        with span("repro.engine.decode", n=len(ids)):
            t_step = time.perf_counter()
            logits = self._decode_logits(ids)
            with span("repro.engine.sample"):
                nxt = self._fetch(jnp.argmax(logits, axis=-1),
                                  self._step_counts, decode=True)
            self._h_decode.observe(time.perf_counter() - t_step)
            out = {}
            for i, rid in enumerate(ids):
                tok = int(nxt[i])
                self.mgr.seqs[rid].out_tokens.append(tok)
                out[rid] = tok
            self.mgr.maintenance()
        return out

    def _finish(self, rid: int) -> Completion:
        """Release a completed sequence + engine-tier telemetry."""
        st = self.mgr.seqs[rid]
        done = Completion(rid, list(st.out_tokens))
        self._h_latency.observe(
            time.perf_counter() - self._admit_ts.pop(rid))
        self._c_requests.value += 1
        self._c_tokens.value += len(st.out_tokens)
        self.mgr.release(rid)
        return done

    # -- main loop: thin client of the continuous-batching scheduler -------------
    def run(self, requests: List[Request],
            arrivals: Optional[List[int]] = None, *,
            config: Optional[SchedConfig] = None,
            seed: int = 0) -> List[Completion]:
        """Serve ``requests`` through the admission-controlled scheduler
        (repro.serving.scheduler).  ``arrivals[i]`` staggers submission
        over virtual ticks (default: everything at once — the historical
        call shape); ``Request.deadline`` is interpreted relative to
        submission.  Greedy tokens are batch-composition-independent, so
        completed outputs are identical to ``run_sync`` on the same
        request set.  Returns one Completion per request — completed,
        shed, or rejected — in termination order."""
        sched = self.make_scheduler(config=config, seed=seed)
        base = sched.clock.now
        sreqs = [SchedRequest(
            req_id=r.req_id, prompt_len=len(r.prompt), max_new=r.max_new,
            priority=r.priority,
            deadline=(base + int(a or 0) + r.deadline) if r.deadline else 0,
            tenant=r.tenant, payload=r)
            for r, a in zip(requests,
                            arrivals or [0] * len(requests))]
        abs_arrivals = None if arrivals is None \
            else [base + int(a) for a in arrivals]
        outs = sched.run(sreqs, abs_arrivals)
        self._g_pending.set(float(len(sched.queue)))
        self._g_active.set(float(len(sched.active)))
        self._last_scheduler = sched
        return [Completion(o.req_id, o.tokens, status=o.status)
                for o in outs]

    def make_scheduler(self, *, config: Optional[SchedConfig] = None,
                       seed: int = 0) -> Scheduler:
        """A scheduler wired to this engine: executes on the model,
        reads backpressure from the pool (free-block watermark + the
        faults ``degraded`` flag), shares the pool's virtual IO clock
        when fault injection is armed, and reports into the engine's obs
        sink (one merged stack snapshot)."""
        cfg = config or SchedConfig(max_batch=self.max_batch)
        clock = self.pool.io_clock()
        return Scheduler(EngineExecutor(self), config=cfg, clock=clock,
                         seed=seed, obs=self.obs)

    # -- compat shim: the pre-scheduler synchronous loop --------------------------
    def run_sync(self, requests: List[Request]) -> List[Completion]:
        """The old synchronous loop: FIFO admission up to ``max_batch``,
        no priorities, no deadlines, no backpressure.  Kept as the
        reference path — the conformance tests lock the scheduler's
        greedy tokens against it — and for callers that want the
        historical semantics."""
        pending, done = [], []
        for r in requests:
            if self._oversize(r):
                done.append(Completion(r.req_id, [], status="rejected"))
            else:
                pending.append(r)
        active: Dict[int, Request] = {}
        while pending or active:
            while pending and len(active) < self.max_batch:
                r = pending.pop(0)
                self._start(r)
                active[r.req_id] = r
            for rid in [rid for rid, r in active.items()
                        if len(self.mgr.seqs[rid].out_tokens) >= r.max_new]:
                done.append(self._finish(rid))
                del active[rid]
            self._g_pending.set(float(len(pending)))
            self._g_active.set(float(len(active)))
            if not active:
                continue
            self._decode_step(sorted(active))
        return done

    def cache_mrc(self, capacities=None, **kw):
        """What-if MRC of the KV block pool at alternative HBM budgets
        (requires ``autotune=``) — see ``BlockPool.estimate_mrc``."""
        return self.pool.estimate_mrc(capacities, **kw)

    def obs_snapshot(self) -> "obs_mod.Snapshot":
        """One merged snapshot of the whole serving stack: engine
        latencies/queue depths + pool swaps + policy hit/flow counters
        (+ tuner, when autotuning)."""
        return obs_mod.merge([self.obs.snapshot(), self.pool.obs_snapshot()])

    @property
    def stats(self):
        return self.pool.stats, dict(self.pool.policy.flows)

    @property
    def degraded(self) -> bool:
        """True while the pool serves read-through (host IO shed by the
        circuit breaker under sustained injected/real failure)."""
        return self.pool.degraded


class EngineExecutor:
    """The ``Scheduler``'s executor surface over a ``ServingEngine``:
    prefill/decode/release run the model against the paged pool, and the
    capacity/backpressure reads come straight from the pool (pinned-
    block watermark, faults ``degraded`` flag)."""

    def __init__(self, eng: ServingEngine):
        self.eng = eng
        self.block_size = eng.pool.bs
        self.n_blocks = eng._max_seq_blocks()

    @property
    def pool_blocks(self) -> int:
        return self.eng.pool.n_blocks

    @property
    def degraded(self) -> bool:
        return self.eng.pool.degraded

    def free_fraction(self) -> float:
        return self.eng.pool.free_fraction()

    def prefill(self, r: SchedRequest) -> int:
        return self.eng._start(r.payload, tenant=r.tenant)

    def decode(self, ids: List[int]) -> Dict[int, int]:
        return self.eng._decode_step(ids)

    def release(self, rid: int) -> None:
        self.eng._finish(rid)
