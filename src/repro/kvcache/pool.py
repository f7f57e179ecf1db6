"""Paged KV block pool with Clock2Q+-managed HBM residency.

The block table of a paged KV cache is a metadata structure mapping
logical (sequence, block-index) -> physical HBM block — exactly the
LBN->PBN mapping of the paper (DESIGN.md §2).  A block holds, for
``block_size`` tokens in every layer, what the model's ``cache_spec``
names: K and V for GQA/MHA (``kpool``, ``vpool``), one latent row for
MLA (``latent``).  Each named array is an attribute of the pool, and
every copy path moves all of them.  The pool is two-tiered:

    HBM  (jnp arrays)  <- Clock2Q+ decides residency (ProdClock2QPlus)
    host (numpy mirror) <- eviction target ("disk"); dirty = HBM-only

Block keys are content hashes for prefix-shared full blocks (identical
prompts share physical blocks) and (seq_id, block_idx) handles for
per-sequence tail blocks.  Correlated references arise naturally: request
admission touches all prefix blocks of a sequence back-to-back.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obs_mod
from repro.core.prodcache import EMPTY, ProdClock2QPlus, drive_resize
from repro.faults import GhostJournal, HostIO, ShardReplicator, splitmix64
from repro.faults.recovery import failover as _failover
from repro.models.config import ModelConfig
from repro.obs.spans import span
from repro.shardcache import ShardedClock2QPlus


@dataclasses.dataclass
class PoolStats:
    """Point-in-time view over the pool's obs counters (compat shim —
    the ``pool_*_total`` families are the source of truth)."""
    hits: int = 0
    misses: int = 0
    swap_in: int = 0       # host -> HBM copies
    swap_out: int = 0      # HBM -> host copies (dirty evictions)
    drops: int = 0         # clean evictions (host copy already existed)

    @property
    def hit_ratio(self) -> float:
        return self.hits / max(1, self.hits + self.misses)


@jax.jit
def _gather_slots(arrays, slots):
    """Each array's blocks at ``slots``, slot-major ``(n, L, bs, ...)``,
    so each block is one contiguous row on the host.  One dynamic slice
    a block: a gather over the slot axis of a pool whose rows span
    several 128-lane tiles (the MLA latent) copies whole slices of the
    pool first."""
    return tuple(jnp.stack([jax.lax.dynamic_index_in_dim(a, s, 1, False)
                            for s in slots]) for a in arrays)


class BlockPool:
    """Fixed HBM pool of KV blocks + host tier, Clock2Q+ replacement."""

    def __init__(self, cfg: ModelConfig, n_hbm_blocks: int, block_size: int,
                 n_host_blocks: int = 0, dtype=jnp.float32, *,
                 window_frac: float = 0.5, max_hbm_blocks: int = 0,
                 n_shards: int = 0, rebalance_headroom: float = 1.0,
                 autotune=False, faults=None, io_retry=None,
                 journal_every: int = 1024, replicate: bool = False,
                 journal_dir: Optional[str] = None,
                 lag_threshold: int = 4096, replica_poll: int = 256,
                 obs=None):
        self.cfg = cfg
        self.bs = block_size
        self.n_blocks = n_hbm_blocks
        # n_shards > 1 selects the sharded concurrent policy backend
        # (repro.shardcache); the pool API is identical either way.
        # rebalance_headroom=1.0 keeps the block arrays at the stated HBM
        # budget (cross-shard borrowing then needs max_hbm_blocks slack);
        # >1 preallocates extra blocks per shard for rebalancing.
        tkw = dict(autotune) if isinstance(autotune, dict) else {}
        # queue-fraction candidates need preallocation headroom (extra
        # payload slots, hence extra HBM blocks) so the tuner's choices
        # are realizable instead of silently clamped
        seg_kw = dict(
            max_small_frac=max(tkw.get("small_fracs") or (0.0,)),
            min_small_frac=min(tkw.get("small_fracs") or (1.0,)),
            max_ghost_frac=max(tkw.get("ghost_fracs") or (0.0,)))
        if n_shards > 1:
            self.policy = ShardedClock2QPlus(
                n_hbm_blocks, n_shards=n_shards, track_io=True,
                window_frac=window_frac,
                max_capacity=max(n_hbm_blocks, max_hbm_blocks),
                rebalance_headroom=rebalance_headroom, **seg_kw)
        else:
            self.policy = ProdClock2QPlus(
                n_hbm_blocks, track_io=True, window_frac=window_frac,
                max_capacity=max(n_hbm_blocks, max_hbm_blocks), **seg_kw)
        # the block arrays cover the policy's full payload-handle space
        # (>= n_hbm_blocks when resize headroom / sharding is configured)
        spec = cfg.cache_spec()
        self.names = tuple(spec)
        for name, shape in spec.items():
            setattr(self, name, jnp.zeros(
                (cfg.n_layers, self.policy.n_slots, block_size) + shape,
                dtype))
        self.block_bytes = sum(a.nbytes // a.shape[1] for a in self.arrays)
        # a host copy of a block: one array per name, in ``names`` order
        self.host: Dict[int, Tuple[np.ndarray, ...]] = {}
        self.n_host_blocks = n_host_blocks or 4 * n_hbm_blocks
        # pool-tier telemetry (the policy keeps its own sink; merged by
        # obs_snapshot()).  ``stats`` is a compat view over these.
        self.obs = obs_mod.ObsSink(src="pool") if obs is None else obs
        lookup_fam = self.obs.counter("pool_lookups_total", ("result",),
                                      "block lookups by outcome")
        self._c_hit = lookup_fam.labels("hit")
        self._c_miss = lookup_fam.labels("miss")
        swap_fam = self.obs.counter("pool_swaps_total", ("dir",),
                                    "HBM<->host block copies")
        self._c_swap_in = swap_fam.labels("in")
        self._c_swap_out = swap_fam.labels("out")
        self._c_writes = self.obs.counter(
            "pool_block_writes_total", (), "prefill block writes (one "
            "eager whole-pool update per pool array each)").labels()
        self._c_flushes = self.obs.counter(
            "pool_flushes_total", (), "flushes that copied a dirty block "
            "to host (batched per flusher pass on the unhardened path, "
            "one blocking device->host copy per pool array each under "
            "faults=)").labels()
        self._c_flush_batches = self.obs.counter(
            "pool_flush_batches_total", (), "device->host transfers the "
            "flusher made (one per pass that copied a block; unhardened "
            "path)").labels()
        self._c_drop = self.obs.counter(
            "pool_drops_total", (), "clean evictions (host copy "
            "already existed)").labels()
        self._g_host = self.obs.gauge(
            "pool_host_blocks", (), "blocks mirrored in the host "
            "tier").labels()
        self.obs.gauge("pool_block_bytes", (), "device bytes of one block "
                       "(every layer, every cache array)").labels().set(
                           float(self.block_bytes))
        self.obs.on_collect(lambda: self._g_host.set(float(len(self.host))))
        # per-tenant lookup attribution (serving threads tenant labels
        # through admissions; None keeps the historical unlabeled keys)
        self._tenant_fam = self.obs.counter(
            "pool_tenant_lookups_total", ("tenant", "result"),
            "block lookups attributed to serving tenants")
        # hardened host IO (repro.faults).  faults=None keeps the
        # historical direct swap path with zero instrumentation; passing
        # a plan (NullPlan in production) routes every host-block copy
        # through HostIO — retries/backoff/deadlines, a circuit breaker
        # that sheds to read-through under sustained failure, torn-write
        # quarantine, and (on a sharded policy) a GhostJournal captured
        # every ``journal_every`` lookups so SHARD_LOSS faults trigger
        # automatic failover.
        self._io: Optional[HostIO] = None
        self._journal: Optional[GhostJournal] = None
        self._corrupt: set = set()
        self._lookups = 0
        self.journal_every = journal_every
        if faults is not None:
            self._io = HostIO(plan=faults, retry=io_retry, obs=self.obs)
            self._c_torn = self.obs.counter(
                "pool_torn_writes_total", (), "swap-outs persisted torn "
                "(PARTIAL_WRITE) and quarantined").labels()
            self._c_corrupt = self.obs.counter(
                "pool_corrupt_dropped_total", (), "quarantined host "
                "copies dropped at swap-in (read repair: refill from "
                "origin)").labels()
            self._c_lost = self.obs.counter(
                "pool_lost_writes_total", (), "dirty evictions whose "
                "swap-out failed — content refills from origin").labels()
            g_deg = self.obs.gauge(
                "pool_degraded", (), "1 while the breaker has shed host "
                "IO (read-through mode)").labels()
            self.obs.on_collect(
                lambda: g_deg.set(1.0 if self._io.degraded else 0.0))
            if hasattr(self.policy, "shards"):
                self._journal = GhostJournal(self.policy)
        # hot-standby replication (repro.faults.replica): a write-ahead
        # delta journal per shard plus a bounded-staleness standby that
        # tails it, polled from the lookup path every ``replica_poll``
        # lookups.  On shard loss, failover_shard() promotes the standby
        # (exact state, no synthetic re-accesses) while its lag is
        # within ``lag_threshold``; past it, the ghost rewarm above is
        # the fallback.  journal_dir=None replicates in memory.
        self._replicator: Optional[ShardReplicator] = None
        self.replica_poll = replica_poll
        if replicate:
            if not hasattr(self.policy, "shards"):
                raise ValueError("replicate= needs a sharded policy "
                                 "(n_shards > 1)")
            self._replicator = ShardReplicator(
                self.policy, journal_dir, lag_threshold=lag_threshold,
                clock=self._io.clock if self._io is not None else None,
                obs=self.obs)
        # autotune=True (defaults) or a dict of OnlineTuner kwargs: the
        # tuner observes the block-key stream through lookup() and
        # retargets the policy's window / queue fractions online via the
        # live-resize protocol.  Retuning never changes the preallocated
        # payload-handle space, so the block arrays above stay valid.
        self.tuner = None
        if autotune:
            from repro.tuning import OnlineTuner
            tkw.setdefault("retune_every", max(1024, 32 * n_hbm_blocks))
            self.tuner = OnlineTuner(self.policy, obs=self.obs, **tkw)

    @property
    def arrays(self) -> Tuple[jnp.ndarray, ...]:
        """The block arrays, in ``names`` order."""
        return tuple(getattr(self, n) for n in self.names)

    @arrays.setter
    def arrays(self, arrays) -> None:
        for n, a in zip(self.names, arrays):
            setattr(self, n, a)

    @property
    def stats(self) -> PoolStats:
        """The historical stats surface, derived from the obs counters."""
        return PoolStats(hits=self._c_hit.value, misses=self._c_miss.value,
                         swap_in=self._c_swap_in.value,
                         swap_out=self._c_swap_out.value,
                         drops=self._c_drop.value)

    def obs_snapshot(self) -> "obs_mod.Snapshot":
        """Merged pool + replacement-policy (+ tuner, which shares the
        pool's sink) telemetry."""
        pol_snap = self.policy.obs_snapshot() \
            if hasattr(self.policy, "obs_snapshot") \
            else self.policy.obs.snapshot()
        return obs_mod.merge([self.obs.snapshot(), pol_snap])

    # -- residency ------------------------------------------------------------
    def lookup(self, key: int, pin: bool = True,
               tenant: Optional[str] = None) -> Tuple[int, bool]:
        """Returns (hbm_slot, needs_fill).  On miss, a slot is allocated
        (evicting per Clock2Q+); if the key has a host copy it is swapped
        in; otherwise the caller must fill the block (needs_fill=True).
        A failed/shed/quarantined swap-in degrades to read-through: the
        caller refills from the origin exactly as for a cold miss.
        ``tenant`` additionally attributes the lookup to a serving
        tenant (``pool_tenant_lookups_total{tenant,result}``)."""
        if self._io is not None or self._replicator is not None:
            self._lookups += 1
            if self._io is not None and self._io.pending_shard_loss:
                self._drain_shard_loss()
            if self._journal is not None and \
                    self._lookups % self.journal_every == 0:
                self._journal.capture(self.policy)
            if self._replicator is not None and \
                    self._lookups % self.replica_poll == 0:
                self._replicator.poll()
        if self.tuner is not None:
            self.tuner.observe(key)
        r = self.policy.access(key, pin=pin)
        if tenant is not None:
            self._tenant_fam.labels(
                tenant, "hit" if r.hit else "miss").value += 1
        if r.hit:
            self._c_hit.value += 1
            return r.block, False
        self._c_miss.value += 1
        if r.evicted_key != EMPTY:
            self._on_evict(r.evicted_key, r.evicted_block)
        if key in self.host and self._swap_in(key, r.block):
            self.policy.io_done(key)
            return r.block, False
        # brand-new block (or unreadable host copy): contents will be
        # written by prefill/decode
        return r.block, True

    def _on_evict(self, key: int, slot: int) -> None:
        """HBM eviction: dirty blocks (no host copy) are swapped out.
        A failed swap-out loses the content (the next access refills from
        origin); a torn one (PARTIAL_WRITE) is quarantined for read
        repair at the next swap-in."""
        if key in self.host:
            self._c_drop.value += 1
            return
        if len(self.host) >= self.n_host_blocks:
            return
        if self._io is None:
            self._copy_out(key, slot)
            self._c_swap_out.value += 1
            return
        res = self._io.run("swap_out", key,
                           lambda: self._copy_out(key, slot))
        if not res.ok:
            self._c_lost.value += 1
            return
        if res.corrupt:
            self._corrupt.add(key)
            self._c_torn.value += 1
        self._c_swap_out.value += 1

    def _copy_out(self, key: int, slot: int) -> None:
        self.host[key] = tuple(np.asarray(a[:, slot]) for a in self.arrays)

    def _copy_in(self, key: int, slot: int) -> None:
        self._set_block(slot, [jnp.asarray(h) for h in self.host[key]])

    def _set_block(self, slot: int, blocks) -> None:
        """One eager whole-pool update per array, each array replaced
        before the next is copied (one pool array's worth of headroom)."""
        for n, b in zip(self.names, blocks):
            setattr(self, n, getattr(self, n).at[:, slot].set(b))

    def _swap_in(self, key: int, slot: int) -> bool:
        """Host -> HBM copy through the hardened path.  False = the copy
        did not happen (IO gave up, breaker shed, or the host copy was
        quarantined) — the caller serves the miss read-through."""
        if self._io is None:
            with span("repro.kv.swap_in"):
                self._copy_in(key, slot)
            self._c_swap_in.value += 1
            return True
        if key in self._corrupt:
            # read repair: the torn copy is detected here (the digest-
            # mismatch path) and dropped; the block refills from origin
            del self.host[key]
            self._corrupt.discard(key)
            self._c_corrupt.value += 1
            return False
        with span("repro.kv.swap_in"):
            res = self._io.run("swap_in", key,
                               lambda: self._copy_in(key, slot))
        if not res.ok:
            return False
        self._c_swap_in.value += 1
        return True

    def write_block(self, slot: int, *blocks: jnp.ndarray,
                    key: Optional[int] = None) -> None:
        """Fill a block after prefill: one (L, block_size, ...) array per
        pool array, in ``names`` order (k and v: (L, block_size, H, hd))."""
        self._set_block(slot, blocks)
        self._c_writes.value += 1
        if key is not None:
            self.policy.io_done(key)
            self.policy.set_dirty(key)  # HBM-only content until flushed

    def unpin(self, key: int) -> None:
        self.policy.unpin(key)

    def flush(self, key: int) -> None:
        """Mirror a dirty block to host (background flusher).  Under the
        hardened path a failed mirror leaves the block dirty, so the
        watermark flusher naturally retries it; a torn mirror is
        quarantined like any other swap-out."""
        slot = self.policy.slot_of(key)
        if slot == EMPTY:
            return
        if key not in self.host and len(self.host) < self.n_host_blocks:
            if self._io is not None:
                res = self._io.run("swap_out", key,
                                   lambda: self._copy_out(key, slot))
                if not res.ok:
                    return  # still dirty: retried by the next flusher pass
                if res.corrupt:
                    self._corrupt.add(key)
                    self._c_torn.value += 1
            else:
                self._copy_out(key, slot)
            self._c_swap_out.value += 1
            self._c_flushes.value += 1
        self.policy.clean(key)

    def run_flusher(self, max_blocks: int = 4) -> int:
        """Watermark flusher (paper §4.1.3): mirror oldest dirty blocks.
        The hardened path flushes key by key (each copy is one retried
        ``HostIO`` operation); the unhardened path copies the pass's
        blocks in one device->host transfer (``_flush_batch``)."""
        with span("repro.kv.flush"):
            dirty = self.policy.dirty_keys()[:max_blocks]
            if self._io is not None:
                for k in dirty:
                    self.flush(k)
            else:
                self._flush_batch(dirty, max_blocks)
        return len(dirty)

    def _flush_batch(self, dirty, max_blocks: int) -> None:
        """``flush`` over ``dirty`` with the copies gathered into one
        program and one fetch.  The same keys are copied, counted and
        cleaned as key-by-key flushing would: a non-resident key is
        skipped, a mirrored one only cleaned, and copies decided earlier
        in the pass count against the host tier's room.  Each block is
        cleaned after its host copy exists, in the same pass."""
        room = self.n_host_blocks - len(self.host)
        resident, copy, slots = [], [], []
        for key in dirty:
            slot = self.policy.slot_of(key)
            if slot == EMPTY:
                continue
            resident.append(key)
            if key not in self.host and len(copy) < room:
                copy.append(key)
                slots.append(slot)
        if copy:
            # padded with a repeat of a real slot, so one program serves
            # every pass of up to max_blocks copies
            idx = np.full(max_blocks, slots[-1], np.int32)
            idx[:len(slots)] = slots
            got = jax.device_get(_gather_slots(self.arrays, idx))
            if len(copy) < max_blocks:
                # the host rows are views: keep no padded row alive
                got = [g[:len(copy)].copy() for g in got]
            for i, key in enumerate(copy):
                self.host[key] = tuple(g[i] for g in got)
            self._c_flush_batches.value += 1
            self._c_swap_out.value += len(copy)
            self._c_flushes.value += len(copy)
        for key in resident:
            self.policy.clean(key)

    # -- backpressure (serving scheduler) -----------------------------------------
    def pinned_count(self) -> int:
        """Resident blocks currently pinned (unevictable) — the hard
        part of occupancy: unpinned blocks are reclaimable by Clock2Q+
        on demand, pinned ones are held by live sequences."""
        if hasattr(self.policy, "shards"):
            return sum(int((s.pin > 0).sum()) for s in self.policy.shards)
        return int((self.policy.pin > 0).sum())

    def free_fraction(self) -> float:
        """Fraction of the HBM budget not pinned — the scheduler's
        free-block watermark signal (1.0 = nothing held)."""
        return 1.0 - self.pinned_count() / max(1, self.n_blocks)

    def io_clock(self):
        """The virtual tick clock the serving scheduler should run on:
        the hardened host-IO path's clock when fault injection is armed
        (so IO backoff time and scheduler time share one axis), a fresh
        one otherwise."""
        from repro.faults.io import Clock
        return self._io.clock if self._io is not None else Clock()

    # -- faults / failover (repro.faults) -----------------------------------------
    @property
    def degraded(self) -> bool:
        """True while host IO is shed (read-through mode).  Always False
        on the uninstrumented path."""
        return self._io is not None and self._io.degraded

    def replication_lag(self, sid: int) -> int:
        """Standby lag for shard ``sid`` in journal records (0 when
        replication is off)."""
        return self._replicator.lag(sid) if self._replicator else 0

    def failover_shard(self, sid: int) -> Tuple[int, int]:
        """Lose shard ``sid`` and rebuild it.

        With replication armed (``replicate=True``) and the standby's
        lag within threshold, the standby is *promoted*: the journal
        tail is replayed past its applied LSN, its exact replacement
        state is loaded into the fresh shard, and only payloads refill
        — no synthetic re-accesses (``repro.faults.replica``).  A
        too-stale standby (or no replication) falls back to the ghost-
        journal rewarm (``repro.faults.recovery.failover``), after
        which the shard's journal is reattached at the next epoch so
        replication resumes.  Either way, readmitted keys whose
        payloads survive in the host tier are refilled directly (the
        recovery scan reads local copies, not the faulted swap path);
        the rest end up in the ghost ring and refill from origin on
        their next touch.  Returns (residents, ghosts) for rewarm,
        (refilled, demoted) for promotion.
        """
        if self._journal is None and self._replicator is None:
            raise RuntimeError("failover needs faults= (or replicate=) "
                               "and a sharded policy (n_shards > 1)")
        base = sid * self.policy.stride

        def fill(key):
            if key not in self.host or key in self._corrupt:
                return None
            return lambda local: self._copy_in(key, base + local)

        rep = self._replicator
        if rep is not None and rep.should_promote(sid):
            res = rep.promote(sid, fill=fill)
            return (res.refilled, res.demoted)
        if self._journal is None:
            raise RuntimeError("standby for shard %d is %d records "
                               "stale (threshold %d) and no ghost "
                               "journal is armed (faults=)"
                               % (sid, rep.lag(sid), rep.lag_threshold))
        out = _failover(self.policy, sid, self._journal, fill=fill)
        if rep is not None:
            rep.reattach(sid)  # resume journaling the rewarmed shard
        return out

    def _drain_shard_loss(self) -> None:
        """Apply SHARD_LOSS faults the plan injected on the IO stream.
        ``shard=-1`` specs pick the victim by hashing the op sequence the
        fault fired at (deterministic per seed)."""
        pending, self._io.pending_shard_loss = \
            self._io.pending_shard_loss, []
        if self._journal is None:
            return  # unsharded policy: nothing to lose a shard from
        n = self.policy.n_shards
        for f in pending:
            sid = f.shard if f.shard >= 0 else \
                splitmix64(self._io.plan.seed ^ f.op_seq) % n
            self.failover_shard(sid)

    # -- what-if analysis --------------------------------------------------------
    def estimate_mrc(self, capacities=None, *, rate_shift: int = 4,
                     window_fracs=None) -> Dict[int, float]:
        """Sampled MRC estimate of the recent block-key stream at
        alternative HBM budgets — what-if input for ``resize()``.
        Requires ``autotune=`` (the tuner's ring buffer is the key
        history); simulated by the registered lane engine for the live
        policy (``policy.engine_policy``), so the estimates describe the
        exact replacement machine this pool runs.  Returns
        {capacity: est. miss ratio} (NaN when the sample is empty)."""
        from repro.tuning import profiler

        if self.tuner is None:
            raise RuntimeError(
                "estimate_mrc needs autotune= — the OnlineTuner's access "
                "ring buffer is the key history it profiles")
        caps = [int(c) for c in
                (capacities or (max(1, self.n_blocks // 2), self.n_blocks,
                                2 * self.n_blocks))]
        live = self.tuner._live_config()
        wfs = tuple(window_fracs) if window_fracs else (live.window_frac,)
        configs = [dataclasses.replace(live, capacity=c, window_frac=wf)
                   for c in caps for wf in wfs]
        trace = self.tuner.recent()
        if trace.size == 0:
            return {c: float("nan") for c in caps}
        est = profiler.estimate_sweep(trace, configs, rate_shift)
        # best window per capacity: the pool would retune after a resize
        per_cap = est.reshape(len(caps), len(wfs))
        out = {c: float(np.nanmin(per_cap[i])) for i, c in enumerate(caps)}
        # what-if MRC as a gauge family: the last estimate at each
        # alternative HBM budget stays scrapeable between calls
        fam = self.obs.gauge("pool_est_miss_ratio", ("capacity",),
                             "sampled-MRC estimate at alternative HBM "
                             "budgets (last estimate_mrc call)")
        for c, mr in out.items():
            fam.labels(str(c)).set(mr)
        return out

    # -- elastic resize (paper §4.2 -> HBM budget changes) -----------------------
    def resize(self, new_n_blocks: int, steps_per_call: int = 64) -> None:
        """Retarget the HBM budget and drive all *migratable* work to
        completion.  Blocks pinned or DOING-IO beyond a shrink boundary
        cannot be drained until released — those are left pending (later
        ``resize_step``/``resize`` calls finish them) instead of spinning:
        the unpin/io_done may be waiting on this very thread."""
        self.policy.begin_resize(new_n_blocks)
        drive_resize(self.policy, steps_per_call)
