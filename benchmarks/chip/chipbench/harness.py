"""One run of one cell: set-up, the measured window, the check, the result.

The harness builds the served path the way ``repro.launch.serve`` does
(``ModelConfig`` at the configuration's sizes, ``fit_pool_blocks``,
``ServingEngine``), drives ``Scheduler.submit``/``tick`` against the wall
clock, and wraps the executor's ``prefill``/``decode``/``release`` in
host spans (``bench.<kind>`` trace annotations) that time every call.
Everything the per-layer metrics read is gathered in a ``RunData``.

What depends on the architecture comes from the configuration's family,
``families/<family>.py`` (``family``): the program's ``ModelConfig``,
the weight layout, the work a call requires, and the reference.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import heapq
import importlib.util
import json
import os
import re
import shutil
import sys
import time
import types
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from chipbench import reference, traffic, weights, work, xplane

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")
TRACE_LEAD_S = 2.0     # the profiler runs this long before the window
TRACE_WINDOW_S = 15.0  # a traced run's window: its trace is read in time
OPEN_CAP_S = 180.0     # longest the loop may take to reach its window


class NoChip(RuntimeError):
    """JAX found no accelerator the benchmark knows, or too few chips."""


# -- what a cell is made of --------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: Dict            # the configuration file
    mix: Dict             # the traffic mix file
    limits: Dict          # the cell's limits file
    metrics: Dict[str, Dict]    # name -> BENCHMARK.json entry (+ "reader")
    family: types.ModuleType    # families/<the configuration's family>.py

    @classmethod
    def load(cls, root: Path, bench_dir: Path, workload: str) -> "Cell":
        """The cell ``workload`` of ``root/BENCHMARK.json``: its
        configuration file and that file's family
        ``families/<family>.py`` (``dense`` where it names none), its
        mix ``traffic/<mix>.json``, its limits ``cells/<workload>.json``
        and a reader ``metrics/<metric>.py`` for each metric it
        reports."""
        spec = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in spec["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
        w = cells[workload]
        conf_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
        conf = json.loads((root / conf_entry["file"]).read_text())
        mix = json.loads((bench_dir / "traffic" /
                          f"{w['traffic']}.json").read_text())
        limits = json.loads((bench_dir / "cells" /
                             f"{workload}.json").read_text())
        metrics = {}
        for group in ("end_to_end", "per_layer"):
            for m in spec[group]:
                if workload in m.get("workloads", [workload]):
                    metrics[m["name"]] = dict(m, group=group, reader=_reader(
                        bench_dir / "metrics" / f"{m['name']}.py"))
        return cls(workload, w["chips"], conf, mix, limits, metrics,
                   family(bench_dir, conf.get("family", "dense")))

    def reported(self, trace: bool) -> Dict[str, Dict]:
        group = "per_layer" if trace else "end_to_end"
        return {k: m for k, m in self.metrics.items() if m["group"] == group}


def family(bench_dir: Path, name: str) -> types.ModuleType:
    """The architecture file ``families/<name>.py``.  It provides
    ``model_config(conf)``, ``Shapes.of(conf)`` (with at least
    ``vocab``), ``prefill_work(s, positions)``, ``decode_work(s,
    contexts)``, ``weight_shapes(s)``, ``program_params(w, s, expected)``
    and ``logits_at(w, conf, seqs, rows, *, pad_to, mode)``."""
    path = bench_dir / "families" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"family {name!r} has no file {path}")
    return _module(path, "chipbench_family_")


def _reader(path: Path) -> Callable:
    return _module(path, "chipbench_metric_").read


@functools.lru_cache(maxsize=None)
def _module(path: Path, prefix: str) -> types.ModuleType:
    """The module of a file under the benchmark, loaded once a process
    and registered under a name of its own path (a dataclass in it looks
    its module up there)."""
    name = prefix + re.sub(r"\W", "_", str(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


# -- what a run records -------------------------------------------------------

@dataclasses.dataclass
class ReqRecord:
    rid: int
    prompt: np.ndarray
    max_new: int
    sent: float                   # host clock, seconds
    in_window: bool               # first answered in the window
    t_prefill: float = 0.0        # prefill call start
    token_times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Call:
    kind: str                     # prefill | decode
    t0: float
    t1: float
    flops: int
    nbytes: int
    lengths: Tuple[int, ...]      # positions computed | rows' contexts


@dataclasses.dataclass
class RunData:
    """Everything a metric reader reads.  Times are host-clock seconds."""

    setup_s: float
    window: Tuple[float, float]
    requests: Dict[int, ReqRecord]
    calls: List[Call]
    pool_delta: Dict[str, int]    # pool counters over the window
    peak: Dict[str, float]
    trace: Optional[xplane.Reduced] = None    # traced runs: the window's

    def window_requests(self) -> List[ReqRecord]:
        return [r for r in self.requests.values() if r.in_window]

    def calls_in(self, kind: str, span: Tuple[float, float]) -> List[Call]:
        return [c for c in self.calls
                if c.kind == kind and span[0] <= c.t0 and c.t1 <= span[1]]

    def mean_call_ms(self, kind: str) -> Optional[float]:
        d = [c.t1 - c.t0 for c in self.calls_in(kind, self.window)]
        return 1e3 * float(np.mean(d)) if d else None

    def roofline_pct(self, kind: str) -> Optional[float]:
        """Least time of the required work of ``kind`` calls in the traced
        window over the device time inside them, in percent."""
        if self.trace is None:
            return None
        calls = self.calls_in(kind, self.window)
        dev = self.trace.device_s.get(kind, 0.0)
        if not calls or dev <= 0:
            return None
        least = sum(work.least_seconds(c.flops, c.nbytes, self.peak)
                    for c in calls)
        return 100.0 * least / dev

    def mfu_pct(self) -> Optional[float]:
        """Required FLOPs of every call in the traced window over the
        window's seconds at the chip's peak, in percent."""
        if self.trace is None or self.trace.busy_s <= 0:
            return None
        flops = sum(c.flops for k in ("prefill", "decode")
                    for c in self.calls_in(k, self.window))
        return 100.0 * flops / (self.trace.window_s * self.peak["flops"])


def percentile(values, q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if len(values) else None


# -- the executor's spans ------------------------------------------------------

class Spans:
    """Wraps the scheduler's executor: each call runs inside a
    ``bench.<kind>`` trace annotation and is timed on the host clock,
    with the work it requires counted by the family from the true
    lengths."""

    def __init__(self, inner, eng, fam: types.ModuleType, shapes,
                 records: Dict[int, ReqRecord]):
        self.inner = inner
        self.fam = fam
        self.shapes = shapes
        self.records = records
        self.calls: List[Call] = []
        self.block = eng.pool.bs
        self._fill: List[int] = []
        admit = eng.mgr.admit

        def spy_admit(*a, **kw):      # which blocks the prefill computes
            st, fill = admit(*a, **kw)
            self._fill = list(fill)
            return st, fill
        eng.mgr.admit = spy_admit

    def __getattr__(self, name):      # capacity surface: n_blocks, ...
        return getattr(self.inner, name)

    def prefill(self, r):
        rec = self.records[r.req_id]
        t0 = time.perf_counter()
        rec.t_prefill = t0
        with jax.profiler.TraceAnnotation("bench.prefill"):
            tok = self.inner.prefill(r)
        t1 = time.perf_counter()
        rec.token_times.append(t1)
        n = len(rec.prompt)
        pos = {p for b in self._fill
               for p in range(b * self.block, min((b + 1) * self.block, n))}
        pos.add(n - 1)
        pos = tuple(sorted(pos))
        self.calls.append(Call("prefill", t0, t1,
                               *self.fam.prefill_work(self.shapes, pos), pos))
        return tok

    def decode(self, ids):
        ctx = tuple(len(self.records[i].prompt)
                    + len(self.records[i].token_times) for i in ids)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.decode"):
            out = self.inner.decode(ids)
        t1 = time.perf_counter()
        for i in ids:
            self.records[i].token_times.append(t1)
        self.calls.append(Call("decode", t0, t1,
                               *self.fam.decode_work(self.shapes, ctx), ctx))
        return out

    def release(self, rid):
        with jax.profiler.TraceAnnotation("bench.release"):
            self.inner.release(rid)


class CompileCount:
    """Programs compiled or loaded from the persistent cache, counted
    from JAX's monitoring events (one listener per process)."""

    n = 0
    _on = False

    @classmethod
    def start(cls) -> None:
        if not cls._on:
            jax.monitoring.register_event_duration_secs_listener(
                lambda ev, _s, **_k: cls._bump(ev))
            cls._on = True

    @classmethod
    def _bump(cls, ev: str) -> None:
        if ev in COMPILE_EVENTS:
            cls.n += 1


# -- devices --------------------------------------------------------------------

def device_info(chips: int, require_chip: bool) -> Tuple[Dict, Dict]:
    """(device, peaks) of the first device; without a chip the benchmark
    knows, or with fewer than ``chips``, raises ``NoChip``."""
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    try:
        peak = work.peaks(d.device_kind)
    except KeyError as e:
        if require_chip:
            raise NoChip(str(e)) from e
        peak = next(iter(work.PEAKS.values()))
    if require_chip and len(devs) < chips:
        raise NoChip(f"{len(devs)} {d.device_kind} device(s), the cell "
                     f"asks for {chips}")
    return info, peak


def use_compile_cache(cache_dir: str) -> None:
    """The persistent compile cache at a fixed directory (the one in
    ``JAX_COMPILATION_CACHE_DIR`` where that is set), keeping every
    program, eager operations included."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# -- the program ----------------------------------------------------------------

def served_dtype(conf: Dict) -> str:
    """The type the configuration is served in (its ``serving.dtype``),
    which may differ from the type its checkpoint is stored in."""
    return conf["serving"]["dtype"]


@dataclasses.dataclass
class Served:
    """The state of the served path during a run."""

    eng: object
    sched: object
    spans: Spans
    records: Dict[int, ReqRecord]


def build_served(fam: types.ModuleType, conf: Dict, shapes, w: Dict
                 ) -> Served:
    from repro.launch import serve
    from repro.models.model import build
    from repro.serving.engine import ServingEngine
    from repro.serving.scheduler import SchedConfig

    cfg = fam.model_config(conf)
    api = build(cfg)
    expected = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    params = fam.program_params(w, shapes, expected)
    jax.block_until_ready(params)
    sv = conf["serving"]
    blocks = sv["hbm_blocks"] or serve.fit_pool_blocks(cfg, sv["block_size"])
    eng = ServingEngine(api, params, block_size=sv["block_size"],
                        hbm_blocks=blocks, max_batch=sv["max_batch"],
                        max_blocks_per_seq=sv["max_blocks_per_seq"])
    sched = eng.make_scheduler(config=SchedConfig(
        token_budget=sv["token_budget"], max_batch=sv["max_batch"]))
    records: Dict[int, ReqRecord] = {}
    spans = Spans(sched.x, eng, fam, shapes, records)
    sched.x = spans
    return Served(eng, sched, spans, records)


def sched_request(req: traffic.Req):
    from repro.serving.admission import SchedRequest
    from repro.serving.engine import Request

    prompt = [int(t) for t in req.prompt]
    return SchedRequest(req_id=req.rid, prompt_len=len(prompt),
                        max_new=req.max_new,
                        payload=Request(req.rid, prompt, req.max_new))


def submit(sv: Served, req: traffic.Req, sent: float, in_window: bool) -> None:
    sv.records[req.rid] = ReqRecord(req.rid, req.prompt, req.max_new, sent,
                                    in_window)
    sv.sched.submit(sched_request(req))


# -- set-up -----------------------------------------------------------------------

def warm_up(sv: Served, mix: Dict, vocab: int, seed: int, block: int,
            max_batch: int) -> None:
    """Run every shape this cell's traffic uses once: the prefill of each
    bucket with each length of its last block (the eager block writes
    differ by it), and a decode step at every batch size.  The warm-up
    prompts share one random prefix, so their blocks mostly hit."""
    from repro.serving.admission import SchedRequest
    from repro.serving.engine import Request

    rng = np.random.default_rng([seed, 1])
    base = rng.integers(1, vocab, max(mix["buckets"]), dtype=np.int32)
    x = sv.spans.inner
    rid = -1

    def start(n: int, max_new: int) -> int:
        nonlocal rid
        rid -= 1
        prompt = [int(t) for t in base[:n]]
        x.prefill(SchedRequest(rid, n, max_new,
                               payload=Request(rid, prompt, max_new)))
        return rid

    for b in mix["buckets"]:
        for tail in range(block):
            x.release(start(b - tail, 2))
    ids = [start(mix["buckets"][0], max_batch + 2) for _ in range(max_batch)]
    for n in range(max_batch, 0, -1):
        x.decode(ids[:n])
    for i in ids:
        x.release(i)


def fill_pool(sv: Served, fill: List[traffic.Req]) -> int:
    """Prefill sessions' histories in order, so that the pool is full
    and the live sessions' histories are resident when traffic starts, as
    in a long-running server.  The fill starts at the oldest history
    whose blocks, with those of every later one, would about fill the
    pool.  Returns the number of histories prefilled."""
    pool = sv.eng.pool
    bs = pool.bs
    prev: Dict[int, int] = {}
    new_blocks = []
    for r in fill:                    # blocks a turn adds to its session's
        shared = prev.get(r.session, 0) // bs
        new_blocks.append(-(-len(r.prompt) // bs) - shared)
        prev[r.session] = len(r.prompt)
    start, need = 0, 0
    for i in range(len(fill) - 1, -1, -1):
        need += new_blocks[i]
        if need >= pool.n_blocks:
            start = i
            break
    n = 0
    for req in fill[start:]:
        r = dataclasses.replace(req, rid=-(10**9) - req.rid, max_new=1)
        sv.records[r.rid] = ReqRecord(r.rid, r.prompt, 1, 0.0, False)
        sv.sched.submit(sched_request(r))
        while sv.sched.queue or sv.sched.active:
            sv.sched.tick()
        n += 1
    return n


# -- the measured window -------------------------------------------------------------

class Tracer:
    """The profiler over the window, its spans, and the reduction.  The
    profiler starts ``TRACE_LEAD_S`` before the window opens: its first
    steps are slow, and the reduction reads the window alone."""

    def __init__(self, trace_dir: Optional[Path]):
        self.dir = trace_dir
        self.window = None
        self.on = False

    def start(self) -> None:
        if self.dir is not None and not self.on:
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.on = True

    def open_window(self) -> None:
        self.window = jax.profiler.TraceAnnotation("bench.window")
        self.window.__enter__()

    def close_window(self) -> None:
        if self.window is not None:
            self.window.__exit__(None, None, None)
            self.window = None

    def stop(self) -> Optional[xplane.Reduced]:
        self.close_window()
        if not self.on:
            return None
        jax.profiler.stop_trace()
        self.on = False
        try:
            return xplane.reduce(xplane.load(str(self.dir)))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def drive(sv: Served, clients: List[List[traffic.Req]], seconds: float,
          mix: Dict, max_batch: int, tracer: Tracer
          ) -> Tuple[float, float, Dict]:
    """Each client sends its next request once the previous one has ended
    and the request's think time has passed.  The window
    opens ``TRACE_LEAD_S`` after ``open_after_completions`` requests have
    completed (and, with ``open_when_slots_full``, every decode slot is
    taken), and lasts ``seconds``."""
    sched = sv.sched
    owner: Dict[int, int] = {}
    queues = [[r for r in c if not r.fill] for c in clients]
    nxt = [0] * len(clients)
    sends: List[Tuple[float, int]] = []      # (send time, client)

    def plan(c: int, now: float) -> None:
        if nxt[c] >= len(queues[c]):
            raise RuntimeError(f"client {c} ran out of requests; raise the "
                               "mix's requests_per_client")
        heapq.heappush(sends, (now + queues[c][nxt[c]].think_s, c))

    now = time.perf_counter()
    for c in range(len(clients)):
        plan(c, now)
    seen = 0
    t_open = w0 = w1 = None
    marks = {}
    need_done = mix.get("open_after_completions", 1)
    need_full = mix.get("open_when_slots_full", True)
    started = now
    while True:
        now = time.perf_counter()
        if w0 is None and now - started > OPEN_CAP_S:
            raise RuntimeError(f"the window did not open in {OPEN_CAP_S} s")
        while sends and sends[0][0] <= now:
            _, c = heapq.heappop(sends)
            r = queues[c][nxt[c]]
            nxt[c] += 1
            owner[r.rid] = c
            submit(sv, r, now, w0 is not None)
        if sched.queue or sched.active:
            with jax.profiler.TraceAnnotation("bench.tick"):
                sched.tick()
        else:
            wake = min(sends[0][0], now + 0.05) if sends else now + 0.01
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(max(0.0, wake - now))
        now = time.perf_counter()
        if w1 is not None and now >= w1:
            tracer.close_window()
            marks["close"] = (sv.eng.pool.stats, CompileCount.n)
            break
        for rid in sched.order[seen:]:
            if rid in owner:
                plan(owner[rid], now)
        seen = len(sched.order)
        if t_open is None and sum(i in owner for i in sched.order) >= \
                need_done and (not need_full or len(sched.active) >= max_batch):
            t_open = now + TRACE_LEAD_S
            tracer.start()
        if w0 is None and t_open is not None and now >= t_open:
            w0, w1 = now, now + seconds
            tracer.open_window()
            marks["open"] = (sv.eng.pool.stats, CompileCount.n)
    return w0, w1, marks


# -- the check -------------------------------------------------------------------------

def sample_finished(sv: Served, w0: float, n: int, seed: int
                    ) -> List[Tuple[np.ndarray, List[int]]]:
    """Up to ``n`` requests that finished and were served in the window,
    drawn from the seed, with the one that served the most tokens among
    them."""
    from repro.serving.admission import ST_COMPLETED

    done = [(rec, sv.sched.outcomes[rid].tokens)
            for rid, rec in sorted(sv.records.items())
            if rid >= 0 and rec.token_times and rec.token_times[-1] >= w0
            and rid in sv.sched.outcomes
            and sv.sched.outcomes[rid].status == ST_COMPLETED]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: (len(done[i][1]), len(done[i][0].prompt)))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng([seed, 2])
    pick = [longest] + list(rng.permutation(rest)[:n - 1])
    return [(done[i][0].prompt, list(done[i][1])) for i in pick]


def check(cell: Cell, w: Dict, sample, mode: str = "f32"
          ) -> Dict[str, float]:
    """The gaps of the served tokens below the reference's best (the
    family's ``logits_at``, ``reference.gaps``); with another ``mode``,
    of the tokens that the control in that mode puts first."""
    seqs, rows, toks = reference.served_rows(*zip(*sample))
    pad_to = cell.mix["max_tokens"]
    ref = cell.family.logits_at(w, cell.conf, seqs, rows, pad_to=pad_to)
    if mode != "f32":
        ctl = cell.family.logits_at(w, cell.conf, seqs, rows, pad_to=pad_to,
                                    mode=mode)
        toks = np.asarray(jax.numpy.argmax(ctl, axis=-1))
    return reference.gaps(ref, toks)


def judge(gaps: Optional[Dict[str, float]], wrong_len: int, limits: Dict
          ) -> Tuple[bool, Dict[str, Dict]]:
    """(correct, checks): each gap that the cell's limits file names,
    beside its limit, and the count of answers of the wrong length.  No
    sample (``gaps`` None) reads as an infinite gap."""
    checks = {k: {"value": gaps[k] if gaps else float("inf"),
                  "limit": limits[k]}
              for k in reference.GAP_NAMES if k in limits}
    checks["wrong_length_answers"] = {"value": wrong_len, "limit": 0}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


# -- one run --------------------------------------------------------------------------------

@dataclasses.dataclass
class Outcome:
    """A finished run: the result line, what the check compared, and the
    executor's calls with the work each required."""

    result: Dict
    sample: List[Tuple[np.ndarray, List[int]]]
    weights: Dict
    notes: Dict
    calls: List[Call]


def run(cell: Cell, seed: int, seconds: float, *, trace: bool,
        t_start: float, require_chip: bool = True,
        cache_dir: Optional[str] = None,
        trace_dir: Optional[Path] = None, keep_weights: bool = False
        ) -> Outcome:
    """One run of ``cell``; ``t_start`` is the host-clock time the process
    started, from which set-up is counted."""
    CompileCount.start()
    dev, peak = device_info(cell.chips, require_chip)
    if cache_dir:
        use_compile_cache(cache_dir)
    conf, mix, sv_conf = cell.conf, cell.mix, cell.conf["serving"]
    if trace:
        seconds = min(seconds, TRACE_WINDOW_S)
    shapes = cell.family.Shapes.of(conf)
    w = weights.make(cell.family.weight_shapes(shapes), seed,
                     served_dtype(conf))
    sv = build_served(cell.family, conf, shapes, w)
    block = sv_conf["block_size"]
    clients = traffic.generate(mix, vocab=shapes.vocab, seed=seed,
                               block=block)
    warm_up(sv, mix, shapes.vocab, seed, block, sv_conf["max_batch"])
    history = sorted((r for c in clients for r in c if r.fill),
                     key=lambda r: r.rid)
    n_fill = fill_pool(sv, history)
    tracer = Tracer(trace_dir if trace else None)
    w0, w1, marks = drive(sv, clients, seconds, mix, sv_conf["max_batch"],
                          tracer)
    reduced = tracer.stop()
    for rec in sv.records.values():     # requests first answered in it
        rec.in_window = bool(rec.token_times) and \
            w0 <= rec.token_times[0] <= w1
    (st0, c0), (st1, c1) = marks["open"], marks["close"]
    mem = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    data = RunData(
        setup_s=w0 - t_start, window=(w0, w1),
        requests={i: r for i, r in sv.records.items() if i >= 0},
        calls=sv.spans.calls,
        pool_delta={"hit": st1.hits - st0.hits,
                    "miss": st1.misses - st0.misses,
                    "swap_in": st1.swap_in - st0.swap_in},
        peak=peak, trace=reduced)
    statuses = {rid: sv.sched.outcomes[rid].status
                for rid in data.requests if rid in sv.sched.outcomes}
    # requests that completed, or were refused, in the window
    done = [r for r in data.requests.values()
            if statuses.get(r.rid) == "completed"
            and w0 <= r.token_times[-1] <= w1]
    refused = [r for r in data.requests.values()
               if statuses.get(r.rid, "completed") != "completed"
               and w0 <= r.sent <= w1]
    sample = sample_finished(sv, w0, cell.limits["sample_requests"], seed)
    wrong_len = sum(len(toks) != len(sv.records[rid].token_times)
                    or len(toks) != sv.records[rid].max_new
                    for rid, o in sv.sched.outcomes.items()
                    if rid >= 0 and o.status == "completed"
                    for toks in [o.tokens])
    notes = {"pool_blocks": sv.eng.pool.n_blocks, "fill_histories": n_fill,
             "window_calls": {k: len(data.calls_in(k, (w0, w1)))
                              for k in ("prefill", "decode")},
             "compiles_in_window": c1 - c0, "pool": dataclasses.asdict(st1),
             "window_hit": data.pool_delta, "completed": sum(
                 s == "completed" for s in statuses.values())}
    del sv, clients
    gc.collect()
    gaps = check(cell, w, sample) if sample else None
    correct, checks = judge(gaps, wrong_len, cell.limits)
    notes["gaps"] = gaps
    metrics = {}
    for name, m in cell.reported(trace).items():
        v = m["reader"](data)
        if v is not None:
            metrics[name] = {"value": v, "unit": m["unit"]}
    if reduced is not None:
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
    result = {"correct": bool(correct),
              "attempted": len(done) + len(refused), "failed": len(refused),
              "metrics": metrics, "device": dev}
    if reduced is not None:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in reduced.device_ops],
            "idle_gaps": [[n, s] for n, s in reduced.idle_gaps]}
        notes["trace_shift_s"] = reduced.shift_s
        notes["device_s"] = reduced.device_s
    result["checks"] = checks
    return Outcome(result, sample, w if keep_weights else {}, notes,
                   data.calls)


def print_result(out: Outcome) -> None:
    """The notes on an earlier line, the checks as the last lines of
    standard error, the result as the last line of standard output."""
    print("notes " + json.dumps(out.notes, default=str), flush=True)
    for name, c in out.result["checks"].items():
        print(f"check {name} value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out.result), flush=True)


def bench_paths() -> Tuple[Path, Path]:
    """(checkout root, benchmark directory) of this file."""
    bench_dir = Path(__file__).resolve().parents[1]
    return bench_dir.parents[1], bench_dir


def program_on_path(root: Path) -> None:
    """Import the program from the checkout's ``src``; keep the TPU
    runtime's logs off (they would go to a fixed directory outside it)."""
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
