"""Bring-up smoke test on one TPU chip.

    python3 chip_smoke.py

Runs two device paths in this one process:

A. The served path of ``repro.launch.serve``: granite-3-8b at its
   published widths, cut to ``N_LAYERS`` layers, random weights from a
   seed, behind the Clock2Q+ KV block pool sized to the chip's free HBM.
   ``N_REQUESTS`` prompts that share a prefix go through the continuous-
   batching scheduler.  Then one prompt's next-token logits after a few
   paged decode steps are compared with a non-paged prefill of the same
   tokens.
B. The on-device Clock2Q+ MRC sweep (``repro.tuning.sweep``) over the
   full-length SUITE ``w01-skewed`` metadata trace, on the fig13 grid.
   Two lanes are checked hit-for-hit against the Python reference.

Every check that fails raises.  The last line of stdout,
``{"ok": true, "device": {...}}``, is printed only when both phases
pass on a TPU; any other outcome exits non-zero without it.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path
from typing import Dict, Sequence, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import stats, traces  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.jax_cache import use_compile_cache  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.serving.engine import Request, ServingEngine  # noqa: E402
from repro.tuning import sweep  # noqa: E402

# -- phase A: the served path -----------------------------------------------
ARCH = "granite-3-8b"
N_LAYERS = 20            # of 40: ~8.8 GB of bf16 weights, the rest is pool
BLOCK_SIZE = 16
MAX_BATCH = 8
N_REQUESTS = 16
PREFIX_LEN = 320         # 20 shared full blocks: the pool's prefix hits
SUFFIX_LENS = (4, 28)    # prompts of 324..347 tokens, two prefill buckets
MAX_NEW = 16
CHECK_STEPS = 4          # paged decode steps before the logits comparison
# bf16 bound on |paged - prefill| logits, relative to the largest
# reference logit.  The two paths round differently through every layer:
# an 8-layer bf16 model at d_model 256 differs by ~1.2% on the CPU,
# while one changed or dropped context token moves its logits by 35% or
# more, so 10% separates rounding from a wrong page.
LOGITS_RTOL = 0.1

# -- phase B: the on-device Clock2Q+ sweep -----------------------------------
SWEEP_TRACE = "w01-skewed"
SWEEP_SIZE_FRACS = (0.01, 0.1)           # of the trace footprint (fig13)
SWEEP_WINDOW_FRACS = (0.1, 0.3, 0.5)
CHECKED_LANES = (0, 5)   # (1%, window 0.1) and (10%, window 0.5)

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class SmokeFailure(RuntimeError):
    """A check of the smoke test did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, summed from
    its monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += secs


def paged_vs_prefill(eng: ServingEngine, prompt: Sequence[int],
                     steps: int) -> Tuple[float, float]:
    """Admit ``prompt`` into the engine's pool, take ``steps`` greedy
    paged decode steps, and compare the next-token logits with a
    non-paged ``forward_prefill`` of the same tokens.  Returns the
    largest absolute difference and the largest reference logit."""
    rid = -1
    eng._start(Request(rid, list(prompt), max_new=steps + 2))
    for _ in range(steps):
        eng._decode_step([rid])
    paged = eng._decode_logits([rid])[0]
    seq = eng.mgr.seqs[rid]
    toks = seq.tokens + seq.out_tokens
    eng._finish(rid)
    ref, _ = jax.jit(lambda p, b: T.forward_prefill(eng.cfg, p, b))(
        eng.params, {"tokens": jnp.asarray(toks, jnp.int32)[None]})
    ref = ref[0, -1].astype(jnp.float32)
    diff = jnp.max(jnp.abs(paged.astype(jnp.float32) - ref))
    return float(diff), float(jnp.max(jnp.abs(ref)))


def phase_serve(cfg: ModelConfig, *, block_size: int, hbm_blocks: int,
                max_batch: int, n_requests: int, prefix_len: int,
                suffix_lens: Sequence[int], max_new: int, check_steps: int,
                logits_rtol: float) -> Dict:
    """Phase A through ``repro.launch.serve.run``; raises on any failed
    check and returns what it measured."""
    reqs = serve.make_requests(cfg.vocab, n_requests, prefix_len=prefix_len,
                               max_new=max_new, suffix_lens=suffix_lens)
    eng, done, serve_s = serve.run(cfg, reqs, block_size=block_size,
                                   hbm_blocks=hbm_blocks,
                                   max_batch=max_batch)
    statuses = sorted({c.status for c in done})
    check(len(done) == n_requests and statuses == ["completed"],
          f"{len(done)} of {n_requests} completions, statuses {statuses}")
    check(all(len(c.tokens) == max_new for c in done),
          f"token counts {[len(c.tokens) for c in done]}, want {max_new}")
    check(all(0 <= t < cfg.vocab for c in done for t in c.tokens),
          "a generated token is outside the vocabulary")
    pool_stats, _ = eng.stats
    check(pool_stats.hit_ratio > 0, "the pool had no prefix hits")
    diff, scale = paged_vs_prefill(eng, reqs[0].prompt, check_steps)
    check(diff <= logits_rtol * scale,
          f"paged vs prefill logits differ by {diff} > "
          f"{logits_rtol} x {scale}")
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(eng.params))
    return {"arch": cfg.name, "n_layers": cfg.n_layers,
            "d_model": cfg.d_model, "n_heads": cfg.n_heads,
            "n_kv_heads": cfg.n_kv_heads, "d_ff": cfg.d_ff,
            "vocab": cfg.vocab, "dtype": cfg.dtype, "n_params": int(n_params),
            "block_size": block_size, "pool_blocks": eng.pool.n_blocks,
            "pool_bytes": int(eng.pool.kpool.nbytes + eng.pool.vpool.nbytes),
            "completions": len(done),
            "tokens": sum(len(c.tokens) for c in done),
            "serve_seconds": serve_s, "pool_hits": pool_stats.hits,
            "pool_misses": pool_stats.misses,
            "pool_hit_ratio": pool_stats.hit_ratio,
            "logits_max_abs_diff": diff, "logits_max_abs_ref": scale,
            "logits_tolerance": logits_rtol * scale}


def phase_sweep(trace: np.ndarray, *, size_fracs: Sequence[float],
                window_fracs: Sequence[float],
                lanes: Sequence[int]) -> Dict:
    """Phase B: the batched sweep's per-lane hit counts, with ``lanes``
    checked exactly against ``stats.simulate("clock2q+", ...)``."""
    fp = traces.footprint(trace)
    caps = [max(10, int(f * fp)) for f in size_fracs]
    grid = sweep.make_grid(caps, window_fracs)
    t0 = time.perf_counter()
    hits = sweep.sweep_hits(trace, grid)
    sweep_s = time.perf_counter() - t0
    checked = []
    for i in lanes:
        c = grid[i]
        ref = stats.simulate("clock2q+", trace, c.capacity,
                             window_frac=c.window_frac).hits
        check(int(hits[i]) == ref,
              f"lane {i} (capacity {c.capacity}, window {c.window_frac}): "
              f"sweep {int(hits[i])} hits, reference {ref}")
        checked.append({"lane": i, "capacity": c.capacity,
                        "window_frac": c.window_frac, "hits": int(hits[i]),
                        "reference_hits": ref})
    return {"requests": int(len(trace)), "footprint": fp,
            "lanes": [{"capacity": c.capacity, "window_frac": c.window_frac,
                       "hits": int(h), "miss_ratio": 1 - int(h) / len(trace)}
                      for c, h in zip(grid, hits)],
            "checked": checked, "sweep_seconds": sweep_s}


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's first device is "
              f"{dev.platform}", file=sys.stderr)
        return 1
    print(f"device {dev.platform} {dev.device_kind} x {len(jax.devices())}; "
          f"compile cache {use_compile_cache()}", flush=True)
    clock = CompileClock()

    t0, c0 = time.perf_counter(), clock.seconds
    a = phase_serve(serve.model_config(ARCH, N_LAYERS),
                    block_size=BLOCK_SIZE, hbm_blocks=0, max_batch=MAX_BATCH,
                    n_requests=N_REQUESTS, prefix_len=PREFIX_LEN,
                    suffix_lens=SUFFIX_LENS, max_new=MAX_NEW,
                    check_steps=CHECK_STEPS, logits_rtol=LOGITS_RTOL)
    a["phase_seconds"] = time.perf_counter() - t0
    a["compile_seconds"] = clock.seconds - c0
    mem = dev.memory_stats() or {}
    a["device_bytes"] = {k: mem.get(k) for k in
                         ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
    print("A serve " + json.dumps(a), flush=True)

    t0, c0 = time.perf_counter(), clock.seconds
    spec = next(s for s in traces.SUITE if s.name == SWEEP_TRACE)
    b = phase_sweep(traces.derive_metadata(spec.data()),
                    size_fracs=SWEEP_SIZE_FRACS,
                    window_fracs=SWEEP_WINDOW_FRACS, lanes=CHECKED_LANES)
    b["trace"] = SWEEP_TRACE
    b["phase_seconds"] = time.perf_counter() - t0
    b["compile_seconds"] = clock.seconds - c0
    print("B sweep " + json.dumps(b), flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
