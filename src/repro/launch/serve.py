"""Serving launcher: batched requests through the Clock2Q+-paged engine.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b \
        --requests 8 --max-new 8 [--hbm-blocks 28] [--shrink-to 14]

Without ``--layers`` the arch runs as its ``reduced()`` CPU preset;
``--layers N`` keeps the published widths and cuts depth to N layers
(``--hbm-blocks 0`` then sizes the pool from the device's free memory).
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import List, Sequence, Tuple

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.models.model import build
from repro.serving.engine import Completion, Request, ServingEngine

# device bytes kept free beside the weights and two copies of the pool:
# the decode/prefill programs' own temporaries
POOL_RESERVE_BYTES = 1 << 30


def model_config(arch: str, n_layers: int = 0) -> ModelConfig:
    """``arch`` at its published widths cut to ``n_layers`` layers, or
    its ``reduced()`` CPU preset when ``n_layers`` is 0."""
    cfg = get_config(arch)
    if n_layers <= 0:
        return reduced(cfg)
    return dataclasses.replace(cfg, n_layers=min(n_layers, cfg.n_layers))


def fit_pool_blocks(cfg: ModelConfig, block_size: int) -> int:
    """Blocks the pool may hold with what the device has free, leaving
    room for a second copy of the pool: the decode step returns a new
    pool before the old one is released."""
    device = jax.devices()[0]
    stats = device.memory_stats()
    if not stats or "bytes_limit" not in stats:
        raise ValueError(f"{device.platform} device reports no memory "
                         "limit; pass --hbm-blocks explicitly")
    free = stats["bytes_limit"] - stats["bytes_in_use"] - POOL_RESERVE_BYTES
    # one block: K and V of block_size tokens in every layer
    block_bytes = (2 * cfg.n_layers * block_size * cfg.n_kv_heads * cfg.hd
                   * L.dtype_of(cfg).itemsize)
    n = free // (2 * block_bytes)
    if n < 1:
        raise ValueError(f"no room for a KV pool: {free} bytes free")
    return int(n)


def make_requests(vocab: int, n: int, *, prefix_len: int, max_new: int,
                  suffix_lens: Sequence[int] = (4, 12)) -> List[Request]:
    """``n`` prompts sharing one ``prefix_len``-token prefix (the pool's
    prefix hits), each with a random suffix of ``suffix_lens[0]`` to
    ``suffix_lens[1] - 1`` tokens."""
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, vocab, prefix_len).tolist()
    lo, hi = suffix_lens
    return [Request(i, prefix + rng.integers(0, vocab,
                                             int(rng.integers(lo, hi))).tolist(),
                    max_new=max_new) for i in range(n)]


def run(cfg: ModelConfig, reqs: List[Request], *, block_size: int,
        hbm_blocks: int, max_batch: int, shrink_to: int = 0
        ) -> Tuple[ServingEngine, List[Completion], float]:
    """The launcher's path: random weights from seed 0, built on the
    device in their own dtype, behind a Clock2Q+-paged engine
    (``hbm_blocks`` 0: fit the pool to the device's free memory), then
    ``reqs`` through the scheduler.  With ``shrink_to`` the pool is
    live-resized to that many blocks halfway (paper §4.2).  Returns the
    engine, the completions and the serving wall seconds."""
    if cfg.family not in ("dense", "vlm", "moe"):
        raise SystemExit(f"{cfg.family} archs have no paged-KV serving path")
    api = build(cfg)
    params = jax.jit(api.init)(jax.random.PRNGKey(0))
    if hbm_blocks <= 0:
        jax.block_until_ready(params)
        hbm_blocks = fit_pool_blocks(cfg, block_size)
    eng = ServingEngine(api, params, block_size=block_size,
                        hbm_blocks=hbm_blocks, max_batch=max_batch)
    half = len(reqs) // 2 if shrink_to else len(reqs)
    t0 = time.perf_counter()
    done = eng.run(reqs[:half])
    if shrink_to:
        print(f"live-shrinking pool {eng.pool.n_blocks} -> {shrink_to}")
        eng.pool.resize(shrink_to)
        done += eng.run(reqs[half:])
    return eng, done, time.perf_counter() - t0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--layers", type=int, default=0,
                    help="published widths at this depth (0: reduced "
                         "CPU preset)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--prefix-len", type=int, default=32)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--hbm-blocks", type=int, default=28,
                    help="pool blocks (0: fit the device's free memory)")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--shrink-to", type=int, default=0,
                    help="live-resize the pool mid-run (paper §4.2)")
    args = ap.parse_args()

    cfg = model_config(args.arch, args.layers)
    reqs = make_requests(cfg.vocab, args.requests,
                         prefix_len=args.prefix_len, max_new=args.max_new)
    eng, done, dt = run(cfg, reqs, block_size=args.block_size,
                        hbm_blocks=args.hbm_blocks,
                        max_batch=args.max_batch, shrink_to=args.shrink_to)
    stats, flows = eng.stats
    n_tok = sum(len(c.tokens) for c in done)
    print(f"{len(done)} completions, {n_tok} tokens in {dt:.1f}s "
          f"({n_tok/dt:.1f} tok/s)")
    print(f"pool: hit_ratio={stats.hit_ratio:.2f} swap_out={stats.swap_out} "
          f"swap_in={stats.swap_in}  flows={flows}")


if __name__ == "__main__":
    main()
