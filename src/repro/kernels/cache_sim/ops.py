"""jit'd public wrapper: lane-parallel Clock2Q+ trace replay.

``simulate_lanes(traces, capacity, ...)`` builds fresh state, replays all
lanes in one kernel launch, and returns per-lane miss ratios + hits.
Sizing follows the paper: Small = 10%, Main = 90%, Ghost = 50%, window =
50% of the Small FIFO.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.engine.layout import c2qp_sizes
from repro.kernels.cache_sim.cache_sim import cache_sim_raw


def init_state(n_lanes: int, capacity: int, *, small_frac: float = 0.1,
               ghost_frac: float = 0.5):
    S, M, G, _ = c2qp_sizes(capacity, small_frac, ghost_frac)
    z = lambda c: jnp.zeros((n_lanes, c), jnp.int32)
    e = lambda c: jnp.full((n_lanes, c), -1, jnp.int32)
    return dict(skey=e(S), sref=z(S), sseq=z(S), mkey=e(M), mref=z(M),
                gkey=e(G), scal=z(4))


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def replay(trace, state, *, window: int, interpret: bool = False):
    outs = cache_sim_raw(trace, state["skey"], state["sref"], state["sseq"],
                         state["mkey"], state["mref"], state["gkey"],
                         state["scal"], window=window, interpret=interpret)
    hits = outs[0]
    new_state = dict(zip(("skey", "sref", "sseq", "mkey", "mref", "gkey",
                          "scal"), outs[1:]))
    return hits, new_state


def simulate_lanes(traces, capacity: int, *, window_frac: float = 0.5,
                   small_frac: float = 0.1, ghost_frac: float = 0.5,
                   interpret: bool = False):
    """traces: (LANES, T) int32 -> (miss_ratios (LANES,), hits (LANES, T))."""
    traces = jnp.asarray(traces, jnp.int32)
    L = traces.shape[0]
    _, _, _, window = c2qp_sizes(capacity, small_frac, ghost_frac,
                                 window_frac)
    state = init_state(L, capacity, small_frac=small_frac,
                       ghost_frac=ghost_frac)
    hits, _ = replay(traces, state, window=window, interpret=interpret)
    mr = 1.0 - jnp.mean(hits.astype(jnp.float32), axis=1)
    return mr, hits
