"""Mixture-of-Experts layer: top-k routing over the experts this chip
holds.

Two routers.  ``softmax``: top-k of the softmax, renormalised, with a
Switch-style load-balance aux loss (mean fraction x mean router prob per
expert, scaled by E).  ``sigmoid`` (DeepSeek-V3's ``noaux_tc``): sigmoid
scores in float32; a correction bias is added to choose the top k but
never weights them; the chosen scores are renormalised and scaled by
``routed_scaling``; no aux loss.

Two dispatches.  With a ``capacity_factor``, a scatter into a per-expert
buffer of shape (E, C, D) drops tokens above the capacity — the expert
axis shards over the mesh "model" axis (expert parallelism); GSPMD lowers
the scatter/gather into all-to-all-style collectives.  For the 1T config
the expert FFN dim additionally shards over "data" (2-D expert sharding,
DESIGN.md §6).  With ``capacity_factor`` 0 the dispatch is dropless: the
token-expert pairs that fall on held experts are sorted by expert and run
as one grouped product per weight (``jax.lax.ragged_dot``).

An expert layer may hold a share of the experts (``experts_held`` from
``expert_first``): it routes over all ``n_experts`` and computes only
its own experts' part of the result, as one chip of an expert-parallel
deployment does without the exchange.

Each call also counts, per held expert, the token-expert pairs routed to
it, and in one last entry the pairs routed to experts held elsewhere.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.config import ModelConfig


def _constrain(x, logical):
    """Hillclimb 3 (EXPERIMENTS.md §Perf): pin MoE dispatch shardings so
    GSPMD keeps dispatch buffers expert-sharded and token tensors
    data-sharded instead of all-gathering per layer.  "data_batch" maps
    to ("data",)/(("pod","data")) depending on the mesh axes present."""
    from repro.models.opt_flags import FLAGS
    if not FLAGS.moe_local_dispatch:
        return x
    import jax
    from jax.sharding import PartitionSpec as P

    def resolve(ax):
        if ax != "data_batch":
            return ax
        try:
            mesh = jax.sharding.get_abstract_mesh()
            if mesh is not None and "pod" in mesh.axis_names:
                return ("pod", "data")
        except Exception:  # noqa: BLE001
            pass
        return "data"

    try:
        return jax.lax.with_sharding_constraint(
            x, P(*[resolve(a) for a in logical]))
    except (ValueError, RuntimeError):
        return x  # no mesh (plain CPU tests)


def moe_params(cfg: ModelConfig, rng) -> Dict:
    D, F, E, pd = cfg.d_model, cfg.moe_d_ff, cfg.n_held, L.pdtype_of(cfg)
    ks = jax.random.split(rng, 5)
    # the sigmoid gate's weights are stored like the rest and upcast in
    # the gate, as the published gate does
    rdt = jnp.float32 if cfg.router == "softmax" else pd
    p = {"router": L.dense_init(ks[0], (D, cfg.n_experts), rdt),
         "w_gate": L.dense_init(ks[1], (E, D, F), pd),
         "w_up": L.dense_init(ks[2], (E, D, F), pd),
         "w_down": L.dense_init(ks[3], (E, F, D), pd)}
    if cfg.router == "sigmoid":
        p["router_bias"] = jnp.zeros((cfg.n_experts,), pd)
    if cfg.n_shared_experts:
        p["shared"] = L.mlp_params(cfg, ks[4],
                                   d_ff=cfg.moe_d_ff * cfg.n_shared_experts)
    return p


def _capacity(cfg: ModelConfig, n_tokens: int, decode: bool) -> int:
    """Capacity per expert.  Decode uses a higher factor (drops at decode
    hurt generation quality) and is exactly dropless when the batch is
    small enough that C would reach T*K anyway."""
    cf = 4.0 if decode else cfg.capacity_factor
    c = int(n_tokens * cfg.experts_per_tok * cf / cfg.n_experts)
    c = max(4, -(-c // 4) * 4)  # round up to a multiple of 4
    return min(c, n_tokens * cfg.experts_per_tok)


def route(cfg: ModelConfig, p: Dict, xf: jnp.ndarray
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(gates (T,K) f32, experts (T,K) int32, aux loss) of tokens ``xf``
    (T,D) over all ``n_experts``."""
    E, K = cfg.n_experts, cfg.experts_per_tok
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, eidx = jax.lax.top_k(scores + p["router_bias"].astype(jnp.float32),
                                K)
        gates = jnp.take_along_axis(scores, eidx, axis=-1)
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
        return gates * cfg.routed_scaling, eidx, jnp.float32(0.0)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, K)
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    # Switch load-balance aux loss
    frac = jnp.mean(jax.nn.one_hot(eidx[:, 0], E, dtype=jnp.float32), axis=0)
    aux = jnp.float32(E) * jnp.sum(frac * jnp.mean(probs, axis=0)) \
        * cfg.router_aux_coef
    return gates, eidx, aux


def _swiglu_experts(cfg: ModelConfig, p: Dict, buf, dot):
    """The expert FFN through ``dot(x, w)``, one product per weight."""
    if cfg.act == "swiglu":
        g = dot(buf, p["w_gate"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(buf.dtype) \
            * dot(buf, p["w_up"])
    else:
        h = jax.nn.gelu(dot(buf, p["w_up"]).astype(jnp.float32)
                        ).astype(buf.dtype)
    return dot(h, p["w_down"])


def _capacity_dispatch(cfg: ModelConfig, p: Dict, xf, gates, eidx,
                       decode: bool):
    """Experts over per-expert buffers of ``_capacity`` rows; pairs above
    it are dropped.  Every expert is held."""
    T, D = xf.shape
    E, K = cfg.n_experts, cfg.experts_per_tok
    C = _capacity(cfg, T, decode)
    # position of each (token, k) within its expert's capacity buffer:
    # rank = #earlier (token', k') routed to the same expert.
    flat_e = eidx.reshape(-1)                            # (T*K,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (T*K, E)
    ranks = jnp.cumsum(onehot, axis=0) - onehot          # exclusive cumsum
    pos = jnp.take_along_axis(ranks, flat_e[:, None], axis=1)[:, 0]  # (T*K,)
    keep = pos < C

    # scatter tokens into (E, C, D)
    buf = jnp.zeros((E, C, D), xf.dtype)
    src = jnp.repeat(xf, K, axis=0)                      # (T*K, D)
    safe_pos = jnp.where(keep, pos, 0)
    src = _constrain(src, ("data_batch", None))
    buf = buf.at[flat_e, safe_pos].add(
        jnp.where(keep[:, None], src, 0), mode="drop")
    buf = _constrain(buf, ("model", None, None))

    out_buf = _swiglu_experts(
        cfg, p, buf, lambda x, w: jnp.einsum("ecd,edf->ecf", x, w))
    out_buf = _constrain(out_buf, ("model", None, None))

    # gather back and combine with gates
    gathered = out_buf[flat_e, safe_pos]                 # (T*K, D)
    gathered = _constrain(gathered, ("data_batch", None))
    gathered = jnp.where(keep[:, None], gathered, 0)
    g = gates.reshape(-1).astype(xf.dtype)
    return jnp.sum((gathered * g[:, None]).reshape(T, K, D), axis=1)


def _dropless_dispatch(cfg: ModelConfig, p: Dict, xf, gates, local, held):
    """The held experts' part of the result, no pair dropped: pairs on
    held experts sorted by expert, one grouped product per weight."""
    T, D = xf.shape
    K, E = cfg.experts_per_tok, cfg.n_held
    key = jnp.where(held, local, E).reshape(-1)          # (T*K,)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=E + 1)[:E].astype(jnp.int32)
    tok = order // K
    out = _swiglu_experts(
        cfg, p, xf[tok],
        lambda x, w: jax.lax.ragged_dot(x, w, sizes))    # (T*K, D)
    # rows past the held pairs are in no group: whatever the product
    # left there is masked out
    g = gates.reshape(-1)[order][:, None]
    out = jnp.where(held.reshape(-1)[order][:, None],
                    out.astype(jnp.float32) * g, 0.0)
    return jnp.zeros((T, D), jnp.float32).at[tok].add(out).astype(xf.dtype)


def moe_apply(cfg: ModelConfig, p: Dict, x: jnp.ndarray,
              decode: bool = False, valid: Optional[jnp.ndarray] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (B, S, D), aux_loss (f32 scalar), counts
    (n_held + 1,) int32: the pairs of the ``valid`` (B, S) tokens (all
    where None) routed to each held expert, then to experts held
    elsewhere."""
    B, S, D = x.shape
    E = cfg.n_held
    xf = x.reshape(B * S, D)
    gates, eidx, aux = route(cfg, p, xf)
    local = eidx - cfg.expert_first
    held = (local >= 0) & (local < E)
    if cfg.capacity_factor:
        y = _capacity_dispatch(cfg, p, xf, gates, eidx, decode)
    else:
        y = _dropless_dispatch(cfg, p, xf, gates, local, held)

    if cfg.n_shared_experts:
        y = y + L.mlp_apply(cfg, p["shared"], xf)

    ok = jnp.ones((B * S, 1), bool) if valid is None \
        else valid.reshape(B * S, 1)
    key = jnp.where(ok, jnp.where(held, local, E), E + 1).reshape(-1)
    counts = jnp.bincount(key, length=E + 2)[:E + 1].astype(jnp.int32)
    return y.reshape(B, S, D), aux, counts
