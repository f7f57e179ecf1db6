"""Latent attention (MLA) and the sigmoid-routed, dropless expert layer
that holds a share of the experts (Moonlight-16B-A3B, ``deepseek_v3``),
at a reduced size on the CPU in float32: the served path through
``ServingEngine`` and the latent pool against a plain full forward
written here, absorbed against expanded attention, expert shares against
the uncut layer, dropless dispatch, the correction bias, and a latent
block's round trip through the host tier."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.prodcache import EMPTY
from repro.kvcache.pool import BlockPool
from repro.launch import serve
from repro.models import mla, moe
from repro.models import transformer as T
from repro.models.model import build
from repro.serving.engine import Request, ServingEngine

# one dense layer and two MoE layers; 16 routed experts of which this
# chip holds 8, 4 a token; one shared expert
CFG = dataclasses.replace(
    reduced(get_config("moonlight-16b-a3b")), n_layers=3, n_experts=16,
    experts_held=8, experts_per_tok=4)
HI = jax.lax.Precision.HIGHEST


def _params(cfg=CFG, seed=0):
    """Seeded weights with a correction bias of standard deviation 0.2
    (the program initialises it to zero)."""
    p = build(cfg).init(jax.random.PRNGKey(seed))
    bias = p["moe"]["router_bias"]
    p["moe"]["router_bias"] = 0.2 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), bias.shape, bias.dtype)
    return p


@pytest.fixture(scope="module")
def params():
    return _params()


# -- the plain full forward -------------------------------------------------------

def _rms(x, w, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Half-split rotary embedding over the last axis; x (S, ..., d)."""
    S, d = x.shape[0], x.shape[-1]
    ang = np.arange(S)[:, None] / theta ** (np.arange(0, d, 2) / d)
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (d // 2,))
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x2 * np.cos(ang) + x1 * np.sin(ang)], -1)


def _silu(x):
    return x / (1 + np.exp(-x))


def _swiglu(h, g, u, d):
    return (_silu(h @ g) * (h @ u)) @ d


def plain_moe(cfg, m, h, first=None, held=None):
    """The expert layer as written down, token by token: sigmoid scores,
    the top k of scores plus bias, gates from the scores alone,
    normalised and scaled; the held experts' part plus the shared
    expert."""
    first = cfg.expert_first if first is None else first
    held = cfg.n_held if held is None else held
    m = jax.tree.map(lambda a: np.asarray(a, np.float64), m)
    s = 1 / (1 + np.exp(-(h @ m["router"])))
    y = _swiglu(h, m["shared"]["w_gate"], m["shared"]["w_up"],
                m["shared"]["w_down"])
    for t in range(h.shape[0]):
        top = np.argsort(-(s[t] + m["router_bias"]), kind="stable")
        top = top[:cfg.experts_per_tok]
        g = s[t, top] / s[t, top].sum() * cfg.routed_scaling
        for e, ge in zip(top, g):
            if first <= e < first + held:
                j = e - first
                y[t] += ge * _swiglu(h[t], m["w_gate"][j], m["w_up"][j],
                                     m["w_down"][j])
    return y


def plain_logits(cfg, p, toks):
    """Logits (S, V) of ``toks`` in float64: expanded MLA, causal, one
    layer at a time."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    H, R, nope = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    S, eps = len(toks), cfg.norm_eps
    x = p["embed"]["tok"][np.asarray(toks)]
    for i in range(cfg.n_layers):
        b = jax.tree.map(lambda a: a[i], p["blocks"])
        a = b["attn"]
        h = _rms(x, b["ln1"]["w"], eps)
        q = (h @ a["wq"]).reshape(S, H, -1)
        lat = h @ a["wkv_a"]
        c = _rms(lat[:, :R], a["kv_norm"], eps)
        k_pe = _rope(lat[:, R:], cfg.rope_theta)
        q_pe = _rope(q[..., nope:], cfg.rope_theta)
        kv = (c @ a["wkv_b"]).reshape(S, H, -1)
        sc = (np.einsum("qhd,khd->hqk", q[..., :nope], kv[..., :nope])
              + np.einsum("qhd,kd->hqk", q_pe, k_pe)) \
            / np.sqrt(nope + cfg.qk_rope_head_dim)
        sc = np.where(np.tril(np.ones((S, S), bool)), sc, -np.inf)
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        o = np.einsum("hqk,khd->qhd", pr, kv[..., nope:]).reshape(S, -1)
        x = x + o @ a["wo"]
        h = _rms(x, b["ln2"]["w"], eps)
        if i < cfg.n_dense_layers:
            d = jax.tree.map(lambda a: a[i], p["dense"])
            x = x + _swiglu(h, d["w_gate"], d["w_up"], d["w_down"])
        else:
            m = jax.tree.map(lambda a: a[i - cfg.n_dense_layers], p["moe"])
            x = x + plain_moe(cfg, m, h)
    x = _rms(x, p["embed"]["final_norm"]["w"], eps)
    return x @ p["embed"]["lm_head"]


def _close(got, want, tol=1e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.max(np.abs(want))
    assert np.max(np.abs(got - want)) / scale < tol, \
        (np.max(np.abs(got - want)), scale)


# -- the served path ------------------------------------------------------------------

def test_served_prefill_and_paged_decode_match_the_plain_forward(params):
    """Three prompts (one bucket-padded mid-block) through the engine's
    prefill, latent block writes and paged absorbed decode, against the
    plain forward of each whole sequence, at every served position."""
    eng = ServingEngine(build(CFG), params, block_size=8, hbm_blocks=24,
                        max_batch=4, max_blocks_per_seq=8)
    assert eng.pool.names == ("latent",)
    # latent rows of 32 + 8 values, padded to one 128-lane tile
    assert eng.pool.latent.shape == (3, eng.pool.policy.n_slots, 8, 128)
    seen = {}
    prefill, decode = eng._prefill_fn, eng._decode_logits

    def spy_prefill(prm, toks, *rest):
        out = prefill(prm, toks, *rest)
        seen["prefill"] = out[0]
        return out

    def spy_decode(ids):
        out = decode(ids)
        for i, rid in enumerate(ids):
            st = eng.mgr.seqs[rid]
            seen.setdefault(rid, []).append((st.length - 1, out[i]))
        return out

    eng._prefill_fn, eng._decode_logits = spy_prefill, spy_decode
    rng = np.random.default_rng(5)
    prompts = [list(map(int, rng.integers(1, CFG.vocab, n)))
               for n in (13, 16, 9)]
    for rid, pr in enumerate(prompts):
        eng._start(Request(rid, pr, max_new=7))
        seen.setdefault(rid, []).append((len(pr) - 1,
                                         seen["prefill"][0, len(pr) - 1]))
    for _ in range(6):
        eng._decode_step([0, 1, 2])
    for rid, pr in enumerate(prompts):
        seq = pr + eng.mgr.seqs[rid].out_tokens[:-1]
        want = plain_logits(CFG, params, seq)
        got = dict(seen[rid])
        assert sorted(got) == list(range(len(pr) - 1, len(seq)))
        for pos, lg in got.items():
            _close(lg, want[pos])
    snap = eng.obs_snapshot()
    held = snap.counters['serve_moe_routed_total{where="held"}']
    away = snap.counters['serve_moe_routed_total{where="away"}']
    # every real token's K pairs in each of the two MoE layers, padded
    # prompt positions and padded decode rows not counted
    n_tok = sum(prompts_len + 6 for prompts_len in map(len, prompts))
    assert held + away == 2 * CFG.experts_per_tok * n_tok
    assert held == sum(
        snap.counters[f'serve_moe_expert_tokens_total{{expert="{e}"}}']
        for e in range(8))
    assert 0 < snap.gauges["serve_moe_expert_tokens_max"] <= 3
    assert snap.gauges["pool_block_bytes"] == 3 * 8 * 128 * 4


def test_the_dense_decode_step_fetches_nothing_new():
    """A model without experts: the decode program returns no counts and
    the engine keeps no expert counters."""
    cfg = reduced(get_config("granite-3-8b"))
    api = build(cfg)
    eng = ServingEngine(api, api.init(jax.random.PRNGKey(0)), block_size=8,
                        hbm_blocks=16, max_batch=2)
    eng._start(Request(0, [3, 4, 5, 6, 7], max_new=3))
    eng._decode_logits([0])
    assert eng._step_counts is None
    assert eng.pool.names == ("kpool", "vpool")
    assert not [k for k in eng.obs_snapshot().counters if "moe" in k]


def test_the_launcher_serves_moonlight_through_the_engine():
    cfg = serve.model_config("moonlight-16b-a3b")
    reqs = serve.make_requests(cfg.vocab, 3, prefix_len=16, max_new=4)
    eng, done, _ = serve.run(cfg, reqs, block_size=8, hbm_blocks=16,
                             max_batch=2)
    assert isinstance(eng, ServingEngine) and eng.pool.names == ("latent",)
    assert [len(c.tokens) for c in done] == [4, 4, 4]


# -- the layers ---------------------------------------------------------------------------

def test_absorbed_decode_equals_expanded_attention(params):
    """The last position's attention output, expanded over the whole
    sequence and absorbed over latent blocks in a shuffled pool."""
    a = jax.tree.map(lambda x: x[0], params["blocks"]["attn"])
    S, bs = 21, 8
    h = jax.random.normal(jax.random.PRNGKey(3), (1, S, CFG.d_model))
    pos = jnp.arange(S)[None]
    want, latent = mla.attend_full(CFG, a, h, pos)
    slots = np.array([5, 1, 3])               # blocks of the sequence
    pool = jnp.zeros((6, bs, latent.shape[-1]))
    flat = jnp.zeros((3 * bs, latent.shape[-1])).at[:S - 1].set(
        latent[0, :S - 1])
    pool = pool.at[slots].set(flat.reshape(3, bs, -1))
    got, pool = mla.attend_paged(
        CFG, a, h[:, S - 1:], pos[:, S - 1:], pool, jnp.asarray(slots)[None],
        jnp.asarray([S - 1]), jnp.asarray([slots[(S - 1) // bs]]),
        jnp.asarray([(S - 1) % bs]))
    _close(got[0, 0], want[0, -1], 1e-5)
    np.testing.assert_allclose(pool[slots[2], (S - 1) % bs],
                               latent[0, S - 1], rtol=1e-6)


def _moe_tokens(seed=4, n=24):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, n, CFG.d_model))


def test_expert_shares_add_up_to_the_uncut_layer(params):
    """Two chips' disjoint shares of 8 experts each, with the shared
    expert counted once, give the layer with all 16 experts."""
    full = dataclasses.replace(CFG, experts_held=0)
    m = jax.tree.map(lambda a: a[0], params["moe"])
    whole = dict(m)
    key = jax.random.PRNGKey(9)
    for w in ("w_gate", "w_up", "w_down"):
        other = jax.random.normal(key, m[w].shape) / np.sqrt(m[w].shape[-2])
        whole[w] = jnp.concatenate([m[w], other], axis=0)   # 16 experts
        key = jax.random.fold_in(key, 1)
    x = _moe_tokens()
    y_full, _, c_full = moe.moe_apply(full, whole, x)
    parts, counts = [], []
    for first in (0, 8):
        share = dataclasses.replace(CFG, expert_first=first)
        mp = dict(whole, **{w: whole[w][first:first + 8]
                            for w in ("w_gate", "w_up", "w_down")})
        y, _, c = moe.moe_apply(share, mp, x)
        parts.append(y)
        counts.append(np.asarray(c))
    shared = moe.L.mlp_apply(CFG, whole["shared"], x)
    _close(parts[0] + parts[1] - shared, y_full, 1e-5)
    _close(y_full[0], plain_moe(full, whole, np.asarray(x[0])), 1e-5)
    # each share counts its own experts' pairs and the other's as away
    assert counts[0][8] == counts[1][:8].sum()
    assert counts[1][8] == counts[0][:8].sum()
    np.testing.assert_array_equal(np.concatenate([counts[0][:8],
                                                  counts[1][:8]]),
                                  np.asarray(c_full)[:16])


def test_dropless_when_every_token_picks_one_expert(params):
    """A bias that sends every token to held expert 3: none is dropped
    (a capacity of 1.25 would keep 24 * 4 * 1.25 / 16 = 8 of 24)."""
    m = dict(jax.tree.map(lambda a: a[0], params["moe"]))
    m["router_bias"] = m["router_bias"].at[3].set(100.0)
    x = _moe_tokens(n=24)
    y, _, counts = moe.moe_apply(CFG, m, x)
    assert int(counts[3]) == 24
    _close(y[0], plain_moe(CFG, m, np.asarray(x[0])), 1e-5)
    capped = dataclasses.replace(CFG, capacity_factor=1.25, experts_held=0,
                                 n_experts=8)
    assert moe._capacity(capped, 24, decode=False) < 24


def test_the_correction_bias_chooses_but_does_not_weight(params):
    m = jax.tree.map(lambda a: a[1], params["moe"])
    xf = _moe_tokens(seed=6, n=64)[0]
    g_b, e_b, _ = moe.route(CFG, m, xf)
    g_0, e_0, _ = moe.route(CFG, dict(m, router_bias=jnp.zeros(16)), xf)
    changed = np.any(np.sort(e_b, -1) != np.sort(e_0, -1), axis=-1)
    assert 0 < changed.mean() < 1
    s = jax.nn.sigmoid(jnp.dot(xf, m["router"], precision=HI))
    for g, e in ((g_b, e_b), (g_0, e_0)):
        chosen = jnp.take_along_axis(s, e, -1)
        np.testing.assert_allclose(
            g, chosen / chosen.sum(-1, keepdims=True) * CFG.routed_scaling,
            rtol=1e-5)


def test_a_latent_block_survives_flush_evict_and_swap_in():
    """write -> flush to host -> evicted by other blocks -> swapped back
    in: the same bits."""
    pool = BlockPool(CFG, 4, 8)
    shape = (CFG.n_layers, 8) + CFG.cache_spec()["latent"]
    block = jax.random.normal(jax.random.PRNGKey(2), shape)
    slot, fill = pool.lookup(1, pin=False)
    assert fill
    pool.write_block(slot, block, key=1)
    pool.run_flusher()
    assert 1 in pool.host and len(pool.host[1]) == 1
    for k in range(2, 12):                    # evicts key 1
        s, f = pool.lookup(k, pin=False)
        if f:
            pool.write_block(s, jnp.zeros(shape), key=k)
    assert pool.policy.slot_of(1) == EMPTY
    slot, fill = pool.lookup(1, pin=False)
    assert not fill and pool.stats.swap_in >= 1
    np.testing.assert_array_equal(np.asarray(pool.latent[:, slot]),
                                  np.asarray(block))
