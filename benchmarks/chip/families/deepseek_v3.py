"""DeepSeek-V3 decoder (Moonlight-16B-A3B): multi-head latent attention
(MLA) in every layer; a dense gated MLP in the first
``first_k_dense_replace`` layers and a mixture of experts in the rest,
with sigmoid scores, a correction bias that chooses the top
``num_experts_per_tok`` but never weights them (``noaux_tc`` with one
group), gates renormalised and scaled by ``routed_scaling_factor``, and
shared experts.  The program's ``family="moe"`` with ``kv_lora_rank``.

The configuration file holds one chip's share of an expert-parallel
deployment: ``n_routed_experts`` is the number of experts held here,
from ``expert_first``, and ``source_values`` gives the published count
the router routes over.  Program and reference compute only the held
experts' part of each expert layer, with the gates from all of them.

Work is counted from the configuration's shapes and the true lengths of
the real rows: prefill counts MLA expanded (``Wkv_b`` over every new
token), decode counts it absorbed (``Wkv_b`` folded into the query and
the output, attention over latent rows).  Routed-expert FLOPs are the
expected held experts a token, ``num_experts_per_tok * held /
published``, from rows and never from routing; every held expert's
weights are read once a call.

The reference is the forward pass in float32 with matrix products at
full precision (``chipbench.reference``), expanded MLA one layer at a
time over whole sequences, the expert layer over every held expert for
every token, weighted by the gates.  RoPE is half-split in program and
reference; the published code first permutes q_pe's and k_pe's
interleaved pairs to halves, which with random weights is a fixed
permutation of ``wq``'s and ``wkv_a``'s columns.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights
from chipbench.harness import served_dtype
from chipbench.reference import HIGHEST, _mm, _norm, _rope
from chipbench.work import ITEMSIZE


# -- the program --------------------------------------------------------------

def published_experts(conf: Dict) -> int:
    return conf.get("source_values", {}).get("n_routed_experts",
                                             conf["n_routed_experts"])


def model_config(conf: Dict):
    """The program's ``ModelConfig`` at the configuration file's sizes
    and expert share, refusing a file that states what the program would
    not run."""
    from repro.launch import serve

    runs_only = {"hidden_act": "silu", "q_lora_rank": None,
                 "scoring_func": "sigmoid", "topk_method": "noaux_tc",
                 "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
                 "moe_layer_freq": 1, "attention_bias": False,
                 "tie_word_embeddings": False, "norm": "rmsnorm"}
    stated = {k: conf.get(k) for k in runs_only}
    if stated != runs_only:
        raise ValueError(f"{conf['name']}: the program runs {runs_only}, "
                         f"the configuration file states {stated}")
    cfg = serve.model_config(conf["arch"], conf["num_hidden_layers"])
    return dataclasses.replace(
        cfg, n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        rope_theta=conf["rope_theta"], norm_eps=conf["rms_norm_eps"],
        kv_lora_rank=conf["kv_lora_rank"],
        qk_nope_head_dim=conf["qk_nope_head_dim"],
        qk_rope_head_dim=conf["qk_rope_head_dim"],
        v_head_dim=conf["v_head_dim"],
        n_dense_layers=conf["first_k_dense_replace"],
        n_experts=published_experts(conf),
        experts_held=conf["n_routed_experts"],
        expert_first=conf["expert_first"],
        experts_per_tok=conf["num_experts_per_tok"],
        moe_d_ff=conf["moe_intermediate_size"],
        n_shared_experts=conf["n_shared_experts"], router="sigmoid",
        routed_scaling=conf["routed_scaling_factor"], capacity_factor=0.0,
        dtype=served_dtype(conf), param_dtype=served_dtype(conf))


# -- required work ------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Shapes:
    """The sizes required work is counted from."""

    n_layers: int
    n_dense: int            # leading dense-MLP layers
    d_model: int
    n_heads: int
    kv_lora: int
    nope: int
    rope: int
    v_dim: int
    d_ff: int               # the dense layers' MLP
    expert_ff: int
    held: int               # routed experts held here
    routed: int             # routed experts published (the router's width)
    topk: int
    n_shared: int
    vocab: int
    itemsize: int = 2

    @classmethod
    def of(cls, conf: Dict) -> "Shapes":
        return cls(n_layers=conf["num_hidden_layers"],
                   n_dense=conf["first_k_dense_replace"],
                   d_model=conf["hidden_size"],
                   n_heads=conf["num_attention_heads"],
                   kv_lora=conf["kv_lora_rank"],
                   nope=conf["qk_nope_head_dim"],
                   rope=conf["qk_rope_head_dim"], v_dim=conf["v_head_dim"],
                   d_ff=conf["intermediate_size"],
                   expert_ff=conf["moe_intermediate_size"],
                   held=conf["n_routed_experts"],
                   routed=published_experts(conf),
                   topk=conf["num_experts_per_tok"],
                   n_shared=conf["n_shared_experts"],
                   vocab=conf["vocab_size"],
                   itemsize=ITEMSIZE[conf["serving"]["dtype"]])

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.n_dense

    @property
    def q_params(self) -> int:
        return self.d_model * self.n_heads * (self.nope + self.rope)

    @property
    def kv_a_params(self) -> int:
        return self.d_model * (self.kv_lora + self.rope)

    @property
    def kv_b_params(self) -> int:
        return self.kv_lora * self.n_heads * (self.nope + self.v_dim)

    @property
    def o_params(self) -> int:
        return self.n_heads * self.v_dim * self.d_model

    @property
    def expert_params(self) -> int:
        return 3 * self.d_model * self.expert_ff

    @property
    def router_params(self) -> int:
        return self.d_model * self.routed

    @property
    def mlp_token_params(self) -> int:
        """Matrix weights one token multiplies in the MLPs of all layers:
        the dense MLPs, then per expert layer the router, the shared
        experts and the expected held experts."""
        routed = self.topk * self.held / self.routed * self.expert_params
        per_moe = (self.router_params + self.n_shared * self.expert_params
                   + routed)
        return round(self.n_dense * 3 * self.d_model * self.d_ff
                     + self.n_moe * per_moe)

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    @property
    def weight_bytes(self) -> int:
        """Every weight a step reads once: attention, norms, the dense
        MLPs, every held expert, the routers and their biases, the
        shared experts, the final norm and the output head."""
        attn = (self.q_params + self.kv_a_params + self.kv_b_params
                + self.o_params + self.kv_lora + 2 * self.d_model)
        moe = (self.router_params + self.routed
               + (self.held + self.n_shared) * self.expert_params)
        n = (self.n_layers * attn + self.n_dense * 3 * self.d_model * self.d_ff
             + self.n_moe * moe + self.d_model + self.head_params)
        return n * self.itemsize

    @property
    def latent_bytes_per_token(self) -> int:
        return self.n_layers * (self.kv_lora + self.rope) * self.itemsize

    def expanded_attn_flops(self, ctx: int) -> int:
        """Scores over [k_nope | k_pe] and weighted values, one query
        over ``ctx`` keys, every layer."""
        per_key = self.n_heads * (self.nope + self.rope + self.v_dim)
        return 2 * self.n_layers * per_key * ctx

    def absorbed_attn_flops(self, ctx: int) -> int:
        """Scores over latent rows [c | k_pe] and the weighted sum of c,
        one query over ``ctx`` latent rows, every layer."""
        per_row = self.n_heads * (2 * self.kv_lora + self.rope)
        return 2 * self.n_layers * per_row * ctx


def prefill_work(s: Shapes, positions: Iterable[int]) -> Tuple[int, int]:
    """(FLOPs, bytes) a prefill requires when the latent row of the
    token at each of ``positions`` has to be computed and written, and
    the logits of the last prompt token (always among them) are needed:
    MLA expanded, ``Wkv_b`` over each computed token.  The cached context
    those tokens attend to is read once."""
    pos = sorted(positions)
    attn = s.q_params + s.kv_a_params + s.kv_b_params + s.o_params
    per_token = 2 * (s.n_layers * attn + s.mlp_token_params)
    flops = sum(per_token + s.expanded_attn_flops(p + 1) for p in pos)
    flops += 2 * s.head_params
    ctx = pos[-1] + 1 if pos else 0
    nbytes = (s.weight_bytes + len(pos) * s.d_model * s.itemsize
              + ctx * s.latent_bytes_per_token)
    return flops, nbytes


def decode_work(s: Shapes, contexts: Iterable[int]) -> Tuple[int, int]:
    """(FLOPs, bytes) one decode step requires for real rows whose new
    token attends to ``contexts`` positions (its own included), MLA
    absorbed: ``Wkv_b``'s halves fold into the query and the output once
    a token.  Every weight once, each row's latent context read once."""
    ctx = list(contexts)
    attn = s.q_params + s.kv_a_params + s.kv_b_params + s.o_params
    per_row = 2 * (s.n_layers * attn + s.mlp_token_params + s.head_params)
    flops = sum(per_row + s.absorbed_attn_flops(c) for c in ctx)
    nbytes = (s.weight_bytes + len(ctx) * s.d_model * s.itemsize
              + sum(ctx) * s.latent_bytes_per_token)
    return flops, nbytes


# -- weights --------------------------------------------------------------------

def weight_shapes(s: Shapes) -> Dict[str, tuple]:
    """Every weight's shape: attention and its norms stacked over all
    layers, the dense MLPs over the dense layers, the expert layers'
    weights over the expert layers (the held experts only)."""
    L, D, V, E = s.n_layers, s.d_model, s.vocab, s.n_moe
    H, R, P = s.n_heads, s.kv_lora, s.rope
    k, F, Fe = s.n_dense, s.d_ff, s.expert_ff
    Fs = s.n_shared * Fe
    return {"embed": (V, D), "head": (D, V), "final_norm": (D,),
            "attn_norm": (L, D), "mlp_norm": (L, D),
            "wq": (L, D, H * (s.nope + P)), "wkv_a": (L, D, R + P),
            "kv_norm": (L, R), "wkv_b": (L, R, H * (s.nope + s.v_dim)),
            "wo": (L, H * s.v_dim, D),
            "dense_gate": (k, D, F), "dense_up": (k, D, F),
            "dense_down": (k, F, D),
            "router": (E, D, s.routed), "router_bias": (E, s.routed),
            "expert_gate": (E, s.held, D, Fe),
            "expert_up": (E, s.held, D, Fe),
            "expert_down": (E, s.held, Fe, D),
            "shared_gate": (E, D, Fs), "shared_up": (E, D, Fs),
            "shared_down": (E, Fs, D)}


def program_params(w: Dict[str, jax.Array], s: Shapes, expected) -> Dict:
    """The program's parameter tree over the same arrays (no copy),
    checked against ``expected`` (``weights.checked``)."""
    tree = {"embed": {"tok": w["embed"], "final_norm": {"w": w["final_norm"]},
                      "lm_head": w["head"]},
            "blocks": {"ln1": {"w": w["attn_norm"]},
                       "ln2": {"w": w["mlp_norm"]},
                       "attn": {k: w[k] for k in ("wq", "wkv_a", "kv_norm",
                                                  "wkv_b", "wo")}},
            "dense": {"w_gate": w["dense_gate"], "w_up": w["dense_up"],
                      "w_down": w["dense_down"]},
            "moe": {"router": w["router"], "router_bias": w["router_bias"],
                    "w_gate": w["expert_gate"], "w_up": w["expert_up"],
                    "w_down": w["expert_down"],
                    "shared": {"w_gate": w["shared_gate"],
                               "w_up": w["shared_up"],
                               "w_down": w["shared_down"]}}}
    return weights.checked(tree, expected)


# -- the reference ------------------------------------------------------------------

def _attention(x, lw: Dict, conf: Dict, mode: str):
    """Expanded MLA, causal, and the residual."""
    B, S, _ = x.shape
    H, R = conf["num_attention_heads"], conf["kv_lora_rank"]
    nope, rope = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    h = _norm(x, lw["attn_norm"], conf)
    q = _mm(h, lw["wq"], mode).reshape(B, S, H, nope + rope)
    a = _mm(h, lw["wkv_a"], mode)
    c = _norm(a[..., :R], lw["kv_norm"], conf)
    k_pe = _rope(a[..., None, R:], conf["rope_theta"])
    q_pe = _rope(q[..., nope:], conf["rope_theta"])
    kv = _mm(c, lw["wkv_b"], mode).reshape(B, S, H, -1)
    s = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], kv[..., :nope],
                    precision=HIGHEST)
         + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe[:, :, 0],
                      precision=HIGHEST)) / np.sqrt(nope + rope)
    causal = np.tril(np.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, kv[..., nope:], precision=HIGHEST)
    return x + _mm(o.reshape(B, S, -1), lw["wo"], mode)


def _swiglu(h, gate, up, down, mode):
    return _mm(jax.nn.silu(_mm(h, gate, mode)) * _mm(h, up, mode), down, mode)


def _dense_mlp(x, lw: Dict, conf: Dict, mode: str):
    h = _norm(x, lw["mlp_norm"], conf)
    return x + _swiglu(h, lw["dense_gate"], lw["dense_up"], lw["dense_down"],
                       mode)


def routing(h, router, bias, conf: Dict, mode: str):
    """(gates (..., held), chosen experts (..., k)): the top k of sigmoid
    scores plus bias over the published experts; each held expert's gate
    is its score over the chosen scores' sum, times the scaling factor,
    0 where it is not chosen."""
    s = jax.nn.sigmoid(_mm(h, router, mode))
    _, top = jax.lax.top_k(s + bias.astype(jnp.float32),
                           conf["num_experts_per_tok"])
    g = jnp.take_along_axis(s, top, axis=-1)
    g = g / jnp.sum(g, -1, keepdims=True) * conf["routed_scaling_factor"]
    local = jax.nn.one_hot(top - conf["expert_first"],
                           conf["n_routed_experts"], dtype=jnp.float32)
    return jnp.einsum("...k,...ke->...e", g, local, precision=HIGHEST), top


def _expert_mlp(x, lw: Dict, conf: Dict, mode: str):
    h = _norm(x, lw["mlp_norm"], conf)
    gates, _ = routing(h, lw["router"], lw["router_bias"], conf, mode)
    y = _swiglu(h, lw["shared_gate"], lw["shared_up"], lw["shared_down"],
                mode)
    for e in range(conf["n_routed_experts"]):
        y = y + gates[..., e:e + 1] * _swiglu(
            h, lw["expert_gate"][e], lw["expert_up"][e],
            lw["expert_down"][e], mode)
    return x + y


_ATTN_KEYS = ("attn_norm", "mlp_norm", "wq", "wkv_a", "kv_norm", "wkv_b",
              "wo")
_DENSE_KEYS = ("dense_gate", "dense_up", "dense_down")
_MOE_KEYS = ("router", "router_bias", "expert_gate", "expert_up",
             "expert_down", "shared_gate", "shared_up", "shared_down")


@functools.partial(jax.jit, static_argnames=("conf_items", "mode", "dense"))
def _layer_jit(x, lw, conf_items, mode, dense):
    conf = dict(conf_items)
    x = _attention(x, lw, conf, mode)
    return (_dense_mlp if dense else _expert_mlp)(x, lw, conf, mode)


@functools.partial(jax.jit, static_argnames=("conf_items", "mode"))
def _head_jit(x, rows_b, rows_s, w, conf_items, mode):
    h = _norm(x[rows_b, rows_s], w["final_norm"], dict(conf_items))
    return _mm(h, w["head"], mode)


def _conf_items(conf: Dict) -> Tuple:
    keys = ("norm", "rms_norm_eps", "num_attention_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "rope_theta",
            "num_experts_per_tok", "routed_scaling_factor", "expert_first",
            "n_routed_experts")
    return tuple((k, conf.get(k)) for k in keys)


def layer_weights(w: Dict, conf: Dict, layer: int) -> Dict:
    """One layer's weights from the stacks it is in."""
    k = conf["first_k_dense_replace"]
    lw = {n: w[n][layer] for n in _ATTN_KEYS}
    if layer < k:
        lw.update({n: w[n][layer] for n in _DENSE_KEYS})
    else:
        lw.update({n: w[n][layer - k] for n in _MOE_KEYS})
    return lw


def embed(w: Dict, seqs: Sequence[np.ndarray], pad_to: int):
    toks = np.zeros((len(seqs), pad_to), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    return jnp.take(w["embed"], jnp.asarray(toks), axis=0).astype(jnp.float32)


def logits_at(w: Dict, conf: Dict, seqs: Sequence[np.ndarray],
              rows: Sequence[Tuple[int, int]], *, pad_to: int,
              mode: str = "f32") -> jax.Array:
    """Logits, (len(rows), vocab) float32, at each (sequence, position)
    of ``rows``.  Sequences are padded at the end to ``pad_to`` tokens,
    which causal attention leaves without effect."""
    items = _conf_items(conf)
    x = embed(w, seqs, pad_to)
    for layer in range(conf["num_hidden_layers"]):
        x = _layer_jit(x, layer_weights(w, conf, layer), items, mode,
                       layer < conf["first_k_dense_replace"])
    rb = jnp.asarray([r[0] for r in rows], jnp.int32)
    rs = jnp.asarray([r[1] for r in rows], jnp.int32)
    hw = {k: w[k] for k in ("final_norm", "head")}
    return _head_jit(x, rb, rs, hw, items, mode)
