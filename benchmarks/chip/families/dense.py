"""Dense decoder: grouped-query attention and a gated (SwiGLU) MLP in
every layer, the program's ``family="dense"``.  A configuration file
that names no ``"family"`` is this one.

Work is counted from the configuration's shapes and the true lengths of
the real rows, never from how the program moves bytes: the padded rows
of a batch, the padded positions of a prefill bucket and copies of the
KV pool are not required, so a program that stops making them reaches a
larger share of the roofline while the yardstick stays.

The reference is the configuration's forward pass in float32 with
matrix products at full precision (``chipbench.reference``), one layer
at a time over whole sequences, over the benchmark's own weights.
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

def model_config(conf: Dict):
    """The program's ``ModelConfig`` at the configuration file's sizes,
    refusing a file that states what the program would not run."""
    from repro.launch import serve

    cfg = serve.model_config(conf["arch"], conf["num_hidden_layers"])
    cfg = dataclasses.replace(
        cfg, n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or 0, d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"], dtype=served_dtype(conf),
        param_dtype=served_dtype(conf))
    stated = {"norm": conf["norm"], "tie_embeddings":
              conf["tie_word_embeddings"], "rope_theta": conf["rope_theta"],
              "act": "swiglu" if conf["hidden_act"] == "silu" else "?",
              "family": "dense", "qkv_bias": conf["attention_bias"],
              "rope_frac": 1.0}
    runs = {k: getattr(cfg, k) for k in stated}
    if runs != stated or cfg.qkv_bias:
        raise ValueError(f"{conf['name']}: the program runs {runs}, the "
                         f"configuration file states {stated}, and this "
                         "family's reference has no q/k/v biases")
    return cfg


# -- required work ------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Shapes:
    """The sizes required work is counted from."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    norm_params: int        # parameters of one norm (0: non-parametric)
    itemsize: int = 2       # bytes of one weight or KV element

    @classmethod
    def of(cls, conf: Dict) -> "Shapes":
        """From a configuration file's keys."""
        d = conf["hidden_size"]
        heads = conf["num_attention_heads"]
        return cls(n_layers=conf["num_hidden_layers"], d_model=d,
                   n_heads=heads, n_kv_heads=conf["num_key_value_heads"],
                   head_dim=conf.get("head_dim") or d // heads,
                   d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
                   tied=conf["tie_word_embeddings"],
                   norm_params=0 if conf["norm"] == "nonparam_ln" else d,
                   itemsize=ITEMSIZE[conf["serving"]["dtype"]])

    @property
    def layer_matmul_params(self) -> int:
        """Weights of one layer's matrix products: q, k, v, o and the
        gated MLP's three."""
        d, hd = self.d_model, self.head_dim
        attn = 2 * d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
        return attn + 3 * d * self.d_ff

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    @property
    def weight_bytes(self) -> int:
        """Every weight a step reads once: the layers, the final norm and
        the output head (the embedding table itself when tied)."""
        n = (self.n_layers * (self.layer_matmul_params + 2 * self.norm_params)
             + self.norm_params + self.head_params)
        return n * self.itemsize

    @property
    def kv_bytes_per_token(self) -> int:
        return (2 * self.n_layers * self.n_kv_heads * self.head_dim
                * self.itemsize)

    def attn_flops(self, ctx: int) -> int:
        """Scores and weighted values of one query over ``ctx`` keys."""
        return 4 * self.n_layers * self.n_heads * self.head_dim * ctx


def prefill_work(s: Shapes, positions: Iterable[int]) -> Tuple[int, int]:
    """(FLOPs, bytes) a prefill requires when the KV of the token at each
    of ``positions`` has to be computed and written, and the logits of the
    last prompt token (always among them) are needed.  The cached context
    those tokens attend to is read once."""
    pos = sorted(positions)
    flops = sum(2 * s.n_layers * s.layer_matmul_params
                + s.attn_flops(p + 1) for p in pos)
    flops += 2 * s.head_params
    ctx = pos[-1] + 1 if pos else 0
    nbytes = (s.weight_bytes + len(pos) * s.d_model * s.itemsize
              + ctx * s.kv_bytes_per_token)
    return flops, nbytes


def decode_work(s: Shapes, contexts: Iterable[int]) -> Tuple[int, int]:
    """(FLOPs, bytes) one decode step requires for real rows whose new
    token attends to ``contexts`` positions (its own included): every
    weight once, each row's context KV read once, its new KV written."""
    ctx = list(contexts)
    flops = sum(2 * (s.n_layers * s.layer_matmul_params + s.head_params)
                + s.attn_flops(c) for c in ctx)
    nbytes = (s.weight_bytes + len(ctx) * s.d_model * s.itemsize
              + sum(ctx) * s.kv_bytes_per_token)
    return flops, nbytes


# -- weights --------------------------------------------------------------------

def weight_shapes(s: Shapes) -> Dict[str, tuple]:
    """Every weight's shape; absent weights (a tied head, non-parametric
    norms) are left out."""
    L, D, F, V = s.n_layers, s.d_model, s.d_ff, s.vocab
    q, kv = s.n_heads * s.head_dim, s.n_kv_heads * s.head_dim
    out = {"embed": (V, D), "wq": (L, D, q), "wk": (L, D, kv),
           "wv": (L, D, kv), "wo": (L, q, D), "w_gate": (L, D, F),
           "w_up": (L, D, F), "w_down": (L, F, D)}
    if not s.tied:
        out["head"] = (D, V)
    if s.norm_params:
        out.update(attn_norm=(L, D), mlp_norm=(L, D), final_norm=(D,))
    return out


def program_params(w: Dict[str, jax.Array], s: Shapes, expected) -> Dict:
    """The program's parameter tree over the same arrays, checked against
    ``expected`` (``weights.checked``)."""
    L = s.n_layers
    dt = w["embed"].dtype

    def norm(name, lead):
        if s.norm_params:
            return {"w": w[name]}
        return {"_": jnp.zeros(lead + (1,), dt)}

    embed = {"tok": w["embed"], "final_norm": norm("final_norm", ())}
    if not s.tied:
        embed["lm_head"] = w["head"]
    tree = {"embed": embed,
            "blocks": {"ln1": norm("attn_norm", (L,)),
                       "ln2": norm("mlp_norm", (L,)),
                       "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
                       "mlp": {k: w[k] for k in ("w_gate", "w_up",
                                                 "w_down")}}}
    return weights.checked(tree, expected)


# -- the reference ------------------------------------------------------------------

def _layer(x, lw: Dict, conf: Dict, mode: str):
    B, S, D = x.shape
    H, Hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf.get("head_dim") or D // H
    res = conf["residual_multiplier"]
    h = _norm(x, lw.get("attn_norm"), conf)
    q = _rope(_mm(h, lw["wq"], mode).reshape(B, S, H, hd),
              conf["rope_theta"])
    k = _rope(_mm(h, lw["wk"], mode).reshape(B, S, Hkv, hd),
              conf["rope_theta"])
    v = _mm(h, lw["wv"], mode).reshape(B, S, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
    s = s * conf["attention_multiplier"]
    causal = np.tril(np.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)
    x = x + res * _mm(o.reshape(B, S, H * hd), lw["wo"], mode)
    h = _norm(x, lw.get("mlp_norm"), conf)
    m = jax.nn.silu(_mm(h, lw["w_gate"], mode)) * _mm(h, lw["w_up"], mode)
    return x + res * _mm(m, lw["w_down"], mode)


_LAYER_KEYS = ("attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "w_gate",
               "w_up", "w_down")


@functools.partial(jax.jit, static_argnames=("conf_items", "mode"))
def _layer_jit(x, lw, conf_items, mode):
    return _layer(x, lw, dict(conf_items), mode)


@functools.partial(jax.jit, static_argnames=("conf_items", "mode"))
def _head_jit(x, rows_b, rows_s, w, conf_items, mode):
    conf = dict(conf_items)
    h = _norm(x[rows_b, rows_s], w.get("final_norm"), conf)
    head = w["head"] if "head" in w else w["embed"].T
    return _mm(h, head, mode) / conf["logits_scaling"]


def _conf_items(conf: Dict) -> Tuple:
    keys = ("norm", "rms_norm_eps", "layer_norm_eps", "num_attention_heads",
            "num_key_value_heads", "head_dim", "rope_theta",
            "residual_multiplier", "attention_multiplier", "logits_scaling")
    return tuple((k, conf.get(k)) for k in keys)


def logits_at(w: Dict, conf: Dict, seqs: Sequence[np.ndarray],
              rows: Sequence[Tuple[int, int]], *, pad_to: int,
              mode: str = "f32") -> jax.Array:
    """Logits, (len(rows), vocab) float32, at each (sequence, position)
    of ``rows``.  Sequences are padded at the end to ``pad_to`` tokens,
    which causal attention leaves without effect."""
    toks = np.zeros((len(seqs), pad_to), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    items = _conf_items(conf)
    x = jnp.take(w["embed"], jnp.asarray(toks), axis=0).astype(jnp.float32)
    x = x * conf["embedding_multiplier"]
    for layer in range(conf["num_hidden_layers"]):
        lw = {k: w[k][layer] for k in _LAYER_KEYS if k in w}
        x = _layer_jit(x, lw, items, mode)
    rb = jnp.asarray([r[0] for r in rows], jnp.int32)
    rs = jnp.asarray([r[1] for r in rows], jnp.int32)
    hw = {k: w[k] for k in ("final_norm", "head", "embed") if k in w}
    return _head_jit(x, rb, rs, hw, items, mode)
