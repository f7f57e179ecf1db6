"""Weights from the seed, made on the device in one jitted call in the
type they are served in, in the benchmark's own layout.  The reference
reads this layout; ``program_params`` hands the same arrays to the
program in the layout its model code expects.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.work import Shapes

def key_of(seed: int) -> jax.Array:
    """A JAX key from any whole-number seed (wider than 32 bits too)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def shapes_of(s: Shapes) -> Dict[str, tuple]:
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


def make(s: Shapes, seed: int, dtype: str) -> Dict[str, jax.Array]:
    """Normal weights scaled by 1/sqrt(fan in), an embedding of standard
    deviation 0.02 and norm gains of 1, in ``dtype``."""
    shapes = shapes_of(s)
    dt = jnp.dtype(dtype)

    def build(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            if name.endswith("norm"):
                out[name] = jnp.ones(shape, dt)
                continue
            std = 0.02 if name == "embed" else 1.0 / np.sqrt(shape[-2])
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out[name] = (x * std).astype(dt)
        return out

    return jax.jit(build)(key_of(seed))


def program_params(w: Dict[str, jax.Array], s: Shapes,
                   expected) -> Dict:
    """The program's parameter tree over the same arrays.  ``expected``
    is the program's own tree of shapes (``jax.eval_shape`` of its
    initialiser); a tree that differs in structure, shape or type is an
    error, not a silent mismatch."""
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
    got = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), tree)
    want = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), expected)
    if got != want:
        raise ValueError(f"program parameter layout differs: want {want}, "
                         f"got {got}")
    return tree
