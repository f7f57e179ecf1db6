"""Weights from the seed, made on the device in one jitted call in the
type they are served in, in the benchmark's own layout, which a family
names (``weight_shapes``).  The family's reference reads this layout;
its ``program_params`` hands the same arrays to the program in the
layout its model code expects.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def key_of(seed: int) -> jax.Array:
    """A JAX key from any whole-number seed (wider than 32 bits too)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def make(shapes: Dict[str, tuple], seed: int, dtype: str
         ) -> Dict[str, jax.Array]:
    """One weight of each of ``shapes`` (a family's ``weight_shapes``),
    drawn in name order, in ``dtype``: normal, scaled by 1/sqrt(fan in)
    (the shape's last axis but one); ``embed`` of standard deviation
    0.02; a name ending in ``norm`` all ones (a norm's gain)."""
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


def checked(tree: Dict, expected) -> Dict:
    """``tree``, a family's program parameter tree over the benchmark's
    weights, where it has the layout of ``expected``, the program's own
    tree of shapes (``jax.eval_shape`` of its initialiser); a tree that
    differs in structure, shape or type is an error, not a silent
    mismatch."""
    got = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), tree)
    want = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), expected)
    if got != want:
        raise ValueError(f"program parameter layout differs: want {want}, "
                         f"got {got}")
    return tree
