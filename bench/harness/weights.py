"""Seeded GPT-2 weights, made on the device in one jitted call.

The tree has the layout the program's dense model reads (and the plain
reference in ``bench/reference`` reads too), in float32, the type the
configurations train and serve in.  Matrices follow GPT-2's initialisation
(normal, std 0.02; the two residual projections scaled by 1/sqrt(2L)).
Biases and LayerNorm parameters are drawn too, not left at 0 and 1, so a
program that dropped one of them would not match the reference.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench.reference.gpt2 import Dims


def prng_key(seed: int) -> jax.Array:
    """A key from a seed of any size (``PRNGKey`` alone keeps 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def shapes(d: Dims) -> Dict[str, Any]:
    L, D, H, K, F = d.n_layers, d.d_model, d.n_heads, d.head_dim, d.d_ff
    norm = {"scale": (L, D), "bias": (L, D)}
    return {
        "embed": (d.vocab_padded, D),
        "pos_embed": (d.n_positions, D),
        "final_norm": {"scale": (D,), "bias": (D,)},
        "layers": {
            "ln1": dict(norm), "ln2": dict(norm),
            "attn": {"wq": (L, D, H, K), "wk": (L, D, H, K),
                     "wv": (L, D, H, K), "wo": (L, H, K, D)},
            "mlp": {"w_up": (L, D, F), "b_up": (L, F),
                    "w_down": (L, F, D), "b_down": (L, D)},
        },
    }


def _std(path: str, d: Dims) -> float:
    if path.endswith(("wo", "w_down")):
        return 0.02 / math.sqrt(2 * d.n_layers)
    return 0.02


def make(seed: int, d: Dims):
    """The whole parameter tree from ``seed``, on the default device."""
    tree = shapes(d)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    paths = ["/".join(str(k.key) for k in p) for p, _ in leaves]
    shps = [s for _, s in leaves]

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(shps))
        out = []
        for k, path, shp in zip(keys, paths, shps):
            x = jax.random.normal(k, shp, jnp.float32) * _std(path, d)
            if path.endswith("scale"):
                x = 1.0 + x
            out.append(x)
        return jax.tree_util.tree_unflatten(treedef, out)

    return build(prng_key(seed))
