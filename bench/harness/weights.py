"""Seeded weights, made on the device in one jitted call.

The tree and each leaf's draw are the cell's reference's
(``weight_shapes(dims)`` and ``weight_init(path, dims)`` of
``bench/reference/<model_type>.py``), which is written to the layout the
program reads.  Every leaf is a normal draw in float32, the type the
configurations train and serve in, from its own key of one split of the
seed's key.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def prng_key(seed: int) -> jax.Array:
    """A key from a seed of any size (``PRNGKey`` alone keeps 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def make(seed: int, cell):
    """The whole parameter tree of ``cell`` from ``seed``, on the default
    device."""
    ref, dims = cell.arch("reference"), cell.dims
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        ref.weight_shapes(dims), is_leaf=lambda x: isinstance(x, tuple))
    paths = ["/".join(str(k.key) for k in p) for p, _ in leaves]
    shps = [s for _, s in leaves]
    inits = [ref.weight_init(path, dims) for path in paths]

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(shps))
        out = []
        for k, shp, (mean, std) in zip(keys, shps, inits):
            x = jax.random.normal(k, shp, jnp.float32) * std
            if mean:
                x = mean + x
            out.append(x)
        return jax.tree_util.tree_unflatten(treedef, out)

    return build(prng_key(seed))
