"""Pure-jnp oracle for the grouped matrix product of a share of experts."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def gmm_reference(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                  start: int) -> jax.Array:
    """lhs (m, k) rows sorted by group; group_sizes (E,); rhs (n_local, k,
    n) for groups ``start .. start + n_local - 1``.  Each row of a held
    group times its group's matrix, in float32; every other row zero."""
    m = lhs.shape[0]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(group_sizes)])
    row = jnp.arange(m)
    out = jnp.zeros((m, rhs.shape[2]), jnp.float32)
    for j in range(rhs.shape[0]):
        g = start + j
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        y = jnp.dot(lhs.astype(jnp.float32), rhs[j].astype(jnp.float32),
                    precision="highest")
        out = out + jnp.where(mine[:, None], y, 0.0)
    return out.astype(lhs.dtype)
