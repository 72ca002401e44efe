"""Pallas TPU grouped matrix products for a share of an expert layer.

The rows of ``lhs`` are sorted by group (expert): group ``g`` owns rows
``offsets[g] .. offsets[g + 1]`` where ``offsets`` is the running sum of
``group_sizes``, which counts the rows of *every* group of the layer.  A
chip that holds the ``n_local`` groups ``start .. start + n_local - 1``
(``start`` a traced scalar) computes only their rows: the grid walks the
row tiles of those groups and no others, so the work follows the rows
routed here, whatever the other groups hold.

* ``gmm``: ``out[rows of g] = lhs[rows of g] @ rhs[g - start]``, with
  ``rhs`` (n_local, k, n) or, ``transpose_rhs``, (n_local, n, k).  Rows of
  other groups are zero.  Named ``moe_gmm`` in the device trace (the
  forward products and the backward's products for ``lhs``).
* ``tgmm``: ``out[g - start] = lhs[:, rows of g] @ rhs[rows of g]``, the
  gradient of ``rhs``; an empty local group gets zeros.  Named
  ``moe_tgmm``.

The tiling follows JAX's megablox kernels: each grid step takes a
``tm``-row tile and the group it belongs to, from tables prefetched into
scalar memory (``group_metadata``).  A tile that two groups share is
visited once per group, and each visit stores only its group's rows.
Accumulation is float32 in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Mosaic's default scoped VMEM on a v5e, less room for its own temporaries
VMEM_BUDGET = 13 * 2**20
# row tile; the wrapper pads the rows to a multiple of it
BLOCK_M = 256
# column tiles tried after the whole dimension, largest first
BLOCKS = (2048, 1024, 512, 256, 128)


def _candidates(dim: int):
    return [dim] + [b for b in BLOCKS if b < dim and dim % b == 0]


def tiling(k: int, n: int, itemsize: int, transposed_out: bool = False
           ) -> tuple[int, int]:
    """(tk, tn) for products over ``k`` into ``n`` columns: the fewest grid
    steps whose blocks fit ``VMEM_BUDGET``, double-buffered, with a float32
    accumulator (``transposed_out``: the ``tgmm`` layout, whose output
    block is (tk, tn))."""
    best = None
    for tk in _candidates(k):
        for tn in _candidates(n):
            if transposed_out:   # lhs (tm, tk), rhs (tm, tn), out (tk, tn)
                vmem = (2 * itemsize * BLOCK_M * (tk + tn)
                        + (2 * itemsize + 4) * tk * tn)
            else:                # lhs (tm, tk), rhs (tk, tn), out (tm, tn)
                vmem = (2 * itemsize * (BLOCK_M * tk + tk * tn)
                        + (2 * itemsize + 4) * BLOCK_M * tn)
            if vmem > VMEM_BUDGET:
                continue
            steps = -(-k // tk) * -(-n // tn)
            if best is None or steps < best[0]:
                best = (steps, tk, tn)
    if best is None:
        return 128, 128
    return best[1], best[2]


def group_metadata(group_sizes: jax.Array, m: int, tm: int,
                   start: jax.Array, n_local: int, visit_empty: bool):
    """Tables of the grid over the row tiles of the local groups.

    Returns ``(offsets, group_ids, tile_ids), n_steps``: ``offsets`` (G+1,)
    the first row of each group; for grid step ``i``, ``group_ids[i]`` its
    group and ``tile_ids[i]`` its row tile; ``n_steps`` how many steps the
    local groups need (the tables are padded past it).  A group that starts
    inside a tile visits that tile too; with ``visit_empty`` an empty local
    group gets one step of its own (``tgmm`` must zero its output).
    """
    n_groups = group_sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(group_sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    starts = offsets[:-1]
    empty = group_sizes == 0
    # tiles each group touches: from its first row's tile to its last's
    span = ((ends + tm - 1) // tm) - (starts // tm)
    tiles = jnp.where(empty, 1 if visit_empty else 0, span)
    group_ids = jnp.repeat(jnp.arange(n_groups, dtype=jnp.int32), tiles,
                           total_repeat_length=tiles_m + n_groups - 1)
    # every tile is visited once by the group owning its first row, and
    # once more by each group that starts inside it (or is empty there)
    starts_inside = (starts % tm != 0) & ~empty
    if visit_empty:
        starts_inside = starts_inside | empty
    extra = jnp.where(starts_inside, starts // tm, tiles_m)
    visits = 1 + jnp.zeros(tiles_m, jnp.int32).at[extra].add(1, mode="drop")
    tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32), visits,
                          total_repeat_length=tiles_m + n_groups - 1)
    # move the local groups' steps to the front
    first = jnp.sum(group_ids < start)
    group_ids = jnp.roll(group_ids, -first)
    tile_ids = jnp.roll(tile_ids, -first)
    g = jnp.arange(n_groups, dtype=jnp.int32)
    local = (g >= start) & (g < start + n_local)
    n_steps = jnp.sum(jnp.where(local, tiles, 0))
    return (offsets, group_ids, tile_ids), n_steps


def _row_mask(meta, i, tm: int, cols: int):
    """(tm, cols) mask of the rows of step ``i``'s tile in its group."""
    offsets, group_ids, tile_ids = meta
    g = group_ids[i]
    row = tile_ids[i] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, cols), 0)
    return (row >= offsets[g]) & (row < offsets[g + 1])


def gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
        start: jax.Array, *, tm: int, tk: int, tn: int,
        transpose_rhs: bool = False, interpret: bool = False) -> jax.Array:
    """lhs (m, k) rows sorted by group, ``m`` a multiple of ``tm``; rhs
    (n_local, k, n), or (n_local, n, k) with ``transpose_rhs``; ``tk`` and
    ``tn`` divide ``k`` and ``n``.  Returns (m, n) in ``lhs``'s dtype; rows
    outside the local groups are zero."""
    m, k = lhs.shape
    n_local = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    if m % tm or k % tk or n % tn:
        raise ValueError(f"tiles ({tm}, {tk}, {tn}) do not divide "
                         f"({m}, {k}, {n})")
    tiles_k, tiles_n = k // tk, n // tn
    meta, n_steps = group_metadata(group_sizes, m, tm, start, n_local,
                                   visit_empty=False)

    def kernel(meta, start, lhs_ref, rhs_ref, out_ref, acc):
        i, kk = pl.program_id(1), pl.program_id(2)

        @pl.when(kk == 0)
        def _zero():
            acc[...] = jnp.zeros_like(acc)

        def _accumulate(last: bool):
            a = lhs_ref[...].astype(jnp.float32)
            b = rhs_ref[...].astype(jnp.float32)
            dims = (((1,), (1,)) if transpose_rhs else ((1,), (0,)), ((), ()))
            acc[...] += jax.lax.dot_general(
                a, b, dims, preferred_element_type=jnp.float32)
            if last:
                mask = _row_mask(meta, i, tm, tn)
                out_ref[...] = jnp.where(
                    mask, acc[...], out_ref[...].astype(jnp.float32)
                ).astype(out_ref.dtype)

        jax.lax.cond(kk == tiles_k - 1, functools.partial(_accumulate, True),
                     functools.partial(_accumulate, False))

    def lhs_map(j, i, kk, meta, start):
        return meta[2][i], kk

    def rhs_map(j, i, kk, meta, start):
        g = meta[1][i] - start[0]
        return (g, j, kk) if transpose_rhs else (g, kk, j)

    def out_map(j, i, kk, meta, start):
        return meta[2][i], j

    from jax.experimental.pallas import tpu as pltpu
    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec(rhs_block, rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            grid=(tiles_n, n_steps, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="moe_gmm",
    )(meta, start[None], lhs, rhs)
    # rows no local group owns were never written
    offsets = meta[0]
    row = jnp.arange(m)[:, None]
    mine = (row >= offsets[start]) & (row < offsets[start + n_local])
    return jnp.where(mine, out, jnp.zeros_like(out))


def tgmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
         start: jax.Array, n_local: int, *, tm: int, tk: int, tn: int,
         interpret: bool = False) -> jax.Array:
    """lhs (m, k), rhs (m, n), rows sorted by group, ``m`` a multiple of
    ``tm``.  Returns (n_local, k, n): lhs[rows of g]^T @ rhs[rows of g] for
    each local group ``g``, in ``rhs``'s dtype; ``tk`` and ``tn`` divide
    ``k`` and ``n``."""
    m, k = lhs.shape
    n = rhs.shape[1]
    if m % tm or k % tk or n % tn:
        raise ValueError(f"tiles ({tm}, {tk}, {tn}) do not divide "
                         f"({m}, {k}, {n})")
    tiles_k, tiles_n = k // tk, n // tn
    meta, n_steps = group_metadata(group_sizes, m, tm, start, n_local,
                                   visit_empty=True)

    def kernel(meta, start, lhs_ref, rhs_ref, out_ref, acc):
        i = pl.program_id(2)
        group_ids = meta[1]
        g = group_ids[i]
        new_group = (i == 0) | (group_ids[jnp.maximum(i - 1, 0)] != g)
        last = i == pl.num_programs(2) - 1
        ends_group = last | (group_ids[jnp.where(last, i, i + 1)] != g)

        @pl.when(new_group)
        def _zero():
            acc[...] = jnp.zeros_like(acc)

        @pl.when(meta[0][g + 1] > meta[0][g])
        def _accumulate():
            a = jnp.where(_row_mask(meta, i, tm, tk),
                          lhs_ref[...].astype(jnp.float32), 0.0)
            b = jnp.where(_row_mask(meta, i, tm, tn),
                          rhs_ref[...].astype(jnp.float32), 0.0)
            acc[...] += jax.lax.dot_general(
                a.swapaxes(0, 1), b, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(ends_group)
        def _store():
            out_ref[...] = acc[...].astype(out_ref.dtype)

    def lhs_map(j, kk, i, meta, start):
        return meta[2][i], kk

    def rhs_map(j, kk, i, meta, start):
        return meta[2][i], j

    def out_map(j, kk, i, meta, start):
        return meta[1][i] - start[0], kk, j

    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_local, k, n), rhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((tm, tn), rhs_map)],
            out_specs=pl.BlockSpec((None, tk, tn), out_map),
            grid=(tiles_n, tiles_k, n_steps),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="moe_tgmm",
    )(meta, start[None], lhs, rhs)
