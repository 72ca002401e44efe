"""Pallas TPU flash-decode: split-KV decode attention over a slot cache.

One query row per slot (the fused decode step's token) attends to that
slot's KV cache rows, of which only the first ``lengths[b]`` are valid
("pos = count of valid entries" — the convention shared with
``models.attention.decode_attention`` and
``distributed.collectives.flash_decode_sharded``).  The jnp decode path
materializes the full ``(slots, KV, G, 1, S_max)`` score tensor per layer
per token; here the cache is streamed in KV blocks and the online-softmax
carry ``(m, l, acc)`` lives in VMEM scratch, so the high-water is
O(KV * G * block_k) per slot.

Grid ``(B, kv_blocks)`` with the kv axis minor: the TPU executes the grid
sequentially, so each slot accumulates its partial softmax across kv
iterations and finalizes at the last block.  A cache block holds every KV
head, ``(block_k, KV, D)``: the chip tiles the last two dimensions of a
block, and a block of one KV head out of ``(.., KV, D)`` is not a legal
tile, while the whole ``(KV, D)`` plane is.  The kernel then walks the
heads in VMEM.  GQA is native — each head's query is its whole ``(G, D)``
group, so no head expansion ever materializes.  There is no backward pass:
decode is inference-only.

``lengths`` rides scalar prefetch into SMEM, so the k/v index maps clamp
every block past a slot's valid length onto its last valid block: the
pipeline only copies when the block index changes, so masked blocks are
never fetched, and ``pl.when`` skips their flops.

The kernel emits *partials* ``(o_unnormalized, m, l)`` rather than the
normalized context: ops.py divides for the single-host path, and
``distributed.collectives.flash_decode_sharded`` merges per-shard partials
with pmax/psum — the same (m, l, o) algebra in both places.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _softmax_step(q, k, v, valid, scale, m_scr, l_scr, acc_scr):
    """One online-softmax update of the (m, l, acc) carry for a (G, D) query
    group against a (block, D) k/v tile; ``valid`` masks dead columns."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    # mask p explicitly: on a fully-masked block m_new stays NEG_INF and
    # exp(s - m_new) would be exp(0) = 1, polluting l with dead columns
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = (acc_scr[...] * corr
                    + jax.lax.dot_general(
                        p, v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
    m_scr[...] = m_new


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   m_scr, l_scr, acc_scr, *, block_k: int, kv_blocks: int,
                   kv_heads: int, scale: float):
    bi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[bi]  # this slot's count of valid cache entries

    # blocks entirely past the valid length: no flops, and no fetch (the
    # index map clamps them onto the resident block)
    @pl.when(ki * block_k < length)
    def _run():
        g = q_ref.shape[2]
        col = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (g, block_k), 1)
        valid = col < length
        for h in range(kv_heads):
            _softmax_step(q_ref[0, h].astype(jnp.float32),        # (G, D)
                          k_ref[0, :, h, :].astype(jnp.float32),  # (bk, D)
                          v_ref[0, :, h, :].astype(jnp.float32),
                          valid, scale, m_scr.at[h], l_scr.at[h],
                          acc_scr.at[h])

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        o_ref[0] = acc_scr[...]
        m_ref[0] = m_scr[...][..., 0]
        l_ref[0] = l_scr[...][..., 0]


def flash_decode_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, *, block_k: int = 128,
                     interpret: bool = False
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """q: (B, KV, G, D); k, v: (B, S, KV, D); lengths: (B,) int32 counts.

    S must be a multiple of ``block_k`` (ops.py pads; padded rows are dead
    because ``lengths <= S_orig``).  Returns fp32 partials
    ``(o (B, KV, G, D) unnormalized, m (B, KV, G), l (B, KV, G))`` — the
    caller normalizes ``o / l`` or psum-merges across sequence shards.
    """
    from jax.experimental.pallas import tpu as pltpu

    b, kvh, g, d = q.shape
    s = k.shape[1]
    assert s % block_k == 0, (s, block_k)
    kv_blocks = s // block_k
    scale = 1.0 / (d ** 0.5)

    def kv_map(bi, ki, lens):
        last = jnp.maximum(lens[bi] - 1, 0) // block_k
        return (bi, jnp.minimum(ki, last), 0, 0)

    kernel = functools.partial(_decode_kernel, block_k=block_k,
                               kv_blocks=kv_blocks, kv_heads=kvh, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kv_blocks),
        in_specs=[
            pl.BlockSpec((1, kvh, g, d), lambda bi, ki, lens: (bi, 0, 0, 0)),
            pl.BlockSpec((1, block_k, kvh, d), kv_map),
            pl.BlockSpec((1, block_k, kvh, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, kvh, g, d), lambda bi, ki, lens: (bi, 0, 0, 0)),
            pl.BlockSpec((1, kvh, g), lambda bi, ki, lens: (bi, 0, 0)),
            pl.BlockSpec((1, kvh, g), lambda bi, ki, lens: (bi, 0, 0)),
        ],
        scratch_shapes=[
            _vmem((kvh, g, 1), jnp.float32),  # m: running row max
            _vmem((kvh, g, 1), jnp.float32),  # l: running row sum
            _vmem((kvh, g, d), jnp.float32),  # acc: weighted values
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, kvh, g, d), jnp.float32),
            jax.ShapeDtypeStruct((b, kvh, g), jnp.float32),
            jax.ShapeDtypeStruct((b, kvh, g), jnp.float32),
        ],
        interpret=interpret,
        name="flash_decode",
    )(lengths.astype(jnp.int32), q, k, v)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _paged_decode_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref,
                         o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr, *,
                         page_size: int, max_pages: int, scale: float):
    bi = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[bi]       # this slot's count of valid cache entries
    owned = pt_ref[bi, pi] >= 0

    def _body():
        g = q_ref.shape[2]
        # global column index of in-page row j is pi * page_size + j
        col = pi * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (g, page_size), 1)
        _softmax_step(q_ref[0, 0].astype(jnp.float32),        # (G, D)
                      k_ref[0, :, 0, :].astype(jnp.float32),  # (page, D)
                      v_ref[0, :, 0, :].astype(jnp.float32),
                      col < length, scale, m_scr, l_scr, acc_scr)

    # pages past the valid length and unowned (-1) table entries contribute
    # nothing; since the page id feeds the index map via scalar prefetch,
    # their HBM fetch is also elided on TPU (the map clamps -1 to page 0
    # but this body never reads the block)
    @pl.when((pi * page_size < length) & owned)
    def _run():
        _body()

    @pl.when(pi == max_pages - 1)
    def _finalize():
        o_ref[0, 0] = acc_scr[...]
        m_ref[0, 0] = m_scr[:, 0]
        l_ref[0, 0] = l_scr[:, 0]


def flash_decode_paged_fwd(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, page_table: jax.Array,
                           lengths: jax.Array, *, interpret: bool = False
                           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Page-table-walking flash decode over a shared KV pool.

    q: (B, KV, G, D); k_pool, v_pool: (n_pages, page_size, KV, D);
    page_table: (B, max_pages) int32 page ids, ``-1`` = unowned;
    lengths: (B,) int32 counts (slot ``b``'s token ``j`` lives in page
    ``page_table[b, j // page_size]`` at offset ``j % page_size``).

    The page table and lengths ride scalar prefetch
    (``PrefetchScalarGridSpec``), so the k/v index maps resolve the *page
    id* per grid step — the kernel walks each slot's page list and never
    touches pages the slot doesn't own.  Masking and the (m, l, o)
    online-softmax merge are the dense kernel's (``_softmax_step``).
    Returns the same fp32 partials as ``flash_decode_fwd``.
    """
    from jax.experimental.pallas import tpu as pltpu

    b, kvh, g, d = q.shape
    page_size = k_pool.shape[1]
    max_pages = page_table.shape[1]
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(_paged_decode_kernel, page_size=page_size,
                               max_pages=max_pages, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda b, h, pi, pt, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, page_size, 1, d),
                         lambda b, h, pi, pt, lens:
                         (jnp.maximum(pt[b, pi], 0), 0, h, 0)),
            pl.BlockSpec((1, page_size, 1, d),
                         lambda b, h, pi, pt, lens:
                         (jnp.maximum(pt[b, pi], 0), 0, h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b, h, pi, pt, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, g), lambda b, h, pi, pt, lens: (b, h, 0)),
            pl.BlockSpec((1, 1, g), lambda b, h, pi, pt, lens: (b, h, 0)),
        ],
        scratch_shapes=[
            _vmem((g, 1), jnp.float32),  # m: running row max
            _vmem((g, 1), jnp.float32),  # l: running row sum
            _vmem((g, d), jnp.float32),  # acc: weighted values
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, kvh, g, d), jnp.float32),
            jax.ShapeDtypeStruct((b, kvh, g), jnp.float32),
            jax.ShapeDtypeStruct((b, kvh, g), jnp.float32),
        ],
        interpret=interpret,
        name="flash_decode_paged",
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pool, v_pool)
