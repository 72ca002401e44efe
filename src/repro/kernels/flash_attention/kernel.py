"""Pallas TPU flash attention: forward *and* backward (causal, GQA-expanded).

Head-folded grid — every kernel takes a group of ``block_h`` heads per grid
step: q, k, dQ and dK move in ``(block_h, block, D)`` blocks, v, o, dO and
dV in ``(block_h, block, D_v)`` blocks (latent attention's values are
narrower than its queries and keys; D_v = D otherwise), the per-row
``lse`` and ``delta`` in ``(block_h, 1, block_q)`` blocks, and the products
are batched over the head axis.  Scores are scaled by 1/sqrt(D).  A grid step carries a fixed
cost on the chip (its DMA issue and wait, the pipeline's bookkeeping) that
one 128 x 128 tile of one head does not cover; folding heads shares that
cost among ``block_h`` tiles, and larger tiles share it further.
``tiles`` chooses ``(block_h, block_q, block_k)`` from the shapes alone:
the whole sequence as one block where that fits, else 512-row blocks, and
the largest head group whose kernels fit Mosaic's default scoped VMEM.

Forward — grid (bh / block_h, q_blocks, kv_blocks) with the kv axis minor;
the TPU executes the grid sequentially, so the online-softmax carry (m, l,
acc) lives in VMEM scratch across kv iterations of one (head group, q)
cell.  Besides the output block the kernel emits the per-row logsumexp
``lse = m + log(l)`` — the residual that lets the backward recompute
softmax rows without a second online pass.

Causal grid pruning — fully-masked kv blocks (strictly above the diagonal)
are pruned at the *index map*: the kv block index is clamped to the last
in-diagonal block, so every pruned grid step maps to the block already
resident in VMEM and Pallas elides the HBM fetch (the pipeline only issues a
copy when the mapped index changes).  ``pl.when`` still skips the flops.
The diagonal depends on the (q, kv) tile only, so the whole head group
shares each decision.

Backward — FlashAttention-2 style split into three kernels, all reusing the
same causal block-skipping and ``valid_len`` tail masking as the forward:

* ``_bwd_preprocess_kernel``: ``delta = rowsum(dO * O)`` per row — the
  softmax-Jacobian correction term, grid (bh / block_h, q_blocks).
* ``_bwd_dq_kernel``: grid (bh / block_h, q_blocks, kv_blocks), kv minor;
  recomputes ``p = exp(s - lse)`` per tile and accumulates
  ``dq += (p * (dO @ V^T - delta)) @ K * scale`` in VMEM scratch.
* ``_bwd_dkv_kernel``: grid (bh / block_h, kv_blocks, q_blocks), q minor;
  accumulates ``dv += p^T @ dO`` and ``dk += (p * (dO @ V^T - delta))^T @ Q
  * scale``.  Causal pruning mirrors the forward: the q index map clamps to
  the first in-diagonal q block for this kv block.

All accumulation is fp32 in scratch; outputs are cast to the input dtype at
the final grid step of each (head group, major) cell.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30

# Mosaic's default scoped VMEM on a v5e; ``tiles`` keeps the kernels'
# estimate (``vmem_bytes``) under it and sets no ``vmem_limit_bytes``
VMEM_BUDGET = 16 * 2**20
# sequence blocks after the whole sequence, largest first (``tiles``)
BLOCKS = (512, 256, 128)
# the head group's cap, so a short sequence does not unroll a kernel over
# hundreds of heads
MAX_BLOCK_H = 64

# dimension numbers of the head-batched products, (h, m, c) x (h, n, c)
_NT = (((2,), (2,)), ((0,), (0,)))  # A @ B^T -> (h, m, n)
_NN = (((2,), (1,)), ((0,), (0,)))  # A @ B   -> (h, m, n)
_TN = (((1,), (1,)), ((0,), (0,)))  # A^T @ B -> (h, m, n)


def _bmm(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _vmem_tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """Bytes of a (rows, cols) slab in VMEM: (8 x 128) tiles, with
    sub-32-bit types packed along the rows."""
    sub = 8 * max(1, 4 // itemsize)
    return -(-rows // sub) * sub * (-(-cols // 128) * 128) * itemsize


def vmem_bytes(block_h: int, block_q: int, block_k: int, d: int,
               itemsize: int, d_v: int | None = None) -> int:
    """Scoped VMEM of the largest of the kernels at one tile: its blocks,
    double-buffered (at ``itemsize``; the fp32 lse and delta rows take one
    (1 x 128) tile per 128 lanes), its fp32 scratch, and the temporaries
    Mosaic keeps in VMEM, taken as one and a half fp32 (block_q, block_k)
    values: every tile of the sweep in PERF.md (section 5) that the
    compiler refused lies above the budget by this count.  The dK/dV
    kernel is the largest unless ``block_q`` > 2 ``block_k``.  Queries and
    keys are ``d`` wide, values, outputs and their gradients ``d_v`` (``d``
    where not given)."""
    d_v = d if d_v is None else d_v
    tile = _vmem_tile_bytes
    tq, tk = tile(block_q, d, itemsize), tile(block_k, d, itemsize)
    vq, vk = tile(block_q, d_v, itemsize), tile(block_k, d_v, itemsize)
    rows = 4 * (-(-block_q // 128) * 128)
    fwd = 2 * (tq + vq + tk + vk + rows) + 2 * tile(block_q, 1, 4) \
        + tile(block_q, d_v, 4)                 # q, o; k, v; lse | m, l, acc
    dq = 2 * (2 * tq + vq + tk + vk + 2 * rows) + tile(block_q, d, 4)
    dkv = 2 * (tq + vq + 2 * tk + 2 * vk + 2 * rows) + tile(block_k, d, 4) \
        + tile(block_k, d_v, 4)
    values = 3 * tile(block_q, block_k, 4) // 2
    return block_h * (max(fwd, dq, dkv) + values)


def tiles(bh: int, s: int, d: int, itemsize: int, block_q: int | None = None,
          block_k: int | None = None, d_v: int | None = None
          ) -> tuple[int, int, int]:
    """(block_h, block_q, block_k) for (bh, s, d) queries and keys and
    (bh, s, d_v) values (``d_v`` of None: ``d``) of ``itemsize``.

    With neither sequence block given, both are the first of the whole
    sequence and ``BLOCKS`` whose kernels fit ``VMEM_BUDGET`` with two heads
    a step (one where ``bh`` is 1), and 128 where none does: on the v5e a
    larger tile costs so much less per score that it pays for the masked
    half of the diagonal tiles it computes (PERF.md, section 5).  A block
    given alone pairs with 128.  Blocks are clamped to ``s``.  ``block_h``
    is the largest divisor of ``bh``, at most ``MAX_BLOCK_H``, whose
    kernels fit ``VMEM_BUDGET`` (``vmem_bytes``).
    """
    def fits(h, bq, bk):
        return vmem_bytes(h, bq, bk, d, itemsize, d_v) <= VMEM_BUDGET

    if block_q is None and block_k is None:
        block_q = block_k = next(
            (b for b in (s,) + BLOCKS[:-1]
             if fits(min(2, bh), min(b, s), min(b, s))), BLOCKS[-1])
    block_q = min(block_q or BLOCKS[-1], s)
    block_k = min(block_k or BLOCKS[-1], s)
    block_h = max(h for h in range(1, min(bh, MAX_BLOCK_H) + 1)
                  if bh % h == 0 and (h == 1 or fits(h, block_q, block_k)))
    return block_h, block_q, block_k


def _last_kv_block(qi, block_q: int, block_k: int):
    """Last kv block intersecting the causal diagonal for q block `qi`."""
    return (qi * block_q + block_q - 1) // block_k


def _first_q_block(ki, block_q: int, block_k: int):
    """First q block intersecting the causal diagonal for kv block `ki`."""
    return (ki * block_k) // block_q


def _kv_index_map(block_q: int, block_k: int, causal: bool):
    """K/V index map for (head group, q_blocks, kv_blocks) grids.  Causal
    pruning clamps above-diagonal steps onto the already-resident block so
    Pallas elides the fetch (shared by fwd and the dQ kernel)."""
    if causal:
        return lambda b, qi, ki: (
            b, jnp.minimum(ki, _last_kv_block(qi, block_q, block_k)), 0)
    return lambda b, qi, ki: (b, ki, 0)


def _q_index_maps(block_q: int, block_k: int, causal: bool):
    """(tensor, per-row) Q-side index maps for the (head group, kv_blocks,
    q_blocks) dK/dV grid — the mirror-image clamp onto the first
    in-diagonal q block."""
    if causal:
        def qi_of(ki, qi):
            return jnp.maximum(qi, _first_q_block(ki, block_q, block_k))
        return (lambda b, ki, qi: (b, qi_of(ki, qi), 0),
                lambda b, ki, qi: (b, 0, qi_of(ki, qi)))
    return (lambda b, ki, qi: (b, qi, 0), lambda b, ki, qi: (b, 0, qi))


def _masked_scores(q, k, qi, ki, *, block_q, block_k, scale, causal,
                   valid_len, kv_len):
    """(block_h, block_q, block_k) fp32 scores with causal + padded-tail
    masking."""
    s = _bmm(q, k, _NT) * scale
    shape = s.shape
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    if causal:
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    if valid_len < kv_len:  # padded tail keys
        s = jnp.where(k_pos < valid_len, s, NEG_INF)
    return s


def _rows(ref):
    """(block_h, 1, block_q) per-row block -> (block_h, block_q, 1)."""
    return ref[:, 0, :][:, :, None]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                block_q: int, block_k: int, scale: float, causal: bool,
                kv_blocks: int, valid_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _body():
        q = q_ref[...].astype(jnp.float32)  # (block_h, block_q, d)
        k = k_ref[...].astype(jnp.float32)  # (block_h, block_k, d)
        v = v_ref[...].astype(jnp.float32)
        s = _masked_scores(q, k, qi, ki, block_q=block_q, block_k=block_k,
                           scale=scale, causal=causal, valid_len=valid_len,
                           kv_len=kv_blocks * block_k)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + _bmm(p, v, _NN)
        m_scr[...] = m_new
        l_scr[...] = l_new

    if causal:
        # skip kv blocks strictly above the diagonal (their fetch is elided
        # by the clamped index map — see module docstring)
        @pl.when(ki <= _last_kv_block(qi, block_q, block_k))
        def _run():
            _body()
    else:
        _body()

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[:, 0, :] = (m_scr[...] + jnp.log(l))[:, :, 0]


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, block_h: int = 1,
                        block_q: int = 128, block_k: int = 128,
                        valid_len: int = 0, interpret: bool = False
                        ) -> tuple[jax.Array, jax.Array]:
    """q, k: (BH, S, D); v: (BH, S, D_v) (GQA repeat handled by ops.py).

    Returns (o (BH, S, D_v), lse (BH, 1, S) fp32); scores are scaled by
    1/sqrt(D).  `valid_len` masks padded
    tail keys (0 = none).  The per-row lse and delta are stored one row of
    lanes per head, ``(BH, 1, S)`` with ``(block_h, 1, block_q)`` blocks:
    the chip tiles a block's last two dimensions by (8, 128), and a
    ``(block_h, block_q)`` block of a ``(BH, S)`` array is not such a tile.
    """
    bh, s, d = q.shape
    d_v = v.shape[-1]
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    assert bh % block_h == 0, (bh, block_h)
    q_blocks = s // block_q
    kv_blocks = s // block_k
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, scale=scale,
        causal=causal, kv_blocks=kv_blocks, valid_len=valid_len or s)

    kv_map = _kv_index_map(block_q, block_k, causal)
    q_map = lambda b, qi, ki: (b, qi, 0)

    return pl.pallas_call(
        kernel,
        grid=(bh // block_h, q_blocks, kv_blocks),
        in_specs=[
            pl.BlockSpec((block_h, block_q, d), q_map),
            pl.BlockSpec((block_h, block_k, d), kv_map),
            pl.BlockSpec((block_h, block_k, d_v), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((block_h, block_q, d_v), q_map),
            pl.BlockSpec((block_h, 1, block_q), lambda b, qi, ki: (b, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d_v), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            _vmem((block_h, block_q, 1), jnp.float32),  # m: running row max
            _vmem((block_h, block_q, 1), jnp.float32),  # l: running row sum
            _vmem((block_h, block_q, d_v), jnp.float32),  # acc: weighted values
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_preprocess_kernel(o_ref, do_ref, delta_ref):
    o = o_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    delta_ref[:, 0, :] = jnp.sum(o * do, axis=-1)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, block_q: int, block_k: int, scale: float,
                   causal: bool, kv_blocks: int, valid_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _body():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        lse = _rows(lse_ref)      # (block_h, block_q, 1)
        delta = _rows(delta_ref)  # (block_h, block_q, 1)
        s = _masked_scores(q, k, qi, ki, block_q=block_q, block_k=block_k,
                           scale=scale, causal=causal, valid_len=valid_len,
                           kv_len=kv_blocks * block_k)
        p = jnp.exp(s - lse)
        dp = _bmm(do, v, _NT)
        ds = p * (dp - delta)
        dq_scr[...] += _bmm(ds, k, _NN) * scale

    if causal:
        @pl.when(ki <= _last_kv_block(qi, block_q, block_k))
        def _run():
            _body()
    else:
        _body()

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                    dv_ref, dk_scr, dv_scr, *, block_q: int, block_k: int,
                    scale: float, causal: bool, q_blocks: int,
                    kv_blocks: int, valid_len: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _body():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        lse = _rows(lse_ref)
        delta = _rows(delta_ref)
        s = _masked_scores(q, k, qi, ki, block_q=block_q, block_k=block_k,
                           scale=scale, causal=causal, valid_len=valid_len,
                           kv_len=kv_blocks * block_k)
        p = jnp.exp(s - lse)  # (block_h, block_q, block_k)
        dv_scr[...] += _bmm(p, do, _TN)
        dp = _bmm(do, v, _NT)
        ds = p * (dp - delta)
        dk_scr[...] += _bmm(ds, q, _TN) * scale

    if causal:
        @pl.when(qi >= _first_q_block(ki, block_q, block_k))
        def _run():
            _body()
    else:
        _body()

    @pl.when(qi == q_blocks - 1)
    def _finalize():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        o: jax.Array, lse: jax.Array, do: jax.Array, *,
                        causal: bool = True, block_h: int = 1,
                        block_q: int = 128, block_k: int = 128,
                        valid_len: int = 0, interpret: bool = False
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Backward pass at the flattened (BH, S, D) layout.

    q, k: (BH, S, D); v, o, do: (BH, S, D_v); lse: (BH, 1, S) fp32 from
    the forward.  Returns (dq, dk, dv) with the input dtypes and shapes.
    """
    bh, s, d = q.shape
    d_v = v.shape[-1]
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    assert bh % block_h == 0, (bh, block_h)
    q_blocks = s // block_q
    kv_blocks = s // block_k
    scale = 1.0 / math.sqrt(d)
    valid_len = valid_len or s
    groups = bh // block_h

    # delta = rowsum(dO * O): the softmax-Jacobian correction term
    delta = pl.pallas_call(
        _bwd_preprocess_kernel,
        grid=(groups, q_blocks),
        in_specs=[
            pl.BlockSpec((block_h, block_q, d_v), lambda b, qi: (b, qi, 0)),
            pl.BlockSpec((block_h, block_q, d_v), lambda b, qi: (b, qi, 0)),
        ],
        out_specs=pl.BlockSpec((block_h, 1, block_q),
                               lambda b, qi: (b, 0, qi)),
        out_shape=jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        interpret=interpret,
        name="flash_attention_delta",
    )(o, do)

    # dQ: kv minor, online accumulation into VMEM scratch
    kv_map = _kv_index_map(block_q, block_k, causal)
    q_map3 = lambda b, qi, ki: (b, qi, 0)
    q_row3 = lambda b, qi, ki: (b, 0, qi)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=block_q, block_k=block_k,
                          scale=scale, causal=causal, kv_blocks=kv_blocks,
                          valid_len=valid_len),
        grid=(groups, q_blocks, kv_blocks),
        in_specs=[
            pl.BlockSpec((block_h, block_q, d), q_map3),
            pl.BlockSpec((block_h, block_k, d), kv_map),
            pl.BlockSpec((block_h, block_k, d_v), kv_map),
            pl.BlockSpec((block_h, block_q, d_v), q_map3),
            pl.BlockSpec((block_h, 1, block_q), q_row3),
            pl.BlockSpec((block_h, 1, block_q), q_row3),
        ],
        out_specs=pl.BlockSpec((block_h, block_q, d), q_map3),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[_vmem((block_h, block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dq",
    )(q, k, v, do, lse, delta)

    # dK/dV: q minor, two accumulators in VMEM scratch
    q_clamp, q_row_clamp = _q_index_maps(block_q, block_k, causal)
    kv_map2 = lambda b, ki, qi: (b, ki, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
                          scale=scale, causal=causal, q_blocks=q_blocks,
                          kv_blocks=kv_blocks, valid_len=valid_len),
        grid=(groups, kv_blocks, q_blocks),
        in_specs=[
            pl.BlockSpec((block_h, block_q, d), q_clamp),
            pl.BlockSpec((block_h, block_k, d), kv_map2),
            pl.BlockSpec((block_h, block_k, d_v), kv_map2),
            pl.BlockSpec((block_h, block_q, d_v), q_clamp),
            pl.BlockSpec((block_h, 1, block_q), q_row_clamp),
            pl.BlockSpec((block_h, 1, block_q), q_row_clamp),
        ],
        out_specs=[
            pl.BlockSpec((block_h, block_k, d), kv_map2),
            pl.BlockSpec((block_h, block_k, d_v), kv_map2),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d_v), v.dtype),
        ],
        scratch_shapes=[
            _vmem((block_h, block_k, d), jnp.float32),
            _vmem((block_h, block_k, d_v), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
