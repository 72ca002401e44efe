"""Pallas TPU flash attention: forward *and* backward (causal, GQA-expanded).

Forward — grid (batch*heads, q_blocks, kv_blocks) with the kv axis minor; the
TPU executes the grid sequentially, so the online-softmax carry (m, l, acc)
lives in VMEM scratch across kv iterations of one (bh, q) cell.  Besides the
output block the kernel emits the per-row logsumexp ``lse = m + log(l)`` —
the residual that lets the backward recompute softmax rows without a second
online pass.

Causal grid pruning — fully-masked kv blocks (strictly above the diagonal)
are pruned at the *index map*: the kv block index is clamped to the last
in-diagonal block, so every pruned grid step maps to the block already
resident in VMEM and Pallas elides the HBM fetch (the pipeline only issues a
copy when the mapped index changes).  ``pl.when`` still skips the flops.
Previously only the flops were skipped and the fetches still occurred.

Backward — FlashAttention-2 style split into three kernels, all reusing the
same causal block-skipping and ``valid_len`` tail masking as the forward:

* ``_bwd_preprocess_kernel``: ``delta = rowsum(dO * O)`` per row — the
  softmax-Jacobian correction term, grid (bh, q_blocks).
* ``_bwd_dq_kernel``: grid (bh, q_blocks, kv_blocks), kv minor; recomputes
  ``p = exp(s - lse)`` per tile and accumulates
  ``dq += (p * (dO @ V^T - delta)) @ K * scale`` in VMEM scratch.
* ``_bwd_dkv_kernel``: grid (bh, kv_blocks, q_blocks), q minor; accumulates
  ``dv += p^T @ dO`` and ``dk += (p * (dO @ V^T - delta))^T @ Q * scale``.
  Causal pruning mirrors the forward: the q index map clamps to the first
  in-diagonal q block for this kv block.

All accumulation is fp32 in scratch; outputs are cast to the input dtype at
the final grid step of each (bh, major) cell.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _last_kv_block(qi, block_q: int, block_k: int):
    """Last kv block intersecting the causal diagonal for q block `qi`."""
    return (qi * block_q + block_q - 1) // block_k


def _first_q_block(ki, block_q: int, block_k: int):
    """First q block intersecting the causal diagonal for kv block `ki`."""
    return (ki * block_k) // block_q


def _kv_index_map(block_q: int, block_k: int, causal: bool):
    """K/V index map for (bh, q_blocks, kv_blocks) grids.  Causal pruning
    clamps above-diagonal steps onto the already-resident block so Pallas
    elides the fetch (shared by fwd and the dQ kernel)."""
    if causal:
        return lambda b, qi, ki: (
            b, jnp.minimum(ki, _last_kv_block(qi, block_q, block_k)), 0)
    return lambda b, qi, ki: (b, ki, 0)


def _q_index_maps(block_q: int, block_k: int, causal: bool):
    """(tensor, per-row) Q-side index maps for the (bh, kv_blocks, q_blocks)
    dK/dV grid — the mirror-image clamp onto the first in-diagonal q block."""
    if causal:
        def qi_of(ki, qi):
            return jnp.maximum(qi, _first_q_block(ki, block_q, block_k))
        return (lambda b, ki, qi: (b, qi_of(ki, qi), 0),
                lambda b, ki, qi: (b, 0, qi_of(ki, qi)))
    return (lambda b, ki, qi: (b, qi, 0), lambda b, ki, qi: (b, 0, qi))


def _masked_scores(q, k, qi, ki, *, block_q, block_k, scale, causal,
                   valid_len, kv_len):
    """(block_q, block_k) fp32 scores with causal + padded-tail masking."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    if causal:
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    if valid_len < kv_len:  # padded tail keys
        s = jnp.where(k_pos < valid_len, s, NEG_INF)
    return s


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
        q = q_ref[0].astype(jnp.float32)  # (block_q, d)
        k = k_ref[0].astype(jnp.float32)  # (block_k, d)
        v = v_ref[0].astype(jnp.float32)
        s = _masked_scores(q, k, qi, ki, block_q=block_q, block_k=block_k,
                           scale=scale, causal=causal, valid_len=valid_len,
                           kv_len=kv_blocks * block_k)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * corr
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
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
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l))[:, 0]


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, block_q: int = 128,
                        block_k: int = 128, valid_len: int = 0,
                        interpret: bool = False
                        ) -> tuple[jax.Array, jax.Array]:
    """q, k, v: (BH, S, D) (GQA repeat handled by ops.py).

    Returns (o (BH, S, D), lse (BH, 1, S) fp32).  `valid_len` masks padded
    tail keys (0 = none).  The per-row lse and delta are stored one row of
    lanes per head, ``(BH, 1, S)`` with ``(1, 1, block_q)`` blocks: the chip
    tiles a block's last two dimensions by (8, 128), and a ``(1, block_q)``
    block of a ``(BH, S)`` array is not such a tile.
    """
    bh, s, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    q_blocks = s // block_q
    kv_blocks = s // block_k
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, scale=scale,
        causal=causal, kv_blocks=kv_blocks, valid_len=valid_len or s)

    kv_map = _kv_index_map(block_q, block_k, causal)

    return pl.pallas_call(
        kernel,
        grid=(bh, q_blocks, kv_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, qi, ki: (b, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            _vmem((block_q, 1), jnp.float32),  # m: running row max
            _vmem((block_q, 1), jnp.float32),  # l: running row sum
            _vmem((block_q, d), jnp.float32),  # acc: weighted values
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_preprocess_kernel(o_ref, do_ref, delta_ref):
    o = o_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    delta_ref[0, 0] = jnp.sum(o * do, axis=-1)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, block_q: int, block_k: int, scale: float,
                   causal: bool, kv_blocks: int, valid_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]      # (block_q, 1)
        delta = delta_ref[0, 0][:, None]  # (block_q, 1)
        s = _masked_scores(q, k, qi, ki, block_q=block_q, block_k=block_k,
                           scale=scale, causal=causal, valid_len=valid_len,
                           kv_len=kv_blocks * block_k)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        @pl.when(ki <= _last_kv_block(qi, block_q, block_k))
        def _run():
            _body()
    else:
        _body()

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


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
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = _masked_scores(q, k, qi, ki, block_q=block_q, block_k=block_k,
                           scale=scale, causal=causal, valid_len=valid_len,
                           kv_len=kv_blocks * block_k)
        p = jnp.exp(s - lse)  # (block_q, block_k)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        @pl.when(qi >= _first_q_block(ki, block_q, block_k))
        def _run():
            _body()
    else:
        _body()

    @pl.when(qi == q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        o: jax.Array, lse: jax.Array, do: jax.Array, *,
                        causal: bool = True, block_q: int = 128,
                        block_k: int = 128, valid_len: int = 0,
                        interpret: bool = False
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Backward pass at the flattened (BH, S, D) layout.

    q, k, v, o, do: (BH, S, D); lse: (BH, 1, S) fp32 from the forward.
    Returns (dq, dk, dv) with the input dtypes.
    """
    bh, s, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    q_blocks = s // block_q
    kv_blocks = s // block_k
    scale = 1.0 / math.sqrt(d)
    valid_len = valid_len or s

    # delta = rowsum(dO * O): the softmax-Jacobian correction term
    delta = pl.pallas_call(
        _bwd_preprocess_kernel,
        grid=(bh, q_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, qi: (b, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q), lambda b, qi: (b, 0, qi)),
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
        grid=(bh, q_blocks, kv_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map3),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_q, d), q_map3),
            pl.BlockSpec((1, 1, block_q), q_row3),
            pl.BlockSpec((1, 1, block_q), q_row3),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_map3),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[_vmem((block_q, d), jnp.float32)],
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
        grid=(bh, kv_blocks, q_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_clamp),
            pl.BlockSpec((1, block_k, d), kv_map2),
            pl.BlockSpec((1, block_k, d), kv_map2),
            pl.BlockSpec((1, block_q, d), q_clamp),
            pl.BlockSpec((1, 1, block_q), q_row_clamp),
            pl.BlockSpec((1, 1, block_q), q_row_clamp),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), kv_map2),
            pl.BlockSpec((1, block_k, d), kv_map2),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
        scratch_shapes=[
            _vmem((block_k, d), jnp.float32),
            _vmem((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
