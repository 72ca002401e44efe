"""Pure-jnp oracle for the flash-attention kernel."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def attention_reference(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True) -> jax.Array:
    """q, k: (BH, S, D); v: (BH, S, D_v). Dense softmax attention in fp32,
    scores scaled by 1/sqrt(D)."""
    bh, s, d = q.shape
    scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask[None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def attention_reference_gqa(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            causal: bool = True) -> jax.Array:
    """Model-layout oracle: q (B, S, H, D); k (B, S, KV, D); v (B, S, KV,
    D_v), H = KV * G.

    The one place the GQA expand/flatten layout is defined alongside the
    dense reference — tests and benchmarks diff kernel outputs/grads
    against this instead of hand-rolling the transpose each time.
    """
    b, s, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = jnp.repeat(k, g, 2).transpose(0, 2, 1, 3).reshape(b * h, s, d)
    d_v = v.shape[-1]
    vf = jnp.repeat(v, g, 2).transpose(0, 2, 1, 3).reshape(b * h, s, d_v)
    o = attention_reference(qf, kf, vf, causal=causal)
    return o.reshape(b, h, s, d_v).transpose(0, 2, 1, 3)
