"""Differentiable jit'd public wrapper for the flash-attention kernels.

``flash_attention`` is a ``jax.custom_vjp`` at the model-facing layout
(q: (B, S, H, D); k: (B, S, KV, D); v: (B, S, KV, D_v) with H = KV * G;
D_v = D but for latent attention):

* tiles: ``kernel.tiles`` chooses ``(block_h, block_q, block_k)`` from
  the shapes (B*H, S, D and the itemsize): sequence blocks of the whole
  sequence where its kernels fit VMEM with two heads a step, else 512 (or
  256, 128), and a group of ``block_h`` heads per grid step, as many as
  fit.  ``block_q`` / ``block_k`` passed in replace the sequence blocks;
  the head group is chosen for them.
* forward: expands KV heads to Q heads (GQA), flattens to (B*H, S, D), pads
  the sequence to a block multiple (padded tail keys masked via
  ``valid_len``), and runs the fused Pallas forward — saving the
  ``(q, k, v, o, lse)`` residuals with k/v kept *unexpanded*, so the k/v
  share of residual memory scales with KV heads, not Q heads (o and lse
  are per-Q-head by nature).
* backward: re-expands/pads, runs the three Pallas backward kernels
  (preprocess delta, dQ, dK/dV — see kernel.py), then accumulates the
  per-Q-head dK/dV back to the (B, S, KV, D) layout by summing over each
  KV head's group of G query heads.

Off-TPU the kernels run in interpret mode (this container is CPU:
``interpret=True`` executes the kernel body in Python for validation);
``jax.grad`` through ``flash_attention`` therefore works on every backend.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.flash_attention.kernel import (flash_attention_bwd,
                                                  flash_attention_fwd, tiles)


def _flatten(x: jax.Array, g: int, pad: int) -> jax.Array:
    """(B, S, Hx, D) -> (B*Hx*g, S+pad, D): GQA-expand, head-major, pad."""
    b, s, h, d = x.shape
    if g > 1:
        x = jnp.repeat(x, g, axis=2)
    x = x.transpose(0, 2, 1, 3).reshape(b * h * g, s, d)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def _unflatten(x: jax.Array, b: int, s: int) -> jax.Array:
    """(B*H, S_pad, D) -> (B, S, H, D): unpad, head-minor."""
    bh, s_pad, d = x.shape
    return x[:, :s, :].reshape(b, bh // b, s, d).transpose(0, 2, 1, 3)


def _prep(q, k, v, block_q, block_k):
    """Shared fwd/bwd prologue: resolve tiles + padding, flatten q/k/v.

    Returns (g, (hg, bq, bk), pad, qf, kf, vf) — the one definition of the
    layout the residuals are saved in and the backward re-derives.
    """
    b, s, h, d = q.shape
    g = h // k.shape[2]
    blocks = tiles(b * h, s, d, q.dtype.itemsize, block_q, block_k,
                   d_v=v.shape[-1])
    # the padded length must be divisible by *both* blocks, not just the
    # larger one (e.g. s=96, bq=64, bk=96 needs lcm padding, not zero)
    pad = (-s) % math.lcm(*blocks[1:])
    return (g, blocks, pad, _flatten(q, 1, pad), _flatten(k, g, pad),
            _flatten(v, g, pad))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    b, s = q.shape[:2]
    _, (hg, bq, bk), _, qf, kf, vf = _prep(q, k, v, block_q, block_k)
    of, lse = flash_attention_fwd(qf, kf, vf, causal=causal, block_h=hg,
                                  block_q=bq, block_k=bk, valid_len=s,
                                  interpret=interpret)
    out = _unflatten(of, b, s)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    b, s, _, d = q.shape
    kv, d_v = k.shape[2], v.shape[-1]
    g, (hg, bq, bk), pad, qf, kf, vf = _prep(q, k, v, block_q, block_k)
    of = _flatten(out, 1, pad)
    dof = _flatten(do, 1, pad)
    dqf, dkf, dvf = flash_attention_bwd(
        qf, kf, vf, of, lse, dof, causal=causal, block_h=hg, block_q=bq,
        block_k=bk, valid_len=s, interpret=interpret)
    dq = _unflatten(dqf, b, s)
    # accumulate per-Q-head dK/dV over each KV head's group of G query
    # heads — in fp32, so bf16 inputs don't compound rounding over G adds
    dk = (_unflatten(dkf, b, s).astype(jnp.float32)
          .reshape(b, s, kv, g, d).sum(axis=3))
    dv = (_unflatten(dvf, b, s).astype(jnp.float32)
          .reshape(b, s, kv, g, d_v).sum(axis=3))
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int | None = None,
                    block_k: int | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """q: (B, S, H, D); k: (B, S, KV, D); v: (B, S, KV, D_v) with
    H = KV * G.  Returns (B, S, H, D_v); scores are scaled by 1/sqrt(D).

    ``block_q`` / ``block_k`` of None are chosen from the shapes
    (``kernel.tiles``).

    Differentiable end-to-end: ``jax.grad`` routes through the Pallas
    backward kernels via the custom VJP above.
    """
    return _flash(q, k, v, causal, block_q, block_k,
                  resolve_interpret(interpret))
