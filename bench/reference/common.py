"""What the plain references share, whatever the architecture: the
precisions a reference runs in, their matrix product, one AdamW step, and
the leaf norms that ``correct`` compares.

Like the references, this imports nothing of the program under test.

``prec`` is the precision the arithmetic runs in.  ``"float32"`` runs
every matrix product at ``"highest"`` precision: the reference.  The two
lower ones are controls, the steps a later change would be tempted to
take: ``"bfloat16"`` stores parameters and activations in bfloat16 (the
normalisation, softmax and loss statistics stay float32); ``"int8"`` keeps
float32 storage and rounds both operands of every matrix product to 255
levels of a symmetric per-tensor scale (max |x| / 127), as an int8 matmul
would, below the one bfloat16 pass that XLA's default precision makes of a
float32 product on a TPU.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "int8")


def storage(prec: str):
    """The type parameters and activations are kept in under ``prec``."""
    if prec not in PRECISIONS:
        raise ValueError(f"unknown precision {prec!r} (want {PRECISIONS})")
    return jnp.bfloat16 if prec == "bfloat16" else jnp.float32


def matmul_precision(prec: str) -> str:
    """XLA's matmul precision for the products of ``prec``."""
    return "highest" if prec != "bfloat16" else "default"


def q8(x):
    """Round to the int8 grid of a symmetric per-tensor scale."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.round(x / s) * s


def mm(spec: str, a, b, prec: str):
    """``einsum(spec, a, b)``, with both operands on the int8 grid under
    ``"int8"``."""
    if prec == "int8":
        a, b = q8(a), q8(b)
    return jnp.einsum(spec, a, b)


def cast(tree, dtype):
    return jax.tree_util.tree_map(lambda t: t.astype(dtype), tree)


def adamw_init(params):
    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return {"m": zeros, "v": jax.tree_util.tree_map(jnp.copy, zeros),
            "count": 0}


@jax.jit
def _adamw(params, grads, m, v, count, lr, clip, b1, b2, eps, wd):
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12))
    grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    m = jax.tree_util.tree_map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda v_, g: b2 * v_ + (1 - b2) * g * g,
                               v, grads)
    bc1 = 1 - b1 ** count
    bc2 = 1 - b2 ** count
    new = jax.tree_util.tree_map(
        lambda p, m_, v_: p - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
                                    + wd * p), params, m, v)
    return new, grads, m, v


def adamw_step(params, grads, opt, *, lr, clip, b1, b2, eps, weight_decay):
    """Global-norm clip, Adam with bias correction, decoupled weight decay
    on every leaf, then the learning rate.  Returns (params, clipped
    gradients, opt)."""
    count = opt["count"] + 1
    new, clipped, m, v = _adamw(params, grads, opt["m"], opt["v"],
                                jnp.float32(count), jnp.float32(lr),
                                jnp.float32(clip), b1, b2, eps, weight_decay)
    return new, clipped, {"m": m, "v": v, "count": count}


def leaf_norms(tree) -> List[float]:
    return [float(x) for x in jax.jit(lambda t: [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for x in jax.tree_util.tree_leaves(t)])(tree)]


def diff_norms(a, b) -> List[float]:
    return [float(x) for x in jax.jit(lambda s, t: [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                    - y.astype(jnp.float32))))
        for x, y in zip(jax.tree_util.tree_leaves(s),
                        jax.tree_util.tree_leaves(t))])(a, b)]
