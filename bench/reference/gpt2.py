"""Plain GPT-2: forward pass, loss, gradients and one AdamW step.

Written from the GPT-2 description (Radford et al. 2019) in straightforward
``jax.numpy``: learned positions, pre-LayerNorm blocks, causal softmax
attention over the whole sequence, a tanh-GELU MLP, a final LayerNorm and
an output head tied to the token embedding.  It imports nothing of the
program under test: no kernels, no cache, no batching tricks.

The parameter tree is the benchmark's own (``bench/harness/weights.py``):

    embed (V_pad, d), pos_embed (P, d), final_norm {scale, bias},
    layers: ln1/ln2 {scale, bias} (L, d); attn wq/wk/wv (L, d, H, Dh),
            wo (L, H, Dh, d); mlp w_up (L, d, F), b_up (L, F),
            w_down (L, F, d), b_down (L, d)

Rows of ``embed`` past the real vocabulary are padding: the logits, the
softmax and the loss run over the first ``vocab`` rows only.

``prec`` is the precision the arithmetic runs in.  ``"float32"`` runs
every matrix product at ``"highest"`` precision: the reference.  The two
lower ones are controls, the steps a later change would be tempted to
take: ``"bfloat16"`` stores parameters and activations in bfloat16 (the
normalisation, softmax and loss statistics stay float32); ``"int8"`` keeps
float32 storage and rounds both operands of every matrix product to 255
levels of a symmetric per-tensor scale (max |x| / 127), as an int8 matmul
would, below the one bfloat16 pass that XLA's default precision makes of a
float32 product on a TPU.  Departures from the published model: no dropout
(the program has none).

Memory: gradients are accumulated over blocks of ``rows_per_block`` rows,
and each layer is recomputed in the backward pass, so the reference fits
next to nothing else on one chip at the timed batch.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    vocab: int
    n_positions: int
    eps: float

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def vocab_padded(self) -> int:
        return -(-self.vocab // 128) * 128


def dims_from_config(cfg: Dict[str, Any]) -> Dims:
    """Dims from a GPT-2 ``config.json``-style dict."""
    d = cfg["n_embd"]
    return Dims(n_layers=cfg["n_layer"], d_model=d, n_heads=cfg["n_head"],
                d_ff=cfg.get("n_inner") or 4 * d, vocab=cfg["vocab_size"],
                n_positions=cfg["n_positions"],
                eps=cfg["layer_norm_epsilon"])


PRECISIONS = ("float32", "bfloat16", "int8")


def _storage(prec: str):
    if prec not in PRECISIONS:
        raise ValueError(f"unknown precision {prec!r} (want {PRECISIONS})")
    return jnp.bfloat16 if prec == "bfloat16" else jnp.float32


def _q8(x):
    """Round to the int8 grid of a symmetric per-tensor scale."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.round(x / s) * s


def _mm(spec: str, a, b, prec: str):
    if prec == "int8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b)


def _layer_norm(x, p, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) / jnp.sqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def _gelu(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x * x * x)))


def _block(x, lp, dims: Dims, prec: str):
    dt = x.dtype
    s = x.shape[1]
    h = _layer_norm(x, lp["ln1"], dims.eps)
    a = lp["attn"]
    q = _mm("bsd,dhk->bshk", h, a["wq"], prec)
    k = _mm("bsd,dhk->bshk", h, a["wk"], prec)
    v = _mm("bsd,dhk->bshk", h, a["wv"], prec)
    scores = _mm("bqhk,bjhk->bhqj", q, k, prec).astype(jnp.float32)
    scores = scores / math.sqrt(dims.head_dim)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(dt)
    ctx = _mm("bhqj,bjhk->bqhk", probs, v, prec)
    x = x + _mm("bshk,hkd->bsd", ctx, a["wo"], prec)
    h = _layer_norm(x, lp["ln2"], dims.eps)
    m = lp["mlp"]
    up = _gelu(_mm("bsd,df->bsf", h, m["w_up"], prec) + m["b_up"])
    return x + _mm("bsf,fd->bsd", up, m["w_down"], prec) + m["b_down"]


def _cast(tree, dtype):
    return jax.tree_util.tree_map(lambda t: t.astype(dtype), tree)


def logits(params, tokens, dims: Dims, prec: str = "float32"):
    """(B, S) token ids -> (B, S, vocab) float32 logits."""
    with jax.default_matmul_precision(
            "highest" if prec != "bfloat16" else "default"):
        p = _cast(params, _storage(prec))
        s = tokens.shape[1]
        x = jnp.take(p["embed"], tokens, axis=0) + p["pos_embed"][:s]

        def body(carry, lp):
            return jax.checkpoint(_block, static_argnums=(2, 3))(
                carry, lp, dims, prec), None

        x, _ = jax.lax.scan(body, x, p["layers"])
        x = _layer_norm(x, p["final_norm"], dims.eps)
        out = _mm("bsd,vd->bsv", x, p["embed"][:dims.vocab], prec)
        return out.astype(jnp.float32)


def loss_sum(params, tokens, labels, dims: Dims, prec: str = "float32"):
    """Summed next-token cross-entropy over every position."""
    lg = logits(params, tokens, dims, prec)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold)


class Reference:
    """Loss, gradients and AdamW steps of the plain model, in row blocks."""

    def __init__(self, dims: Dims, prec: str = "float32",
                 rows_per_block: int = 1):
        _storage(prec)
        self.dims = dims
        self.prec = prec
        self.rows_per_block = rows_per_block
        self._grad_block = jax.jit(jax.value_and_grad(
            lambda p, t, l: loss_sum(p, t, l, dims, prec)))
        self._acc = jax.jit(lambda acc, g: jax.tree_util.tree_map(
            jnp.add, acc, g), donate_argnums=(0,))

    def _blocks(self, tokens, labels):
        rb = self.rows_per_block
        for i in range(0, tokens.shape[0], rb):
            yield (jnp.asarray(tokens[i:i + rb]),
                   jnp.asarray(labels[i:i + rb]))

    def loss_and_grad(self, params, tokens, labels):
        """Mean loss over every token of the batch and its gradient."""
        total, grads = 0.0, None
        for t, l in self._blocks(tokens, labels):
            v, g = self._grad_block(params, t, l)
            total += float(v)
            grads = g if grads is None else self._acc(grads, g)
        n = tokens.size
        grads = jax.tree_util.tree_map(lambda g: g / n, grads)
        return total / n, grads


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
