"""Plain GPT-2: its parameter tree, forward pass, loss and gradients.

Written from the GPT-2 description (Radford et al. 2019) in straightforward
``jax.numpy``: learned positions, pre-LayerNorm blocks, causal softmax
attention over the whole sequence, a tanh-GELU MLP, a final LayerNorm and
an output head tied to the token embedding.  It imports nothing of the
program under test: no kernels, no cache, no batching tricks.

The parameter tree is the one the program's dense model reads, and this
file defines it (``weight_shapes``, ``weight_init``), so the benchmark's
seeded weights (``bench/harness/weights.py``) are made to its layout:

    embed (V_pad, d), pos_embed (P, d), final_norm {scale, bias},
    layers: ln1/ln2 {scale, bias} (L, d); attn wq/wk/wv (L, d, H, Dh),
            wo (L, H, Dh, d); mlp w_up (L, d, F), b_up (L, F),
            w_down (L, F, d), b_down (L, d)

Rows of ``embed`` past the real vocabulary are padding: the logits, the
softmax and the loss run over the first ``vocab`` rows only.

``prec`` is the precision the arithmetic runs in (``bench/reference/
common.py``): ``"float32"`` is the reference, ``"bfloat16"`` and
``"int8"`` the controls.  Departures from the published model: no dropout
(the program has none).

Memory: gradients are accumulated over blocks of ``rows_per_block`` rows,
and each layer is recomputed in the backward pass, so the reference fits
next to nothing else on one chip at the timed batch.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from bench.reference.common import cast, matmul_precision, mm, storage


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


def weight_shapes(d: Dims) -> Dict[str, Any]:
    """The parameter tree, as shapes."""
    L, D, H, K, F = d.n_layers, d.d_model, d.n_heads, d.head_dim, d.d_ff
    norm = {"scale": (L, D), "bias": (L, D)}
    return {
        "embed": (d.vocab_padded, D),
        "pos_embed": (d.n_positions, D),
        "final_norm": {"scale": (D,), "bias": (D,)},
        "layers": {
            "ln1": dict(norm), "ln2": dict(norm),
            "attn": {"wq": (L, D, H, K), "wk": (L, D, H, K),
                     "wv": (L, D, H, K), "wo": (L, H, K, D)},
            "mlp": {"w_up": (L, D, F), "b_up": (L, F),
                    "w_down": (L, F, D), "b_down": (L, D)},
        },
    }


def weight_init(path: str, d: Dims) -> Tuple[float, float]:
    """(mean, std) of the normal draw of the leaf at ``path``: GPT-2's
    std 0.02, the two residual projections scaled by 1/sqrt(2L).  Biases
    and LayerNorm parameters are drawn too, not left at 0 and 1, so a
    program that dropped one of them would not match the reference."""
    mean = 1.0 if path.endswith("scale") else 0.0
    if path.endswith(("wo", "w_down")):
        return mean, 0.02 / math.sqrt(2 * d.n_layers)
    return mean, 0.02


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
    q = mm("bsd,dhk->bshk", h, a["wq"], prec)
    k = mm("bsd,dhk->bshk", h, a["wk"], prec)
    v = mm("bsd,dhk->bshk", h, a["wv"], prec)
    scores = mm("bqhk,bjhk->bhqj", q, k, prec).astype(jnp.float32)
    scores = scores / math.sqrt(dims.head_dim)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(dt)
    ctx = mm("bhqj,bjhk->bqhk", probs, v, prec)
    x = x + mm("bshk,hkd->bsd", ctx, a["wo"], prec)
    h = _layer_norm(x, lp["ln2"], dims.eps)
    m = lp["mlp"]
    up = _gelu(mm("bsd,df->bsf", h, m["w_up"], prec) + m["b_up"])
    return x + mm("bsf,fd->bsd", up, m["w_down"], prec) + m["b_down"]


def logits(params, tokens, dims: Dims, prec: str = "float32"):
    """(B, S) token ids -> (B, S, vocab) float32 logits."""
    with jax.default_matmul_precision(matmul_precision(prec)):
        p = cast(params, storage(prec))
        s = tokens.shape[1]
        x = jnp.take(p["embed"], tokens, axis=0) + p["pos_embed"][:s]

        def body(carry, lp):
            return jax.checkpoint(_block, static_argnums=(2, 3))(
                carry, lp, dims, prec), None

        x, _ = jax.lax.scan(body, x, p["layers"])
        x = _layer_norm(x, p["final_norm"], dims.eps)
        out = mm("bsd,vd->bsv", x, p["embed"][:dims.vocab], prec)
        return out.astype(jnp.float32)


def loss_sum(params, tokens, labels, dims: Dims, prec: str = "float32"):
    """Summed next-token cross-entropy over every position."""
    lg = logits(params, tokens, dims, prec)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold)


class Reference:
    """Loss and gradients of the plain model, in row blocks."""

    def __init__(self, dims: Dims, prec: str = "float32",
                 rows_per_block: int = 1):
        storage(prec)
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
