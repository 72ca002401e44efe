"""Plain DeepSeek-V3 (as Moonlight-16B-A3B configures it), cut to one
chip's share: its parameter tree, forward pass, loss and gradients.

Written from the DeepSeek-V3 description (``model_type`` ``deepseek_v3``:
the published ``config.json`` and modeling code) in straightforward
``jax.numpy``.  It imports nothing of the program under test: no kernels,
no sorting of tokens, no grouped products.

* Pre-RMSNorm blocks; the first ``first_k_dense_replace`` layers have a
  SwiGLU MLP of ``intermediate_size``, the rest a mixture of experts.
* Latent attention without q LoRA: q = x Wq, (16 heads of 128 + 64); the
  latent c and the shared rotary key k_r = x Wkv_a; keys and values per
  head = RMSNorm(c) Wkv_b, (128 + 128); RoPE on the 64 rotary dims of q
  and of k_r, rotating consecutive pairs (2i, 2i + 1) by position times
  theta^(-2i/64); k_r is the same for every head; causal softmax at
  1/sqrt(192); the output projection Wo.  Attention is computed in blocks
  of queries, each against every key, so the whole score matrix never
  exists at once.
* Experts: the router in float32 over the published ``n_routed_experts``
  (``published``), sigmoid scores, the top ``num_experts_per_tok`` of
  score + correction bias (``noaux_tc`` with one group), weights the
  chosen scores normalised over the k (``norm_topk_prob``), times
  ``routed_scaling_factor``.  The experts held here (the file's
  ``n_routed_experts`` from ``expert_offset``) each run on every token,
  weighted by that token's weight for them (zero where not chosen); the
  absent experts' part is left out.  Shared experts: one SwiGLU of
  ``n_shared_experts`` x ``moe_intermediate_size``.
* A final RMSNorm and an untied head over the vocabulary slice; the loss
  is the cross-entropy over it.

Departures from the published model (the configuration file's
``departures``): the correction bias is seeded and fixed (it takes no
gradient), no sequence-wise balance loss, weights from a seed.

The parameter tree is the one the program reads (``weight_shapes``,
``weight_init``):

    embed (V_pad, d); lm_head (d, V_pad); final_norm {scale}
    dense_layers / layers: ln1, ln2 {scale}; attn {wq (L, d, H, 192),
        wkv_a (L, d, 576), kv_norm {scale (L, 512)}, wkv_b (L, 512, H, 256),
        wo (L, H, 128, d)}
    dense_layers: mlp {w_gate, w_up (L, d, F), w_down (L, F, d)}
    layers: moe {router (L, d, E), router_bias (L, E), w_gate, w_up
        (L, E_held, d, F_e), w_down (L, E_held, F_e, d), shared {w_gate,
        w_up (L, d, F_s), w_down (L, F_s, d)}}

``prec`` is the precision the arithmetic runs in (``bench/reference/
common.py``).  Memory: gradients over blocks of ``rows_per_block`` rows,
each layer and each query block recomputed in the backward pass.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from bench.reference.common import cast, matmul_precision, mm, storage

# the std of the seeded correction bias: a normal draw, so that a program
# that ignores the bias chooses other experts; kept small beside the
# spread of the sigmoid scores, so that the experts held here see near
# their even share of the rows whatever the seed
BIAS_STD = 0.01
# rows of queries per attention block
QUERY_BLOCK = 1024


@dataclass(frozen=True)
class Dims:
    n_layers: int        # all layers, the dense ones included
    n_dense: int
    d_model: int
    n_heads: int
    kv_lora_rank: int
    qk_nope: int
    qk_rope: int
    v_head: int
    d_ff: int            # the dense layers' width
    d_expert: int
    n_experts: int       # the router's width: the published count
    n_held: int
    expert_offset: int
    top_k: int
    n_shared: int
    routed_scaling: float
    vocab: int
    eps: float
    rope_theta: float
    n_positions: int

    @property
    def head_dim(self) -> int:
        """The query and key width of a head."""
        return self.qk_nope + self.qk_rope

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.n_dense

    @property
    def vocab_padded(self) -> int:
        return -(-self.vocab // 128) * 128


def dims_from_config(cfg: Dict[str, Any]) -> Dims:
    """Dims from a DeepSeek-V3 ``config.json``-style dict, with the
    published expert count under ``published`` where experts are cut."""
    fixed = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
             "n_group": 1, "topk_group": 1, "q_lora_rank": None,
             "hidden_act": "silu", "moe_layer_freq": 1,
             "attention_bias": False, "tie_word_embeddings": False,
             "norm_topk_prob": True}
    for key, want in fixed.items():
        if cfg.get(key) != want:
            raise ValueError(f"{key} {cfg.get(key)!r}: this reference has "
                             f"only {want!r}")
    if cfg.get("rope_scaling"):
        raise ValueError("rope_scaling: this reference has none")
    published = cfg.get("published", {})
    return Dims(
        n_layers=cfg["num_hidden_layers"],
        n_dense=cfg["first_k_dense_replace"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope=cfg["qk_nope_head_dim"], qk_rope=cfg["qk_rope_head_dim"],
        v_head=cfg["v_head_dim"], d_ff=cfg["intermediate_size"],
        d_expert=cfg["moe_intermediate_size"],
        n_experts=published.get("n_routed_experts", cfg["n_routed_experts"]),
        n_held=cfg["n_routed_experts"],
        expert_offset=cfg.get("expert_offset", 0),
        top_k=cfg["num_experts_per_tok"], n_shared=cfg["n_shared_experts"],
        routed_scaling=cfg["routed_scaling_factor"], vocab=cfg["vocab_size"],
        eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        n_positions=cfg["max_position_embeddings"])


def _attn_shapes(L: int, d: Dims) -> Dict[str, Any]:
    D, H, r = d.d_model, d.n_heads, d.kv_lora_rank
    return {"wq": (L, D, H, d.head_dim), "wkv_a": (L, D, r + d.qk_rope),
            "kv_norm": {"scale": (L, r)},
            "wkv_b": (L, r, H, d.qk_nope + d.v_head),
            "wo": (L, H, d.v_head, D)}


def _swiglu_shapes(L: int, D: int, F: int) -> Dict[str, Any]:
    return {"w_gate": (L, D, F), "w_up": (L, D, F), "w_down": (L, F, D)}


def weight_shapes(d: Dims) -> Dict[str, Any]:
    """The parameter tree, as shapes."""
    D, Lm, Eh, Fe = d.d_model, d.n_moe, d.n_held, d.d_expert
    tree: Dict[str, Any] = {
        "embed": (d.vocab_padded, D),
        "lm_head": (D, d.vocab_padded),
        "final_norm": {"scale": (D,)},
        "layers": {
            "ln1": {"scale": (Lm, D)}, "ln2": {"scale": (Lm, D)},
            "attn": _attn_shapes(Lm, d),
            "moe": {"router": (Lm, D, d.n_experts),
                    "router_bias": (Lm, d.n_experts),
                    "w_gate": (Lm, Eh, D, Fe), "w_up": (Lm, Eh, D, Fe),
                    "w_down": (Lm, Eh, Fe, D),
                    "shared": _swiglu_shapes(Lm, D, d.n_shared * Fe)},
        },
    }
    if d.n_dense:
        tree["dense_layers"] = {
            "ln1": {"scale": (d.n_dense, D)}, "ln2": {"scale": (d.n_dense, D)},
            "attn": _attn_shapes(d.n_dense, d),
            "mlp": _swiglu_shapes(d.n_dense, D, d.d_ff)}
    return tree


def weight_init(path: str, d: Dims) -> Tuple[float, float]:
    """(mean, std) of the normal draw of the leaf at ``path``: DeepSeek's
    initializer range 0.02 for every matrix; the norm scales are drawn
    about 1 and the correction bias about 0 (``BIAS_STD``), not left at
    their defaults, so a program that dropped one would not match."""
    if path.endswith("scale"):
        return 1.0, 0.02
    if path.endswith("router_bias"):
        return 0.0, BIAS_STD
    return 0.0, 0.02


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                       + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rotate_pairs(x, theta: float):
    """x (B, S, heads, R): dims (2i, 2i + 1) turned by position x
    theta^(-2i/R), as a complex number is by a unit one."""
    s, r = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x32 = x.astype(jnp.float32)
    re, im = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([re * cos - im * sin, re * sin + im * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _attention(x, a, dims: Dims, prec: str):
    b, s, _ = x.shape
    r, dn = dims.kv_lora_rank, dims.qk_nope
    q = mm("bsd,dhk->bshk", x, a["wq"], prec)
    kv_a = mm("bsd,dr->bsr", x, a["wkv_a"], prec)
    kv = mm("bsr,rhk->bshk", _rms_norm(kv_a[..., :r], a["kv_norm"]["scale"],
                                       dims.eps), a["wkv_b"], prec)
    k_rope = _rotate_pairs(kv_a[:, :, None, r:], dims.rope_theta)
    q = jnp.concatenate([q[..., :dn], _rotate_pairs(q[..., dn:],
                                                    dims.rope_theta)], -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, kv.shape[:3] + (dims.qk_rope,))],
        -1)
    v = kv[..., dn:]
    qb = min(QUERY_BLOCK, s)
    if s % qb:
        qb = s
    scale = 1.0 / math.sqrt(dims.head_dim)

    def block(args):
        i, qi = args
        scores = mm("bqhk,bjhk->bhqj", qi, k, prec).astype(jnp.float32)
        q_pos = i * qb + jnp.arange(qb)
        causal = q_pos[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(causal, scores * scale, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        return mm("bhqj,bjhk->bqhk", probs, v, prec)

    blocks = q.reshape(b, s // qb, qb, dims.n_heads, dims.head_dim)
    ctx = jax.lax.map(jax.checkpoint(block),
                      (jnp.arange(s // qb), blocks.swapaxes(0, 1)))
    ctx = ctx.swapaxes(0, 1).reshape(b, s, dims.n_heads, dims.v_head)
    return mm("bshk,hkd->bsd", ctx, a["wo"], prec)


def _swiglu(x, p, prec: str):
    h = jax.nn.silu(mm("td,df->tf", x, p["w_gate"], prec)) \
        * mm("td,df->tf", x, p["w_up"], prec)
    return mm("tf,fd->td", h, p["w_down"], prec)


def _experts(x, p, dims: Dims, prec: str):
    """The held experts' share of the layer and the shared experts."""
    b, s, dm = x.shape
    xt = x.reshape(b * s, dm)
    logits = mm("td,de->te", xt.astype(jnp.float32),
                p["router"].astype(jnp.float32), prec)
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores + p["router_bias"].astype(jnp.float32),
                              dims.top_k)
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    weight = weight * dims.routed_scaling
    y = _swiglu(xt, p["shared"], prec)
    for j in range(dims.n_held):
        mine = (chosen == dims.expert_offset + j).astype(jnp.float32)
        w_j = jnp.sum(weight * mine, axis=-1, keepdims=True).astype(x.dtype)
        y = y + w_j * _swiglu(xt, {"w_gate": p["w_gate"][j],
                                   "w_up": p["w_up"][j],
                                   "w_down": p["w_down"][j]}, prec)
    return y.reshape(b, s, dm)


def _block(x, lp, dims: Dims, prec: str, moe: bool):
    x = x + _attention(_rms_norm(x, lp["ln1"]["scale"], dims.eps),
                       lp["attn"], dims, prec)
    h = _rms_norm(x, lp["ln2"]["scale"], dims.eps)
    if moe:
        return x + _experts(h, lp["moe"], dims, prec)
    b, s, dm = h.shape
    return x + _swiglu(h.reshape(b * s, dm), lp["mlp"], prec).reshape(h.shape)


def logits(params, tokens, dims: Dims, prec: str = "float32"):
    """(B, S) token ids -> (B, S, vocab) float32 logits."""
    with jax.default_matmul_precision(matmul_precision(prec)):
        p = cast(params, storage(prec))
        x = jnp.take(p["embed"], tokens, axis=0)
        for stack, moe in (("dense_layers", False), ("layers", True)):
            if stack not in p:
                continue

            def body(carry, lp, moe=moe):
                return jax.checkpoint(_block, static_argnums=(2, 3, 4))(
                    carry, lp, dims, prec, moe), None

            x, _ = jax.lax.scan(body, x, p[stack])
        x = _rms_norm(x, p["final_norm"]["scale"], dims.eps)
        out = mm("bsd,dv->bsv", x, p["lm_head"][:, :dims.vocab], prec)
        return out.astype(jnp.float32)


def loss_sum(params, tokens, labels, dims: Dims, prec: str = "float32"):
    """Summed next-token cross-entropy over every position."""
    lg = logits(params, tokens, dims, prec)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold)


class Reference:
    """Loss and gradients of the plain model, in row blocks.

    The gradients are computed on the default device and handed back on
    the host's CPU device, committed there, so the optimizer step that
    takes them, and the state it returns, run and stay on the host too.
    A float32 AdamW step holds the weights, the gradient, both
    moments and its four results, eight copies of the parameters: at
    Moonlight's one-chip cut (2.27 GB a copy) more than the chip holds.
    The device holds the weights of one step, the gradient and the pass's
    temporaries; the host runs the optimizer's arithmetic, which is the
    same in float32 wherever it runs."""

    def __init__(self, dims: Dims, prec: str = "float32",
                 rows_per_block: int = 1):
        storage(prec)
        self.dims = dims
        self.prec = prec
        self.rows_per_block = rows_per_block
        self.host = jax.devices("cpu")[0]
        self._grad_block = jax.jit(jax.value_and_grad(
            lambda p, t, l: loss_sum(p, t, l, dims, prec)))
        self._acc = jax.jit(lambda acc, g: jax.tree_util.tree_map(
            jnp.add, acc, g), donate_argnums=(0,))

    def loss_and_grad(self, params, tokens, labels):
        """Mean loss over every token of the batch and its gradient, the
        gradient on the host."""
        params = jax.device_put(params, jax.devices()[0])
        total, grads = 0.0, None
        rb = self.rows_per_block
        for i in range(0, tokens.shape[0], rb):
            v, g = self._grad_block(params, jnp.asarray(tokens[i:i + rb]),
                                    jnp.asarray(labels[i:i + rb]))
            total += float(v)
            grads = g if grads is None else self._acc(grads, g)
        del params
        n = tokens.size
        grads = jax.tree_util.tree_map(lambda g: g / n,
                                       jax.device_put(grads, self.host))
        return total / n, grads
