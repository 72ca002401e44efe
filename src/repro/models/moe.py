"""Fine-grained MoE (DeepSeekMoE / Moonlight style): shared + routed experts.

Two capacity dispatches: top-k token-choice routing with a capacity
buffer, sort-free — the position of each (token, expert) assignment inside
its expert's capacity buffer is a cumulative count over a one-hot matrix —
static shapes, scatter + gather, TPU/XLA-SPMD friendly.  Experts are
sharded over the ``model`` mesh axis (EP); the scatter/gather across the
token-sharded <-> expert-sharded boundary lowers to all-to-all-style
collectives under SPMD.

And one dropless expert-share dispatch (``moe_ffn_dropless``): the layer
holds ``n_held_experts`` of the router's ``n_experts``, routes over all of
them, sorts the assignments by expert and computes only its own experts'
rows through grouped matrix products (``repro.kernels.moe_gmm``), whose
work follows the rows routed here.  No assignment is dropped.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.kernels.moe_gmm.kernel import BLOCK_M
from repro.kernels.moe_gmm.ops import gmm
from repro.models import attention as attn_mod
from repro.models.layers import (
    ParamDef, advance_pos, apply_norm, cast, cross_entropy_loss,
    maybe_checkpoint, maybe_scan, mlp_def, mlp_apply, norm_def, round_up,
    stack_defs)
from repro.models.transformer import DenseLM, _logits, embed_inputs


def moe_ffn_def(cfg: ModelConfig) -> Dict[str, Any]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    held = cfg.held_experts
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(f)
    defs: Dict[str, Any] = {
        "router": ParamDef((d, e), ("embed", "experts"), "normal", s_in),
        "w_gate": ParamDef((held, d, f), ("experts", "embed", "mlp"), "normal", s_in),
        "w_up": ParamDef((held, d, f), ("experts", "embed", "mlp"), "normal", s_in),
        "w_down": ParamDef((held, f, d), ("experts", "mlp", "embed"), "normal", s_out),
    }
    if cfg.moe_dispatch == "dropless":
        defs["router_bias"] = ParamDef((e,), ("experts",), "zeros")
    if cfg.n_shared_experts:
        defs["shared"] = mlp_def(d, cfg.d_ff * cfg.n_shared_experts, "swiglu")
    return defs


def capacity(tokens: int, cfg: ModelConfig) -> int:
    return max(1, int(math.ceil(
        tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)))


def moe_ffn(params, x: jax.Array, cfg: ModelConfig
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, D) -> (B, S, D), aux losses."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    c = capacity(t, cfg)
    xt = x.reshape(t, d)

    logits = (xt.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    gate, expert_idx = jax.lax.top_k(probs, k)  # (T, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)  # renorm

    # position of each assignment within its expert (sort-free ranking)
    flat_e = expert_idx.reshape(-1)  # (T*k,)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # (T*k, E)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                              flat_e[:, None], axis=1)[:, 0]  # (T*k,)
    keep = pos < c
    dest = jnp.where(keep, flat_e * c + pos, e * c)  # E*c = drop slot

    src = jnp.arange(t * k) // k  # token index per assignment
    gathered = jnp.take(xt, src, axis=0)  # (T*k, D)
    buf = jnp.zeros((e * c + 1, d), x.dtype).at[dest].set(gathered)
    buf = buf[:e * c].reshape(e, c, d)
    buf = constrain(buf, ("experts", None, None))

    wg = params["w_gate"].astype(x.dtype)
    wu = params["w_up"].astype(x.dtype)
    wd = params["w_down"].astype(x.dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
    h = h * jnp.einsum("ecd,edf->ecf", buf, wu)
    y_buf = jnp.einsum("ecf,efd->ecd", h, wd)  # (E, C, D)
    y_buf = constrain(y_buf, ("experts", None, None))

    y_flat = jnp.concatenate(
        [y_buf.reshape(e * c, d), jnp.zeros((1, d), x.dtype)], axis=0)
    y_assign = jnp.take(y_flat, dest, axis=0)  # (T*k, D); drops read zeros
    w = (gate.reshape(-1).astype(x.dtype) * keep.astype(x.dtype))
    y = (y_assign * w[:, None]).reshape(t, k, d).sum(axis=1)

    if cfg.n_shared_experts:
        y = y + mlp_apply(params["shared"], xt, "swiglu")

    # aux losses (Switch-style load balance + router z-loss)
    me = probs.mean(axis=0)  # mean router prob per expert
    ce_frac = (onehot.sum(axis=0).astype(jnp.float32) / (t * k))
    aux = {
        "load_balance": e * jnp.sum(me * ce_frac),
        "router_z": jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
        "dropped_frac": 1.0 - keep.astype(jnp.float32).mean(),
    }
    return y.reshape(b, s, d), aux


def moe_ffn_rowlocal(params, x: jax.Array, cfg: ModelConfig
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Row-local (hierarchical GShard-style) dispatch — the §Perf fix.

    The global-cumsum dispatch above ranks (token, expert) assignments over
    the *global* token axis, which under SPMD forces every device to see
    every token (~95 GiB/layer of all-gather on the 256-chip mesh — see
    EXPERIMENTS.md §Perf).  Here ranking + capacity are computed per batch
    row, so all dispatch arithmetic is local to the row's data shard and the
    only cross-device movement is the unavoidable token hop from the
    batch-sharded buffer to the expert-sharded einsum (all-to-all-sized).
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    c = capacity(s, cfg)  # per-row capacity

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_idx = jax.lax.top_k(probs, k)  # (B, S, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    flat_e = expert_idx.reshape(b, s * k)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # (B, S*k, E)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=1) - 1,
                              flat_e[..., None], axis=2)[..., 0]  # (B, S*k)
    keep = pos < c
    dest = jnp.where(keep, flat_e * c + pos, e * c)

    # Dispatch = int32 inverse-slot scatter + batched gather.  Scattering the
    # *vectors* here (first attempt — see EXPERIMENTS.md §Perf, refuted) is
    # not SPMD-partitionable: XLA replicates the updates and masks+all-reduces
    # the sharded output (~180 GiB/layer).  Scattering only the slot->token
    # int32 map moves KBs, and the vector movement becomes a batch-aligned
    # take_along_axis that partitions cleanly.
    rows = jnp.arange(b)[:, None]
    inv = jnp.full((b, e * c + 1), s * k, jnp.int32)
    inv = inv.at[rows, dest].set(
        jnp.broadcast_to(jnp.arange(s * k, dtype=jnp.int32), (b, s * k)))
    inv = inv[:, :e * c]  # (B, E*C): assignment index occupying each slot
    slot_valid = inv < s * k
    tok = jnp.minimum(inv // k, s - 1)  # token index per slot
    buf = jnp.take_along_axis(x, tok[..., None], axis=1)  # (B, E*C, D)
    buf = buf * slot_valid[..., None].astype(x.dtype)
    buf = buf.reshape(b, e, c, d)
    buf = constrain(buf, ("batch", "experts", None, None))

    wg = params["w_gate"].astype(x.dtype)
    wu = params["w_up"].astype(x.dtype)
    wd = params["w_down"].astype(x.dtype)
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf, wg))
    h = h * jnp.einsum("becd,edf->becf", buf, wu)
    y_buf = jnp.einsum("becf,efd->becd", h, wd)
    y_buf = constrain(y_buf, ("batch", "experts", None, None))

    y_flat = jnp.concatenate(
        [y_buf.reshape(b, e * c, d), jnp.zeros((b, 1, d), x.dtype)], axis=1)
    y_assign = jnp.take_along_axis(y_flat, dest[..., None], axis=1)
    w = (gate.reshape(b, s * k).astype(x.dtype) * keep.astype(x.dtype))
    y = (y_assign * w[..., None]).reshape(b, s, k, d).sum(axis=2)

    if cfg.n_shared_experts:
        y = y + mlp_apply(params["shared"], x, "swiglu")

    me = probs.reshape(-1, e).mean(axis=0)
    ce_frac = onehot.sum(axis=(0, 1)).astype(jnp.float32) / (b * s * k)
    aux = {
        "load_balance": e * jnp.sum(me * ce_frac),
        "router_z": jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
        "dropped_frac": 1.0 - keep.astype(jnp.float32).mean(),
    }
    return y, aux


def route(params, xt: jax.Array, cfg: ModelConfig
          ) -> Tuple[jax.Array, jax.Array]:
    """(T, D) tokens -> (weights, expert ids), each (T, top_k): the router
    in float32 at full precision over all ``n_experts``, as DeepSeek-V3's
    noaux_tc with one group: experts chosen by sigmoid score plus
    ``router_bias``, weighted by their sigmoid scores; the bias takes no
    gradient.  The weights are normalised over the k, then scaled by
    ``routed_scaling_factor``."""
    logits = jnp.dot(xt.astype(jnp.float32),
                     params["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(
        scores + params["router_bias"].astype(jnp.float32), cfg.top_k)
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    gate = gate / (gate.sum(-1, keepdims=True) + 1e-20)
    return gate * cfg.routed_scaling_factor, idx


# the held experts' window holds their expected rows times this
WINDOW_HEADROOM = 1.25


def window_rows(tokens: int, top_k: int, held: int, n_experts: int
                ) -> int | None:
    """Rows of the held experts' window of the sorted assignments: their
    expected rows, ``tokens * top_k * held / n_experts``, times
    ``WINDOW_HEADROOM``, rounded up to the kernels' row tile.  None where
    that is no fewer than the most rows the held experts can take,
    ``tokens * min(top_k, held)``: every assignment is then computed."""
    m = round_up(math.ceil(tokens * top_k * held / n_experts
                           * WINDOW_HEADROOM), BLOCK_M)
    return m if m < tokens * min(top_k, held) else None


def _expert_rows(xt, gate, rows, sizes, off, weights, top_k):
    """The held experts' SwiGLU over the assignments ``rows`` (indices into
    the T x top_k assignments, sorted by expert, each expert's rows counted
    by ``sizes``), each row weighted by its gate and added back to its
    token.  An index past the assignments reads zero and adds nothing."""
    wg, wu, wd = weights
    tok = rows // top_k
    xs = jnp.take(xt, tok, axis=0, mode="fill", fill_value=0)
    w = jnp.take(gate, rows, mode="fill", fill_value=0).astype(xt.dtype)
    # each row weighted before the down product, not after it: its
    # backward pass then keeps the (rows, F) products it needs anyway, not
    # the (rows, D) outputs as well
    h = jax.nn.silu(gmm(xs, wg, sizes, off)) * gmm(xs, wu, sizes, off)
    ys = gmm(h * w[:, None], wd, sizes, off)  # zero outside the held rows
    return jnp.zeros_like(xt).at[tok].add(ys, mode="drop")


def _window(statics, xt, gate, weights, order, sizes, c):
    """The held experts' rows ``c * m .. (c + 1) * m``, counted from their
    first in ``order`` (padded past the assignments by ``m``, so that the
    slice is never clamped): the held groups, cut to those rows, start at
    the window's first row, and rows past theirs belong to no group."""
    off, held, top_k, m = statics
    edges = jnp.concatenate([jnp.zeros(1, sizes.dtype),
                             jnp.cumsum(sizes[off:off + held])])
    cut = jnp.diff(jnp.clip(edges - c * m, 0, m))
    held_sizes = jnp.zeros_like(sizes).at[off:off + held].set(cut)
    part = jax.lax.dynamic_slice(order, (sizes[:off].sum() + c * m,), (m,))
    return _expert_rows(xt, gate, part, held_sizes, off, weights, top_k)


def _windows(statics, sizes):
    """Windows the held experts' rows take; at least one."""
    off, held, _, m = statics
    return jnp.maximum((sizes[off:off + held].sum() + m - 1) // m, 1)


# The held rows in as many windows as they need, one at the expected load.
# A vjp of its own, whose backward pass recomputes each window's forward
# as remat does: autodiff of a loop whose length is known only on the
# device is not defined.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_rows(statics, xt, gate, weights, order, sizes):
    def window(c):
        return _window(statics, xt, gate, weights, order, sizes, c)

    return jax.lax.fori_loop(1, _windows(statics, sizes),
                             lambda c, y: y + window(c), window(0))


def _held_rows_fwd(statics, xt, gate, weights, order, sizes):
    return (_held_rows(statics, xt, gate, weights, order, sizes),
            (xt, gate, weights, order, sizes))


def _held_rows_bwd(statics, res, dy):
    xt, gate, weights, order, sizes = res

    def grads(c):
        def window(xt, gate, weights):
            return _window(statics, xt, gate, weights, order, sizes, c)
        return jax.vjp(window, xt, gate, weights)[1](dy)

    def add(c, g):
        return jax.tree_util.tree_map(jnp.add, g, grads(c))

    g = jax.lax.fori_loop(1, _windows(statics, sizes), add, grads(0))
    return (*g, None, None)


_held_rows.defvjp(_held_rows_fwd, _held_rows_bwd)


def moe_ffn_dropless(params, x: jax.Array, cfg: ModelConfig
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The held experts' share of the layer, plus the shared experts.

    The T x top_k assignments are sorted by expert; the held experts'
    SwiGLU runs as grouped products over their rows only, and each row,
    weighted, is added back to its token.  What the experts held elsewhere
    would add is left out: on one chip of an expert-parallel deployment
    that is their chips' part of the exchange.

    The held experts' rows are one run of the sorted order.  Where the
    shapes allow a window shorter than the most rows they can take
    (``window_rows``), that run is gathered, computed and scattered in
    windows of that many rows, as many as the step's rows need (one at
    the expected load), so no assignment is dropped at any load.
    Counters: ``held_rows`` (assignments computed here), ``load_max`` (the
    fullest held expert's rows over the held experts' mean) and ``window``
    (1 where the held rows fit one window)."""
    b, s, d = x.shape
    t, k = b * s, cfg.top_k
    off, held = cfg.expert_offset, cfg.held_experts
    xt = x.reshape(t, d)
    gate, idx = route(params, xt, cfg)

    flat = idx.reshape(-1)
    order = jnp.argsort(flat)
    sizes = jnp.bincount(flat, length=cfg.n_experts).astype(jnp.int32)
    weights = tuple(params[w].astype(x.dtype)
                    for w in ("w_gate", "w_up", "w_down"))
    gate = gate.reshape(-1)
    rows = sizes[off:off + held].astype(jnp.float32)
    m = window_rows(t, k, held, cfg.n_experts)
    if m is None:
        y = _expert_rows(xt, gate, order, sizes, off, weights, k)
        window = jnp.float32(0)
    else:
        padded = jnp.concatenate([order, jnp.full(m, t * k, order.dtype)])
        y = _held_rows((off, held, k, m), xt, gate, weights, padded, sizes)
        window = (rows.sum() <= m).astype(jnp.float32)

    if cfg.n_shared_experts:
        y = y + mlp_apply(params["shared"], xt, "swiglu")
    aux = {"held_rows": rows.sum(),
           "load_max": rows.max() / jnp.maximum(rows.mean(), 1.0),
           "window": window}
    return y.reshape(b, s, d), aux


def apply_moe_ffn(params, x, cfg: ModelConfig):
    if cfg.moe_dispatch == "dropless":
        return moe_ffn_dropless(params, x, cfg)
    if cfg.moe_dispatch == "row_local":
        return moe_ffn_rowlocal(params, x, cfg)
    return moe_ffn(params, x, cfg)


def moe_defs(cfg: ModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    pv = round_up(cfg.vocab_size, 128)
    layer = {
        "ln1": norm_def(d, cfg.norm),
        "attn": attn_mod.attention_def(cfg),
        "ln2": norm_def(d, cfg.norm),
        "moe": moe_ffn_def(cfg),
    }
    defs: Dict[str, Any] = {
        "embed": ParamDef((pv, d), ("vocab", "embed"), "embed", 0.02),
        "layers": stack_defs(cfg.n_layers - cfg.n_dense_layers, layer),
        "final_norm": norm_def(d, cfg.norm),
        "lm_head": ParamDef((d, pv), ("embed", "vocab"), "normal",
                            1.0 / math.sqrt(d)),
    }
    if cfg.n_dense_layers:
        dense = {"ln1": norm_def(d, cfg.norm),
                 "attn": attn_mod.attention_def(cfg),
                 "ln2": norm_def(d, cfg.norm),
                 "mlp": mlp_def(d, cfg.dense_d_ff, cfg.mlp)}
        defs["dense_layers"] = stack_defs(cfg.n_dense_layers, dense)
    return defs


@dataclass
class MoELM(DenseLM):
    """MoE decoder — reuses the dense attention/serving skeleton, swaps the
    FFN for shared+routed experts and adds router aux losses (none for the
    dropless dispatch: its loss is the cross-entropy alone).  Leading
    dense layers (``n_dense_layers``, MLP width ``dense_d_ff``) run first,
    from their own parameter stack ``dense_layers``."""

    def _moe_block(self, collect_kv: bool):
        cfg = self.cfg

        def fn(x, lp, positions):
            h = apply_norm(lp["ln1"], x, cfg.norm, cfg.norm_eps)
            a, kv = attn_mod.full_attention(lp["attn"], h, cfg, positions,
                                            block_kv=self.block_kv)
            x = x + a
            h = apply_norm(lp["ln2"], x, cfg.norm, cfg.norm_eps)
            y, aux = apply_moe_ffn(lp["moe"], h, cfg)
            x = x + y
            x = constrain(x, ("batch", "seq", "embed"))
            if collect_kv:
                return x, (kv, aux)
            return x, aux
        return fn

    def loss(self, params, batch):
        cfg = self.cfg
        params = cast(params, self.dtype)
        x, positions = embed_inputs(params, batch, cfg, self.dtype)
        x = constrain(x, ("batch", "seq", "embed"))
        block = maybe_checkpoint(self._moe_block(collect_kv=False), self.remat)

        if cfg.n_dense_layers:
            dense = maybe_checkpoint(self._block_nokv(), self.remat)
            x, _ = maybe_scan(lambda c, lp: (dense(c, lp, positions), None),
                              x, params["dense_layers"], self.unroll_layers)

        def body(carry, lp):
            return block(carry, lp, positions)

        x, aux = maybe_scan(body, x, params["layers"], self.unroll_layers)
        logits = _logits(params, x, cfg)
        if cfg.frontend == "vision_patches":
            logits = logits[:, batch["patch_embeds"].shape[1]:, :]
        loss, denom = cross_entropy_loss(
            logits, batch["labels"], batch.get("loss_mask"), cfg.vocab_size)
        if cfg.moe_dispatch == "dropless":
            return loss, {"loss": loss, "tokens": denom,
                          "moe_held_rows": aux["held_rows"].sum(),
                          "moe_load_max": aux["load_max"].max(),
                          "moe_window_share": aux["window"].mean()}
        lb = aux["load_balance"].mean()
        rz = aux["router_z"].mean()
        total = loss + cfg.router_aux_coef * lb + cfg.router_z_coef * rz
        return total, {"loss": loss, "tokens": denom, "load_balance": lb,
                       "router_z": rz,
                       "dropped_frac": aux["dropped_frac"].mean()}

    def _require_servable(self):
        attn_mod.require_kv_cache(self.cfg)
        if self.cfg.n_dense_layers:
            raise NotImplementedError(
                f"{self.cfg.name}: serving skips no layer, and the serving "
                "path has no leading dense layers")

    def prefill(self, params, batch, cache_len=None):
        self._require_servable()
        cfg = self.cfg
        params = cast(params, self.dtype)
        x, positions = embed_inputs(params, batch, cfg, self.dtype)
        s = x.shape[1]
        cache_len = cache_len or s
        block = self._moe_block(collect_kv=True)

        def body(carry, lp):
            y, (kv, _aux) = block(carry, lp, positions)
            return y, kv

        x, (ks, vs) = maybe_scan(body, x, params["layers"], self.unroll_layers)
        logits = _logits(params, x[:, -1:, :], cfg)[:, 0]
        pad = cache_len - s
        if pad:
            zeros = jnp.zeros(
                (ks.shape[0], ks.shape[1], pad) + ks.shape[3:], ks.dtype)
            ks = jnp.concatenate([ks, zeros], axis=2)
            vs = jnp.concatenate([vs, zeros], axis=2)
        cache = {"k": ks.astype(self.dtype), "v": vs.astype(self.dtype),
                 "pos": jnp.asarray(s, jnp.int32)}
        return logits, cache

    def decode(self, params, cache, tokens):
        self._require_servable()
        cfg = self.cfg
        params = cast(params, self.dtype)
        pos = cache["pos"]
        active = cache.get("active")
        page_table = cache.get("page_table")
        x, _ = embed_inputs(params, {"tokens": tokens}, cfg, self.dtype,
                            start_pos=pos)

        def body(carry, inp):
            x = carry
            lp, ck, cv = inp
            h = apply_norm(lp["ln1"], x, cfg.norm, cfg.norm_eps)
            a, ck, cv = attn_mod.decode_attention(lp["attn"], h, cfg, ck, cv,
                                                  pos, active=active,
                                                  page_table=page_table)
            x = x + a
            h = apply_norm(lp["ln2"], x, cfg.norm, cfg.norm_eps)
            y, _aux = apply_moe_ffn(lp["moe"], h, cfg)
            x = x + y
            return x, (ck, cv)

        x, (ks, vs) = maybe_scan(
            body, x, (params["layers"], cache["k"], cache["v"]),
            self.unroll_layers)
        logits = _logits(params, x, cfg)[:, 0]
        if page_table is not None:
            cap = page_table.shape[1] * cache["k"].shape[2]
        else:
            cap = cache["k"].shape[2]
        new_pos = advance_pos(pos, tokens.shape[1], active,
                              limit=cap if pos.ndim else None)
        out = {"k": ks, "v": vs, "pos": new_pos}
        if active is not None:
            out["active"] = active
        if page_table is not None:
            out["page_table"] = page_table
        return logits, out
