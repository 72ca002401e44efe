"""Dense decoder-only LM (llama/GPT-style) with scan-over-layers.

Covers: smollm-360m, phi3-mini-3.8b, qwen3-32b (qk_norm), qwen2-1.5b
(qkv_bias), musicgen-large (audio_frames frontend stub, learned pos, GELU),
llava-next-mistral-7b (vision_patches prefix stub), and the paper's GPT-2 /
GPT-3 replicas (learned pos, LayerNorm, GELU).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import attention as attn_mod
from repro.models.layers import (
    ParamDef, advance_pos, apply_norm, cast, cross_entropy_loss,
    maybe_checkpoint, maybe_scan, mlp_def, mlp_apply, norm_def, round_up,
    stack_defs)


def dense_defs(cfg: ModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    pv = round_up(cfg.vocab_size, 128)
    layer = {
        "ln1": norm_def(d, cfg.norm),
        "attn": attn_mod.attention_def(cfg),
        "ln2": norm_def(d, cfg.norm),
        "mlp": mlp_def(d, cfg.d_ff, cfg.mlp),
    }
    defs: Dict[str, Any] = {
        "embed": ParamDef((pv, d), ("vocab", "embed"), "embed", 0.02),
        "layers": stack_defs(cfg.n_layers, layer),
        "final_norm": norm_def(d, cfg.norm),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((d, pv), ("embed", "vocab"), "normal",
                                   1.0 / math.sqrt(d))
    if cfg.pos_emb == "learned":
        defs["pos_embed"] = ParamDef((cfg.max_seq_len, d), ("pos", "embed"),
                                     "embed", 0.02)
    return defs


def embed_inputs(params, batch: Dict[str, jax.Array], cfg: ModelConfig,
                 dtype, start_pos=0) -> Tuple[jax.Array, jax.Array]:
    """Token/frontend embedding. Returns (x, positions).

    ``start_pos`` is a scalar (legacy whole-batch decode) or a per-row (B,)
    vector (continuous batching), yielding positions (S,) or (B, S).
    """
    if cfg.frontend == "audio_frames" and "frames" in batch:
        x = batch["frames"].astype(dtype)  # stubbed EnCodec frame embeddings
    else:
        # cast the table *before* the take: the convert runs shard-local, so
        # the SPMD gather of the rows moves bf16, not f32 (halves that
        # all-gather — see EXPERIMENTS.md §Perf)
        x = jnp.take(params["embed"].astype(dtype), batch["tokens"], axis=0)
        if cfg.frontend == "vision_patches" and "patch_embeds" in batch:
            x = jnp.concatenate([batch["patch_embeds"].astype(dtype), x], axis=1)
    s = x.shape[1]
    start = jnp.asarray(start_pos)
    positions = (start[:, None] + jnp.arange(s) if start.ndim
                 else start + jnp.arange(s))
    if cfg.pos_emb == "learned":
        x = x + jnp.take(params["pos_embed"], positions, axis=0).astype(dtype)
    return x, positions


def _logits(params, x, cfg: ModelConfig):
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(x.dtype))
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(x.dtype))


def _block(cfg: ModelConfig, block_kv: int):
    def fn(x, lp, positions):
        h = apply_norm(lp["ln1"], x, cfg.norm, cfg.norm_eps)
        a, kv = attn_mod.full_attention(lp["attn"], h, cfg, positions,
                                        block_kv=block_kv)
        x = x + a
        h = apply_norm(lp["ln2"], x, cfg.norm, cfg.norm_eps)
        x = x + mlp_apply(lp["mlp"], h, cfg.mlp)
        x = constrain(x, ("batch", "seq", "embed"))
        return x, kv
    return fn


@dataclass
class DenseLM:
    cfg: ModelConfig
    dtype: Any = jnp.bfloat16
    remat: str = "full"
    block_kv: int = 512
    # roofline measurement mode: unroll the layer scan (see layers.maybe_scan)
    unroll_layers: bool = False

    # -- training ----------------------------------------------------------
    def loss(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        params = cast(params, self.dtype)
        x, positions = embed_inputs(params, batch, cfg, self.dtype)
        x = constrain(x, ("batch", "seq", "embed"))
        block = maybe_checkpoint(self._block_nokv(), self.remat)

        def body(carry, lp):
            return block(carry, lp, positions), None

        x, _ = maybe_scan(body, x, params["layers"], self.unroll_layers)
        logits = _logits(params, x, cfg)
        if cfg.frontend == "vision_patches":
            logits = logits[:, batch["patch_embeds"].shape[1]:, :]
        loss, denom = cross_entropy_loss(
            logits, batch["labels"], batch.get("loss_mask"), cfg.vocab_size)
        return loss, {"loss": loss, "tokens": denom}

    def _block_nokv(self):
        inner = _block(self.cfg, self.block_kv)

        def fn(x, lp, positions):
            y, _ = inner(x, lp, positions)
            return y
        return fn

    # -- serving -----------------------------------------------------------
    def prefill(self, params, batch, cache_len: Optional[int] = None):
        """Full forward; returns (last-position logits, KV cache)."""
        cfg = self.cfg
        attn_mod.require_kv_cache(cfg)
        params = cast(params, self.dtype)
        x, positions = embed_inputs(params, batch, cfg, self.dtype)
        s = x.shape[1]
        cache_len = cache_len or s
        block = _block(cfg, self.block_kv)

        def body(carry, lp):
            y, kv = block(carry, lp, positions)
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
        """One decode step: tokens (B, 1) against the cache. Returns
        (logits (B, V), new cache).

        Slot caches may carry two optional leaves the legacy scalar-pos
        cache lacks: ``active`` (per-slot occupancy — inactive slots freeze
        their position and drop cache writes) and ``page_table`` (the KV
        leaves are shared paged pools — see serve/paging.py); both pass
        through unchanged."""
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
            x = x + mlp_apply(lp["mlp"], h, cfg.mlp)
            return x, (ck, cv)

        x, (ks, vs) = maybe_scan(
            body, x, (params["layers"], cache["k"], cache["v"]),
            self.unroll_layers)
        logits = _logits(params, x, cfg)[:, 0]
        if page_table is not None:
            cap = page_table.shape[1] * cache["k"].shape[2]  # pages * page_sz
        else:
            cap = cache["k"].shape[2]  # dense per-slot row length
        new_pos = advance_pos(pos, tokens.shape[1], active,
                              limit=cap if pos.ndim else None)
        out = {"k": ks, "v": vs, "pos": new_pos}
        if active is not None:
            out["active"] = active
        if page_table is not None:
            out["page_table"] = page_table
        return logits, out

    # -- specs ---------------------------------------------------------------
    def cache_shapes(self, batch_size: int, seq_len: int):
        cfg = self.cfg
        kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        kv = jax.ShapeDtypeStruct(
            (cfg.n_layers, batch_size, seq_len, kvh, hd), self.dtype)
        return {"k": kv, "v": kv,
                "pos": jax.ShapeDtypeStruct((), jnp.int32)}

    def cache_axes(self):
        kv = ("layers", "batch", "seq", "kv_heads", "head_dim")
        return {"k": kv, "v": kv, "pos": ()}
