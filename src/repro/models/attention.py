"""GQA and latent (MLA) attention: flash/blockwise train-prefill backends
+ cached decode (GQA only).

Two train/prefill backends, selected by ``ModelConfig.attn_backend``:

* ``blockwise`` — jnp online-softmax scan over KV blocks.  Peak memory is
  O(S * block) instead of O(S^2) and it is fully differentiable through
  XLA; it doubles as the pure-jnp oracle for the kernel below.
* ``flash`` — the Pallas flash-attention kernel
  (``repro.kernels.flash_attention``), now differentiable end-to-end via
  ``jax.custom_vjp`` (fused forward emitting logsumexp residuals + three
  backward kernels), so ``jax.value_and_grad`` in the train step runs the
  kernel in both directions.  On non-TPU backends "flash" falls back to
  blockwise; "flash_interpret" forces the kernel in interpret mode (tests).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import on_tpu, resolve_backend
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_decode.ops import flash_decode, flash_decode_paged
from repro.kernels.flash_decode.ref import gather_pages
from repro.models.layers import ParamDef, apply_rope, rms_norm

NEG_INF = -1e30


def attention_def(cfg: ModelConfig) -> Dict[str, ParamDef]:
    if cfg.attn_kind == "mla":
        return mla_def(cfg)
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    s = 1.0 / math.sqrt(d)
    s_o = 1.0 / math.sqrt(h * hd)
    defs = {
        "wq": ParamDef((d, h, hd), ("embed", "heads", "head_dim"), "normal", s),
        "wk": ParamDef((d, kv, hd), ("embed", "kv_heads", "head_dim"), "normal", s),
        "wv": ParamDef((d, kv, hd), ("embed", "kv_heads", "head_dim"), "normal", s),
        "wo": ParamDef((h, hd, d), ("heads", "head_dim", "embed"), "normal", s_o),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((h, hd), ("heads", "head_dim"), "zeros")
        defs["bk"] = ParamDef((kv, hd), ("kv_heads", "head_dim"), "zeros")
        defs["bv"] = ParamDef((kv, hd), ("kv_heads", "head_dim"), "zeros")
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef((hd,), ("head_dim",), "ones")
        defs["k_norm"] = ParamDef((hd,), ("head_dim",), "ones")
    return defs


def mla_def(cfg: ModelConfig) -> Dict[str, ParamDef]:
    """Latent attention (DeepSeek-V2/V3, no q LoRA): ``wq`` is q_proj,
    ``wkv_a`` kv_a_proj_with_mqa (the latent and the shared rotary key),
    ``kv_norm`` the latent's RMSNorm, ``wkv_b`` kv_b_proj (each head's
    non-rotary key and value), ``wo`` o_proj."""
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    s = 1.0 / math.sqrt(d)
    return {
        "wq": ParamDef((d, h, dn + dr), ("embed", "heads", "head_dim"),
                       "normal", s),
        "wkv_a": ParamDef((d, r + dr), ("embed", None), "normal", s),
        "kv_norm": {"scale": ParamDef((r,), (None,), "ones")},
        "wkv_b": ParamDef((r, h, dn + dv), (None, "heads", "head_dim"),
                          "normal", 1.0 / math.sqrt(r)),
        "wo": ParamDef((h, dv, d), ("heads", "head_dim", "embed"), "normal",
                       1.0 / math.sqrt(h * dv)),
    }


def _rope_pairs(x: jax.Array, positions: jax.Array, theta: float
                ) -> jax.Array:
    """DeepSeek's rotary convention: the dims are pairs (2i, 2i + 1),
    de-interleaved to (evens, odds) and then rotated half against half."""
    d = x.shape[-1]
    x = x.reshape(x.shape[:-1] + (d // 2, 2)).swapaxes(-1, -2)
    return apply_rope(x.reshape(x.shape[:-2] + (d,)), positions, theta)


def mla_attention(params, x: jax.Array, cfg: ModelConfig,
                  positions: jax.Array, block_kv: int) -> jax.Array:
    """Causal latent attention for training: queries and keys are
    qk_nope_head_dim + qk_rope_head_dim wide, the rotary part of the key
    one head shared by all, values v_head_dim wide; the context runs
    through ``_context`` (flash with d_qk != d_v), scaled by 1/sqrt(d_qk)."""
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    kv_a = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"].astype(x.dtype))
    latent = rms_norm(kv_a[..., :r], params["kv_norm"]["scale"],
                      cfg.norm_eps)
    kv = jnp.einsum("bsr,rhk->bshk", latent, params["wkv_b"].astype(x.dtype))
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_pe = _rope_pairs(q[..., dn:], positions, cfg.rope_theta)
    k_pe = _rope_pairs(kv_a[..., None, r:], positions, cfg.rope_theta)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:-1] + k_pe.shape[-1:])],
        axis=-1)
    ctx = _context(q, k, v, cfg, block_kv)
    return jnp.einsum("bshk,hkd->bsd", ctx, params["wo"].astype(x.dtype))


def require_kv_cache(cfg: ModelConfig) -> None:
    """Serving reads and writes a KV cache; latent attention has none."""
    if cfg.attn_kind == "mla":
        raise NotImplementedError(
            f"{cfg.name}: latent attention (attn_kind='mla') is trained "
            "only; serving it (prefill, decode) needs a latent KV cache, "
            "which the program does not have")


def _project_qkv(params, x, cfg: ModelConfig, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    if cfg.pos_emb == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True, block_kv: int = 512,
                        q_offset: int = 0) -> jax.Array:
    """Online-softmax attention scanning over KV blocks.

    q: (B, Sq, H, D); k: (B, Sk, KV, D); v: (B, Sk, KV, D_v) with
    H = KV * G.  Returns (B, Sq, H, D_v).
    Memory high-water is O(Sq * block_kv) per head instead of O(Sq * Sk).
    """
    b, sq, h, d = q.shape
    sk, kvh, d_v = k.shape[1], k.shape[2], v.shape[-1]
    g = h // kvh
    scale = 1.0 / math.sqrt(d)
    # odd bucket/remainder lengths (e.g. sk=544 against the default 512)
    # used to trip the divisibility assert.  Prefer the largest divisor of
    # sk within (block_kv/2, block_kv] — an exact scan with bounded waste;
    # when none exists (e.g. prime sk) pad the tail and mask the dead keys
    # rather than degenerating toward block_kv=1 (trace-time, sk is static)
    block_kv = min(block_kv, sk)
    block_kv = next((c for c in range(block_kv, block_kv // 2, -1)
                     if sk % c == 0), block_kv)
    pad = (-sk) % block_kv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_blocks = (sk + pad) // block_kv

    qg = q.reshape(b, sq, kvh, g, d) * scale
    kb = k.reshape(b, n_blocks, block_kv, kvh, d)
    vb = v.reshape(b, n_blocks, block_kv, kvh, d_v)
    q_pos = q_offset + jnp.arange(sq)

    def step(carry, inp):
        m, l, acc = carry
        kc, vc, blk = inp  # (B, blk, KV, D), (B, blk, KV, D), ()
        s = jnp.einsum("bqkgd,bjkd->bkgqj", qg, kc).astype(jnp.float32)
        k_pos = blk * block_kv + jnp.arange(block_kv)
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]  # (Sq, blk)
            if pad:
                mask &= (k_pos < sk)[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        elif pad:
            s = jnp.where((k_pos < sk)[None, None, None, None, :], s,
                          NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqj,bjkd->bkgqd", p.astype(vc.dtype), vc).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, kvh, g, sq, d_v), jnp.float32)
    kb_t = jnp.moveaxis(kb, 1, 0)  # (n_blocks, B, blk, KV, D)
    vb_t = jnp.moveaxis(vb, 1, 0)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0), (kb_t, vb_t, jnp.arange(n_blocks)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d_v)
    return out.astype(q.dtype)


def _context(q: jax.Array, k: jax.Array, v: jax.Array, cfg: ModelConfig,
             block_kv: int) -> jax.Array:
    """Backend dispatch for the train/prefill context computation.

    ``cfg.attn_backend`` selects between the jnp blockwise scan (the oracle)
    and the differentiable Pallas flash-attention kernel.  "flash" uses the
    compiled kernel only on TPU and falls back to blockwise elsewhere, so
    full-scale presets remain lowerable/compilable on any backend (e.g. the
    CPU dry-run); "flash_interpret" forces the kernel in interpret mode —
    the CPU validation path the gradient tests and the flash train-step
    smoke test run.
    """
    backend = cfg.attn_backend
    if backend == "flash" and on_tpu():
        return flash_attention(q, k, v, causal=True)
    if backend == "flash_interpret":
        return flash_attention(q, k, v, causal=True, interpret=True)
    if backend not in ("blockwise", "flash"):
        raise ValueError(f"unknown attn_backend {backend!r}")
    return blockwise_attention(q, k, v, causal=True, block_kv=block_kv)


def full_attention(params: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig,
                   positions: jax.Array, block_kv: int = 512
                   ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Causal self-attention over the whole sequence. Returns (out, (k, v));
    latent attention returns (out, None).

    Routes through ``cfg.attn_backend`` (see ``_context``): the training
    step (``jax.value_and_grad`` in launch/steps.py) and the serve prefill
    both reach the Pallas kernel — forward *and* backward — when "flash" is
    selected on TPU.
    """
    if cfg.attn_kind == "mla":  # no KV cache (see ``require_kv_cache``)
        return mla_attention(params, x, cfg, positions, block_kv), None
    q, k, v = _project_qkv(params, x, cfg, positions)
    ctx = _context(q, k, v, cfg, block_kv)
    out = jnp.einsum("bshk,hkd->bsd", ctx, params["wo"].astype(x.dtype))
    return out, (k, v)


def decode_attention(params: Dict[str, jax.Array], x: jax.Array,
                     cfg: ModelConfig, cache_k: jax.Array, cache_v: jax.Array,
                     pos: jax.Array, active: Optional[jax.Array] = None,
                     page_table: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode against a KV cache at position `pos`.

    ``pos`` is a scalar (whole batch at one position — the legacy static
    path) or a per-row (B,) vector (continuous batching: every slot decodes
    at its own depth).  Returns (out, new_cache_k, new_cache_v).

    Cache layouts:

    * dense (``page_table=None``): ``cache_k``/``cache_v`` are
      ``(B, S_max, KV, D)`` per-slot rows.
    * paged (``page_table`` = ``(B, max_pages)`` int32, ``-1`` = unowned):
      the caches are shared ``(n_pages, page_size, KV, D)`` pools and row
      ``b``'s token ``j`` lives at ``page_table[b, j // page_size]``,
      offset ``j % page_size`` (see serve/paging.py).  Paged decode is
      per-slot single-token only (the fused engine step).

    ``active`` is the per-slot (B,) occupancy mask when given: writes for
    inactive rows are dropped, so a free/evicted slot's cache never drifts
    between an evict and the next insert.  Writes past the cache capacity
    are likewise dropped (scatter ``mode="drop"`` on an out-of-bounds
    sentinel index), not silently clamped onto the last row — under a page
    table a clamped runaway position would corrupt another slot's page.

    Mask convention — **count of valid entries**: after this step's k/v
    write, a row decoding at position ``p`` has ``p + 1`` valid cache
    entries (indices ``0..p`` inclusive of the token just written) and
    cache row ``j`` attends iff ``j < p + 1``.  This is the same convention
    ``distributed.collectives.flash_decode_sharded`` and the flash-decode
    kernel use (``lengths`` = counts), pinned by the parity tests in
    tests/test_flash_decode.py.

    ``cfg.decode_backend`` selects the context computation: "reference"
    (jnp masked softmax over the full cache — the oracle; paged caches are
    gathered through the page table first), "kernel" (the Pallas split-KV
    flash-decode kernel on TPU — the page-table-walking variant for paged
    caches — reference elsewhere) or "kernel_interpret" (kernel in
    interpret mode — CPU validation).  The kernel serves the single-token
    step; multi-token calls stay on the reference path.
    """
    require_kv_cache(cfg)
    b, s_q, h, = x.shape[0], x.shape[1], cfg.n_heads
    pos = jnp.asarray(pos)
    per_slot = pos.ndim == 1
    if per_slot:
        positions = pos[:, None] + jnp.arange(s_q)[None, :]  # (B, s_q)
    else:
        positions = pos + jnp.arange(s_q)[None, :]  # (1, s_q) broadcast
    q, k, v = _project_qkv(params, x, cfg, positions)

    if page_table is not None:
        if not per_slot or s_q != 1:
            raise ValueError("paged decode is per-slot single-token only "
                             f"(got pos ndim {pos.ndim}, s_q {s_q})")
        n_pages, page_size = cache_k.shape[0], cache_k.shape[1]
        max_pages = page_table.shape[1]
        pid = page_table[jnp.arange(b),
                         jnp.minimum(pos // page_size, max_pages - 1)]
        ok = (pid >= 0) & (pos // page_size < max_pages)
        if active is not None:
            ok = ok & active
        # flatten the pool and scatter at page*page_size + offset; rows
        # that may not write (inactive, unowned page, past capacity) get
        # the one-past-the-end sentinel and are dropped
        flat_idx = jnp.where(ok, pid * page_size + pos % page_size,
                             n_pages * page_size)

        def _pool_write(c, upd):
            fc = c.reshape((n_pages * page_size,) + c.shape[2:])
            fc = fc.at[flat_idx].set(upd.astype(c.dtype), mode="drop")
            return fc.reshape(c.shape)
        cache_k = _pool_write(cache_k, k[:, 0])
        cache_v = _pool_write(cache_v, v[:, 0])
    elif per_slot:
        s_max = cache_k.shape[1]
        if s_q == 1:
            ok = pos < s_max
            if active is not None:
                ok = ok & active
            idx = jnp.where(ok, pos, s_max)  # OOB sentinel -> dropped
            rows = jnp.arange(b)
            cache_k = cache_k.at[rows, idx].set(
                k[:, 0].astype(cache_k.dtype), mode="drop")
            cache_v = cache_v.at[rows, idx].set(
                v[:, 0].astype(cache_v.dtype), mode="drop")
        else:
            # multi-token per-slot replay: row b writes its s_q tokens at
            # pos[b]..pos[b]+s_q-1 (vmapped dynamic_update_slice lowers to
            # a scatter; callers keep pos + s_q <= s_max)
            def _row_write(c, upd, p):
                return jax.lax.dynamic_update_slice_in_dim(c, upd, p, axis=0)
            cache_k = jax.vmap(_row_write)(cache_k, k.astype(cache_k.dtype),
                                           pos)
            cache_v = jax.vmap(_row_write)(cache_v, v.astype(cache_v.dtype),
                                           pos)
    else:
        cache_k = jax.lax.dynamic_update_slice_in_dim(
            cache_k, k.astype(cache_k.dtype), pos, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(
            cache_v, v.astype(cache_v.dtype), pos, axis=1)

    kvh = cfg.n_kv_heads
    g = h // kvh
    d = cfg.resolved_head_dim
    use_kernel, interpret = resolve_backend(cfg.decode_backend,
                                            "decode_backend")
    if use_kernel and s_q == 1:
        # counts of valid entries per row (the token just written included)
        lengths = (pos + 1 if per_slot
                   else jnp.broadcast_to(pos + 1, (b,))).astype(jnp.int32)
        if page_table is not None:
            ctx = flash_decode_paged(q[:, 0], cache_k, cache_v, page_table,
                                     lengths, interpret=interpret)[:, None]
        else:
            ctx = flash_decode(q[:, 0], cache_k, cache_v, lengths,
                               interpret=interpret)[:, None]
    else:
        if page_table is not None:
            kc = gather_pages(cache_k, page_table)
            vc = gather_pages(cache_v, page_table)
        else:
            kc, vc = cache_k, cache_v
        s_max = kc.shape[1]
        scale = 1.0 / math.sqrt(d)
        qg = q.reshape(b, s_q, kvh, g, d) * scale
        s = jnp.einsum("bqkgd,bjkd->bkgqj", qg, kc).astype(jnp.float32)
        counts = positions + 1  # (B, s_q) or (1, s_q): valid-entry counts
        if per_slot:
            valid = jnp.arange(s_max)[None, None, :] < counts[:, :, None]
            s = jnp.where(valid[:, None, None], s, NEG_INF)
        else:
            valid = jnp.arange(s_max)[None, :] < counts[0][:, None]
            s = jnp.where(valid[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("bkgqj,bjkd->bkgqd", p.astype(vc.dtype), vc)
        ctx = ctx.transpose(0, 3, 1, 2, 4).reshape(b, s_q, h, d)
    out = jnp.einsum("bshk,hkd->bsd", ctx, params["wo"].astype(x.dtype))
    return out, cache_k, cache_v
