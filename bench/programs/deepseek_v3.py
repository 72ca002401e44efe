"""The program's model for a DeepSeek-V3 configuration file: its MoE
family with latent attention (MLA), leading dense SwiGLU layers, RMSNorm,
DeepSeek's RoPE and an untied head, and the dropless expert-share layer
holding the file's experts of the router's published count
(``bench/reference/deepseek_v3.py``'s ``Dims``), run as the file's
``program`` section says."""
from __future__ import annotations

from typing import Any, Dict


def model_config(name: str, d, program: Dict[str, Any]):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=name, family="moe", n_layers=d.n_layers, d_model=d.d_model,
        n_heads=d.n_heads, n_kv_heads=d.n_heads, d_ff=d.d_expert,
        vocab_size=d.vocab, head_dim=d.head_dim, attn_kind="mla",
        kv_lora_rank=d.kv_lora_rank, qk_nope_head_dim=d.qk_nope,
        qk_rope_head_dim=d.qk_rope, v_head_dim=d.v_head,
        rope_theta=d.rope_theta, pos_emb="rope", norm="rmsnorm",
        mlp="swiglu", norm_eps=d.eps, tie_embeddings=False,
        n_experts=d.n_experts, n_held_experts=d.n_held,
        expert_offset=d.expert_offset, n_shared_experts=d.n_shared,
        top_k=d.top_k, moe_dispatch=program["moe_dispatch"],
        routed_scaling_factor=d.routed_scaling, n_dense_layers=d.n_dense,
        dense_d_ff=d.d_ff, max_seq_len=d.n_positions,
        attn_backend=program["attn_backend"])
