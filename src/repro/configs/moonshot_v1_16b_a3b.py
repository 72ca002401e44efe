"""moonshot-v1-16b-a3b (kimi/moonlight) — fine-grained MoE, 64 experts top-6.

The repository's assignment block, not the published config: 48L
d_model=2048 16H (GQA kv=16) d_ff=1408 (per expert) vocab=163840, MoE 64e
top-6 (+2 shared experts), softmax top-k with capacity drops, no leading
dense layer.  The published Moonlight-16B-A3B (hf:moonshotai/Moonlight-16B-A3B
config.json, ``model_type`` deepseek_v3) has 27 layers, latent attention
(MLA), one dense first layer of width 11264 and sigmoid routing with a
correction bias; that block, cut to one chip's share, is
``bench/configs/moonlight-16b-a3b-l5.json`` (``bench/programs/deepseek_v3.py``
builds its ``ModelConfig``).
"""
from repro.configs.base import ArchSpec, ModelConfig

MODEL = ModelConfig(
    name="moonshot-v1-16b-a3b",
    family="moe",
    n_layers=48,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,
    vocab_size=163840,
    n_experts=64,
    n_shared_experts=2,
    top_k=6,
)

SPEC = ArchSpec(
    model=MODEL,
    source="assignment block after hf:moonshotai/Moonlight-16B-A3B (not its "
           "config.json; see bench/configs/moonlight-16b-a3b-l5.json)",
    notes="EP: experts sharded over the model axis; long_500k skipped: full attention",
)
