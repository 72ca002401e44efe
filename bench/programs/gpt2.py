"""The program's model for a GPT-2 configuration file: its dense family,
with LayerNorm, a tanh-GELU MLP, learned positions and a head tied to the
token embedding, at the file's sizes (``bench/reference/gpt2.py``'s
``Dims``) and as its ``program`` section says to run it."""
from __future__ import annotations

from typing import Any, Dict


def model_config(name: str, d, program: Dict[str, Any]):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=name, family="dense", n_layers=d.n_layers,
        d_model=d.d_model, n_heads=d.n_heads, n_kv_heads=d.n_heads,
        d_ff=d.d_ff, vocab_size=d.vocab, pos_emb="learned",
        norm="layernorm", mlp="gelu", norm_eps=d.eps, tie_embeddings=True,
        max_seq_len=d.n_positions, attn_backend=program["attn_backend"],
        decode_backend=program["decode_backend"])
