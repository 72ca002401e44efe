"""Model operations of DeepSeek-V3 steps at one chip's share, counted from
shapes.

A multiply-add is two operations.  Counted, per token: the latent
attention's four projections (q_proj, kv_a_proj_with_mqa, kv_b_proj,
o_proj) in every layer; the dense layers' SwiGLU; in every MoE layer the
router, the shared experts' SwiGLU and the held experts' SwiGLU at their
expected load (each token chooses ``top_k`` of the router's
``n_experts``, so a held expert sees rows x seq x top_k / n_experts rows
on average; the rows routed in a given step vary about that); the head
over the vocabulary slice.  And the two attention products over the
causal half of the score matrix (S^2/2 entries per head), scores over
d_qk = 192 and values over d_v = 128.  Not counted: normalisations,
RoPE, softmax, routing's sort and gathers, the embedding lookup, and any
recomputation.  Training is three times the forward pass.

The bound is compute: these are the numbers that model FLOP utilisation
divides by the chip's peak.  ``d`` is ``bench/reference/deepseek_v3.py``'s
``Dims``.
"""
from __future__ import annotations

from typing import List, Tuple

BOUND = "compute"


def attention_params(d) -> int:
    """Weights of one layer's latent attention projections."""
    D, H, r = d.d_model, d.n_heads, d.kv_lora_rank
    return (D * H * d.head_dim + D * (r + d.qk_rope)
            + r * H * (d.qk_nope + d.v_head) + H * d.v_head * D)


def expected_rows(d, rows: int, seq: int) -> float:
    """Rows one held expert sees on average in a step."""
    return rows * seq * d.top_k / d.n_experts


def matmul_params(d) -> float:
    """Weights that take part in a matrix product, per token: the held
    experts at their expected share of a token."""
    swiglu = 3 * d.d_model
    dense = d.n_dense * swiglu * d.d_ff
    routed = d.n_held * d.top_k / d.n_experts * swiglu * d.d_expert
    moe = d.n_moe * (d.d_model * d.n_experts
                     + d.n_shared * swiglu * d.d_expert + routed)
    return (d.n_layers * attention_params(d) + dense + moe
            + d.d_model * d.vocab)


def forward_flops(d, rows: int, seq: int) -> float:
    """Forward pass over ``rows`` sequences of ``seq`` tokens."""
    attn = (d.n_layers * 2.0 * rows * d.n_heads * (seq * seq / 2)
            * (d.head_dim + d.v_head))
    return 2.0 * matmul_params(d) * rows * seq + attn


def train_step_flops(d, rows: int, seq: int) -> float:
    return 3.0 * forward_flops(d, rows, seq)


def attention_calls(d, rows: int, seq: int
                    ) -> List[Tuple[int, int, int, int, int]]:
    """The flash-attention calls of one training step, as (b, h, s, d_qk,
    d_v): one per layer, queries and keys 192 wide, values 128."""
    return [(rows, d.n_heads, seq, d.head_dim, d.v_head)] * d.n_layers


def expert_calls(d, rows: int, seq: int) -> List[Tuple[int, int, int, int]]:
    """The held experts' grouped products of one training step, per MoE
    layer, as (rows, d_model, expert width, held experts): the rows are
    the held experts' expected load, ``expected_rows`` x ``n_held``."""
    routed = int(round(expected_rows(d, rows, seq) * d.n_held))
    return [(routed, d.d_model, d.d_expert, d.n_held)] * d.n_moe
