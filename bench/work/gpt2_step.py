"""Model operations of GPT-2 steps, counted from shapes.

A multiply-add is two operations.  Counted: every matrix product of the
model (the four attention projections, the two MLP matrices and the tied
output head) and the two attention products, over the causal half of the
score matrix (S^2/2 entries per head and sequence).  Not counted:
normalisations, softmax, element-wise work, the embedding lookup, and any
recomputation (a remat that recomputes the forward pass does not raise
the count).  Training is three times the forward pass (the backward pass
makes two products for each one of the forward).

The bound is compute: these are the numbers that model FLOP utilisation
divides by the chip's peak.  ``d`` is ``bench/reference/gpt2.py``'s
``Dims``.
"""
from __future__ import annotations

from typing import List, Tuple

BOUND = "compute"


def matmul_params(d) -> int:
    """Weights that take part in a matrix product, per token."""
    per_layer = 4 * d.d_model * d.d_model + 2 * d.d_model * d.d_ff
    return d.n_layers * per_layer + d.d_model * d.vocab


def forward_flops(d, rows: int, seq: int) -> float:
    """Forward pass over ``rows`` sequences of ``seq`` tokens."""
    dense = 2.0 * matmul_params(d) * rows * seq
    attn = d.n_layers * 2 * 2.0 * rows * (seq * seq / 2) * d.d_model
    return dense + attn


def train_step_flops(d, rows: int, seq: int) -> float:
    return 3.0 * forward_flops(d, rows, seq)


def attention_calls(d, rows: int, seq: int
                    ) -> List[Tuple[int, int, int, int, int]]:
    """The flash-attention calls of one training step, as (b, h, s, d_qk,
    d_v): one per layer, queries, keys and values all of the head's
    width."""
    return [(rows, d.n_heads, seq, d.head_dim, d.head_dim)] * d.n_layers
