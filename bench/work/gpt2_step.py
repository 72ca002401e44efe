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
divides by the chip's peak.
"""
from __future__ import annotations

from bench.reference.gpt2 import Dims

BOUND = "compute"


def matmul_params(d: Dims) -> int:
    """Weights that take part in a matrix product, per token."""
    per_layer = 4 * d.d_model * d.d_model + 2 * d.d_model * d.d_ff
    return d.n_layers * per_layer + d.d_model * d.vocab


def forward_flops(d: Dims, rows: int, seq: int) -> float:
    """Forward pass over ``rows`` sequences of ``seq`` tokens."""
    dense = 2.0 * matmul_params(d) * rows * seq
    attn = d.n_layers * 2 * 2.0 * rows * (seq * seq / 2) * d.d_model
    return dense + attn


def train_step_flops(d: Dims, rows: int, seq: int) -> float:
    return 3.0 * forward_flops(d, rows, seq)

