"""Required work of causal flash attention, from its shapes.

Taken from (B, H, S, D) and the itemsize of the operands, never from the
kernel's grid or padding, so it reads the same whatever implements it.
Operations: the causal half of the score matrix, S^2/2 entries per head;
the forward pass makes two products over it (scores, then probabilities
times values) and the backward pass four (scores again is not counted:
dV, dP, dQ, dK), with no recomputation.  Bytes: each operand read once and
each result written once (forward: q, k, v in, o and the per-row
log-sum-exp out; backward: q, k, v, o, dO and the log-sum-exp in, dQ, dK,
dV out).

Which bound applies depends on S and the operands' itemsize: the forward
makes S/(2 * itemsize) operations per byte.  With float32 operands at
S <= 1024 that is at most 128, under the v5e's 197e12 / 819e9 = 240, so
the timed cells are bound by bandwidth; bfloat16 at long S is bound by
compute.  The least time of a call is the larger of the two bounds.
"""
from __future__ import annotations

from typing import Dict

BOUND = "bandwidth (float32, S <= 1024); compute at long S"
# in the device trace the kernels are custom calls named after the jitted
# function: the forward, delta, dQ and dK/dV calls all carry this
KERNELS = ("flash_attention",)


def flops(b: int, h: int, s: int, d: int) -> Dict[str, float]:
    half = b * h * (s * s / 2.0) * d
    return {"fwd": 2 * 2.0 * half, "bwd": 4 * 2.0 * half}


def bytes_moved(b: int, h: int, s: int, d: int, itemsize: int
                ) -> Dict[str, float]:
    tile = b * h * s * d * itemsize
    rows = b * h * s * 4  # float32 log-sum-exp / delta per row
    return {"fwd": 4 * tile + rows, "bwd": 8 * tile + 2 * rows}


def least_seconds(b: int, h: int, s: int, d: int, itemsize: int,
                  peaks: Dict[str, float], backward: bool = True) -> float:
    """The least time one forward (and, with ``backward``, one backward)
    call could take on the chip."""
    f, m = flops(b, h, s, d), bytes_moved(b, h, s, d, itemsize)
    parts = ("fwd", "bwd") if backward else ("fwd",)
    return sum(max(f[p] / peaks["bf16_flops_per_s"],
                   m[p] / peaks["hbm_bytes_per_s"]) for p in parts)
