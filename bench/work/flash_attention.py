"""Required work of causal flash attention, from its shapes.

Taken from (B, H, S, D_qk, D_v) and the itemsize of the operands, never
from the kernel's grid or padding, so it reads the same whatever
implements it.  Queries and keys have D_qk per head, values and the
output D_v (the same for GPT-2; latent attention makes them differ).
Operations: the causal half of the score matrix, S^2/2 entries per head;
the forward pass makes two products over it (scores over D_qk, then
probabilities times values over D_v) and the backward pass four (scores
again is not counted: dV and dP over D_v, dQ and dK over D_qk), with no
recomputation.  Bytes: each operand read once and each result written
once (forward: q, k, v in, o and the per-row log-sum-exp out; backward:
q, k, v, o, dO and the log-sum-exp in, dQ, dK, dV out).

Which bound applies depends on S and the operands' itemsize: the forward
makes S/(2 * itemsize) operations per byte.  With float32 operands at
S <= 1024 that is at most 128, under the v5e's 197e12 / 819e9 = 240, so
the timed cells are bound by bandwidth; bfloat16 at long S is bound by
compute.  The least time of a call is the larger of the two bounds.
"""
from __future__ import annotations

from collections import Counter
from typing import Dict, Iterable, Tuple

BOUND = "bandwidth (float32, S <= 1024); compute at long S"
# in the device trace the kernels are custom calls named after the jitted
# function: the forward, delta, dQ and dK/dV calls all carry this
KERNELS = ("flash_attention",)


def flops(b: int, h: int, s: int, d_qk: int, d_v: int) -> Dict[str, float]:
    half_qk = b * h * (s * s / 2.0) * d_qk
    half_v = b * h * (s * s / 2.0) * d_v
    return {"fwd": 2.0 * half_qk + 2.0 * half_v,
            "bwd": 2 * 2.0 * half_qk + 2 * 2.0 * half_v}


def bytes_moved(b: int, h: int, s: int, d_qk: int, d_v: int, itemsize: int
                ) -> Dict[str, float]:
    tile_qk = b * h * s * d_qk * itemsize
    tile_v = b * h * s * d_v * itemsize
    rows = b * h * s * 4  # float32 log-sum-exp / delta per row
    return {"fwd": 2 * tile_qk + 2 * tile_v + rows,
            "bwd": 4 * tile_qk + 4 * tile_v + 2 * rows}


def least_seconds(b: int, h: int, s: int, d_qk: int, d_v: int,
                  itemsize: int, peaks: Dict[str, float],
                  backward: bool = True) -> float:
    """The least time one forward (and, with ``backward``, one backward)
    call could take on the chip."""
    f = flops(b, h, s, d_qk, d_v)
    m = bytes_moved(b, h, s, d_qk, d_v, itemsize)
    parts = ("fwd", "bwd") if backward else ("fwd",)
    return sum(max(f[p] / peaks["bf16_flops_per_s"],
                   m[p] / peaks["hbm_bytes_per_s"]) for p in parts)


def step_least_seconds(calls: Iterable[Tuple[int, int, int, int, int]],
                       itemsize: int, peaks: Dict[str, float],
                       part: str) -> float:
    """The least time of one step's calls (``attention_calls`` of the
    architecture's step work): ``part`` is ``"fwd"``, ``"bwd"`` or
    ``"both"``."""
    total = 0.0
    for (b, h, s, d_qk, d_v), n in Counter(calls).items():
        both = least_seconds(b, h, s, d_qk, d_v, itemsize, peaks)
        fwd = least_seconds(b, h, s, d_qk, d_v, itemsize, peaks,
                            backward=False)
        total += n * {"both": both, "fwd": fwd, "bwd": both - fwd}[part]
    return total
