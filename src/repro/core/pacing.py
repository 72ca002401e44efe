"""Pacing functions (paper Section 4) + TPU bucket quantization.

The paper's pacing function is step-wise linear:

    seqlen_t = seqlen_s + (seqlen_e - seqlen_s) * min(t / T, 1)

with a post-processing ``seqlen_t -= seqlen_t mod 8`` for V100 tensor cores.
Also implemented: the root variant (paper §4 item ii), the Shortformer
discrete 2-stage schedule (the baseline §5.1 shows diverging at the switch)
and the linear envelope of the beyond-paper variance gate.

TPU adaptation: every distinct sequence length is an XLA recompilation, so
the raw pacing value is quantized onto a bounded *bucket ladder* —
geometric doubling from ``seqlen_s`` up to the rounding multiple, then
arithmetic steps of the multiple, thinned to at most ``max_buckets`` values.
jax.jit's shape-keyed executable cache then holds one compiled step per
bucket.  The paper's eager implementation is the special case
``round_multiple=8, max_buckets=big``.
"""
from __future__ import annotations

import bisect
import math
from typing import List, Sequence, Tuple

from repro.configs.base import SLWConfig


def raw_seqlen(cfg: SLWConfig, step: int, full_len: int,
               warmup_steps_hint: int = 0) -> float:
    """Un-quantized pacing value at `step` (paper formulas)."""
    s0 = cfg.start_seq_len
    s1 = cfg.end_seq_len or full_len
    T = cfg.duration_steps or max(2 * warmup_steps_hint, 1)
    if cfg.pacing in ("linear", "variance_gated"):
        # variance_gated (beyond-paper) is driven by the observed Adam
        # variance max; the seqlen regulator owns the gate and reads only
        # this linear upper envelope here
        return s0 + (s1 - s0) * min(step / T, 1.0)
    if cfg.pacing == "root":
        return s0 + (s1 - s0) * min((step / T) ** (1.0 / cfg.root_degree), 1.0)
    if cfg.pacing == "two_stage":  # Shortformer baseline
        switch = cfg.two_stage_switch_step or T
        return float(cfg.two_stage_short_len if step < switch else s1)
    raise ValueError(f"unknown pacing {cfg.pacing!r}")


def bucket_ladder(s0: int, s1: int, m: int,
                  max_buckets: int) -> Tuple[int, ...]:
    """Monotone ladder of allowed sequence lengths from ``s0`` up to the
    full length ``s1`` in steps of the rounding multiple ``m``,
    |ladder| <= max_buckets."""
    ladder: List[int] = []
    # geometric sub-multiple region
    v = s0
    while v < min(m, s1):
        ladder.append(v)
        v *= 2
    # arithmetic multiples of m
    lo = max(m, s0 - s0 % m or m)
    n_arith = max(1, (s1 - lo) // m + 1)
    budget = max(1, max_buckets - len(ladder))
    stride = max(1, math.ceil(n_arith / budget))
    v = lo
    while v < s1:
        ladder.append(v)
        v += stride * m
    ladder.append(s1)
    # Smallest admissible bucket: s0 itself when s0 is below the rounding
    # multiple, else s0 rounded *down* to the multiple (the arithmetic
    # anchor).  Filtering at s0 would delete that anchor whenever s0 is not
    # a multiple of m, leaving the smallest bucket *above* s0 — early
    # warmup steps would silently run longer than configured.
    floor = s0 if s0 < m else s0 - s0 % m
    ladder = sorted(set(x for x in ladder if floor <= x <= s1 or x == s1))
    return tuple(ladder)


def quantize(raw: float, ladder: Sequence[int]) -> int:
    """Largest ladder value <= raw (paper's round-*down* semantics);
    clamps to the smallest bucket."""
    i = bisect.bisect_right(ladder, raw) - 1
    return ladder[max(i, 0)]


def seqlen_at(cfg: SLWConfig, step: int, full_len: int,
              warmup_steps_hint: int = 0,
              ladder: Sequence[int] = None) -> int:
    if ladder is None:
        ladder = bucket_ladder(cfg.start_seq_len, cfg.end_seq_len or full_len,
                               cfg.round_multiple, cfg.max_buckets)
    return quantize(raw_seqlen(cfg, step, full_len, warmup_steps_hint), ladder)
