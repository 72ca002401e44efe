"""Table 1: loss-ratio instability across training recipes.

Arms (scaled-down replicas of the paper's cases):
  baseline @ moderate LR   (paper case 1/7: bsz512)
  baseline @ aggressive LR (paper case 3/9: bsz4K + 4x LR -> spikes)
  baseline @ aggressive LR + tighter grad clip (A.3.2: clipping insufficient)
  SLW @ aggressive LR      (paper case 4/10: spikes -> 0)
  Shortformer @ aggressive LR (case 11: spike at the stage switch)
  Batch-size warmup @ aggressive LR (case 12: no stability benefit)
"""
from __future__ import annotations

from typing import List

from benchmarks.common import (Row, bench_config, run_arm, stability_row)
from repro.configs.base import RegulatorSpec

MODERATE_LR = 6e-3
# Calibrated on this container: fp32 + tiny params + global clip suppress
# spikes until LR ~0.3-0.8; 0.5 is the regime where the paper's phenomenology
# (frequent loss-ratio spikes, SLW suppressing them) reproduces.
AGGRESSIVE_LR = 0.5


def _with_throttle(tc):
    import dataclasses
    return dataclasses.replace(
        tc, regulators=tc.regulators
        + (RegulatorSpec(kind="var_lr_throttle"),))


def run(quick: bool = False) -> List[Row]:
    steps = 80 if quick else 160
    dur = steps // 3
    arms = [
        ("table1/baseline_moderate",
         bench_config(slw=False, lr=MODERATE_LR, steps=steps)),
        ("table1/baseline_aggressive",
         bench_config(slw=False, lr=AGGRESSIVE_LR, steps=steps)),
        ("table1/baseline_aggressive_clip0.25",
         bench_config(slw=False, lr=AGGRESSIVE_LR, steps=steps,
                      grad_clip=0.25)),
        ("table1/slw_aggressive",
         bench_config(slw=True, lr=AGGRESSIVE_LR, steps=steps,
                      duration=steps // 2)),
        ("table1/shortformer_aggressive",
         bench_config(slw=True, lr=AGGRESSIVE_LR, steps=steps, duration=dur,
                      pacing="two_stage")),
        ("table1/bszwarmup_aggressive",
         bench_config(slw=False, lr=AGGRESSIVE_LR, steps=steps,
                      batch_warmup=True)),
        ("table1/slw_variance_gated",
         bench_config(slw=True, lr=AGGRESSIVE_LR, steps=steps, duration=dur,
                      pacing="variance_gated")),
        # beyond-paper: LR throttled by the Adam variance-max precursor
        # instead of (or on top of) the seqlen curriculum
        ("table1/baseline_var_lr_throttle",
         _with_throttle(bench_config(slw=False, lr=AGGRESSIVE_LR,
                                     steps=steps))),
    ]
    rows = []
    for name, tc in arms:
        rows.append(stability_row(*run_arm(name, tc)))
    return rows
