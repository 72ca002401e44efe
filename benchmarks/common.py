"""Shared harness for the paper-replication benchmarks.

Scale note: this container is one CPU core, so the paper's experiments are
replicated on small same-family GPT-2 configs over the synthetic corpus.
The *mechanisms* under test (instability at aggressive LR/long sequences,
SLW stabilization, variance telemetry, tuning heuristic, token-wise decay)
are scale-free; the headline full-scale numbers are additionally derived
analytically from the compiled dry-run cost model in bench_table2_pareto.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.configs import get_arch, reduced
from repro.configs.base import (BatchWarmupConfig, ModelConfig,
                                OptimizerConfig, RegulatorSpec, SLWConfig,
                                TrainConfig)
from repro.launch.train import TrainResult, train

Row = Tuple[str, float, str]  # (name, us_per_call, derived)

# the benchmark model: a deeper-than-smoke GPT-2 replica that actually shows
# training dynamics on CPU in ~seconds (sized for the 1-core container)
BENCH_MODEL = ModelConfig(
    name="gpt2-bench", family="dense", n_layers=3, d_model=96, n_heads=4,
    n_kv_heads=4, d_ff=384, vocab_size=512, pos_emb="learned",
    norm="layernorm", mlp="gelu", tie_embeddings=True, max_seq_len=512)

SEQ = 192
BATCH = 8


def bench_config(slw: bool = False, lr: float = 1e-3, steps: int = 150,
                 pacing: str = "linear", duration: Optional[int] = None,
                 start_seq: int = 8, batch_warmup: bool = False,
                 schedule: str = "token_cosine", warmup_steps: int = 15,
                 seq: int = SEQ, batch: int = BATCH, grad_clip: float = 1.0,
                 mode: str = "truncate", seed: int = 1234,
                 total_tokens: int = 0,
                 regulators: Optional[Tuple[RegulatorSpec, ...]] = None
                 ) -> TrainConfig:
    """One bench arm.  `slw` and `batch_warmup` turn on their regulators
    and *compose* (the paper's joint recipe is both at once), before the LR
    schedule; `regulators` gives the whole stack instead (e.g. to add the
    adaptive beyond-paper regulators)."""
    if regulators is None:
        kinds = ("seqlen",) * slw + ("batch_warmup",) * batch_warmup + ("lr",)
        regulators = tuple(RegulatorSpec(k) for k in kinds)
    return TrainConfig(
        model=BENCH_MODEL,
        optimizer=OptimizerConfig(
            lr=lr, min_lr=lr / 30, schedule=schedule,
            warmup_steps=warmup_steps,
            warmup_tokens=warmup_steps * batch * seq,
            total_steps=steps,
            total_tokens=total_tokens or steps * batch * seq,
            grad_clip=grad_clip),
        slw=SLWConfig(pacing=pacing, start_seq_len=start_seq,
                      duration_steps=duration or steps // 3,
                      round_multiple=8, max_buckets=12, mode=mode),
        batch_warmup=BatchWarmupConfig(
            start_batch=max(batch // 4, 1),
            warmup_tokens=(duration or steps // 3) * batch * seq // 2),
        regulators=regulators,
        seq_len=seq, global_batch=batch, seed=seed, remat="none",
        eval_interval=10)


def run_arm(name: str, tc: TrainConfig, **kw) -> Tuple[str, TrainResult, float]:
    t0 = time.time()
    res = train(tc, quiet=True, stop_on_nan=False, **kw)
    return name, res, time.time() - t0


def stability_row(name: str, res: TrainResult, wall: float) -> Row:
    s = res.tracker_summary
    derived = (f"spikes={s['spikes']}({100 * s['spike_frac']:.2f}%) "
               f"max_ratio={s['max_loss_ratio']:.2f} "
               f"diverged={res.diverged} "
               f"final_loss={res.loss_history[-1]:.3f}")
    us = wall / max(res.steps, 1) * 1e6
    return (name, us, derived)


def final_ppl(res: TrainResult) -> float:
    if res.val_ppl_history:
        return res.val_ppl_history[-1][1]
    return float("nan")
