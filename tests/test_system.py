"""System-level behaviour: the paper's recipe end to end (reduced versions;
the full stability comparisons live in benchmarks/).

Whole module is `slow` tier: each test is a real multi-bucket training run
(minutes on the 1-core container).  Run with `pytest -m slow`.
"""
import numpy as np
import pytest

pytestmark = pytest.mark.slow

from repro.configs import get_arch, reduced
from repro.configs.base import (BatchWarmupConfig, OptimizerConfig,
                                RegulatorSpec, SLWConfig, TrainConfig)
from repro.launch.train import train


def _tc(slw: bool, steps=24, lr=2e-3, pacing="linear", batch_warmup=False,
        schedule="token_cosine"):
    cfg = reduced(get_arch("gpt2-117m").model).replace(vocab_size=256)
    seq, batch = 128, 8
    return TrainConfig(
        model=cfg,
        optimizer=OptimizerConfig(
            lr=lr, min_lr=1e-5, schedule=schedule, warmup_steps=6,
            warmup_tokens=6 * batch * seq, total_steps=steps,
            total_tokens=steps * batch * seq),
        slw=SLWConfig(pacing=pacing, start_seq_len=8,
                      duration_steps=steps // 2, round_multiple=8,
                      max_buckets=5),
        batch_warmup=BatchWarmupConfig(
            start_batch=2, warmup_tokens=steps * batch * seq // 4),
        regulators=tuple(RegulatorSpec(k) for k in (
            ("seqlen",) * slw + ("batch_warmup",) * batch_warmup + ("lr",))),
        seq_len=seq, global_batch=batch, remat="none", eval_interval=10)


def test_slw_recipe_end_to_end():
    """Full recipe: pacing + truncation + token-wise LR + token budget."""
    res = train(_tc(slw=True), quiet=True)
    assert res.steps == 24
    # token budget respected: SLW saw fewer tokens than steps*batch*seq
    assert res.tokens < 24 * 8 * 128
    # seqlen ramps to full
    assert res.seqlen_history[0] < res.seqlen_history[-1] == 128
    # validation perplexity is finite and recorded at full length
    assert all(np.isfinite(p) for _, p in res.val_ppl_history)


def test_baseline_and_related_work_arms_run():
    """All four arms of Table 1 execute: baseline, SLW, Shortformer
    (two_stage), batch-size warmup."""
    for kwargs in (dict(slw=False),
                   dict(slw=True),
                   dict(slw=True, pacing="two_stage"),
                   dict(slw=False, batch_warmup=True)):
        res = train(_tc(**kwargs), quiet=True)
        assert res.steps == 24, kwargs
        assert np.isfinite(res.loss_history[-1]) or res.diverged


def test_variance_telemetry_recorded_every_step():
    res = train(_tc(slw=True), quiet=True)
    assert len(res.var_max_history) == res.steps
    assert len(res.var_l1_history) == res.steps
    assert all(v >= 0 for v in res.var_max_history)
    # Adam variance accumulates from zero: max element grows early
    assert res.var_max_history[5] >= res.var_max_history[0]


def test_token_budget_termination():
    """Same 157B-token-style budget semantics: stop on tokens, not steps."""
    import dataclasses
    tc = _tc(slw=True, steps=1000)
    budget = 10 * 8 * 128
    tc = dataclasses.replace(tc, optimizer=OptimizerConfig(
        lr=1e-3, schedule="token_cosine", warmup_tokens=100,
        total_steps=10**6, total_tokens=budget))
    res = train(tc, quiet=True)
    assert res.tokens >= budget
    # SLW needs more steps than a full-length run for the same token budget
    assert res.steps > 10
