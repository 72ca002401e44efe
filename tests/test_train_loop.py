"""End-to-end training loop: learning, SLW mechanics, the composed
regulator recipe, fault tolerance."""
import dataclasses
import math
import os

import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.configs.base import (BatchWarmupConfig, OptimizerConfig,
                                RegulatorSpec, SLWConfig, TrainConfig)
from repro.core import pacing
from repro.core.regulators import StepTelemetry
from repro.distributed.fault_tolerance import (DrainSignal, StepWatchdog,
                                               TrainSupervisor)
from repro.launch.train import Trainer, train
from repro.optim import lr_at


def _tc(steps=40, slw=True, lr=2e-3, seq=128, batch=8, ckpt_dir="",
        pacing="linear", mode="truncate", vocab=256, buckets=5):
    cfg = reduced(get_arch("gpt2-117m").model).replace(vocab_size=vocab)
    return TrainConfig(
        model=cfg,
        optimizer=OptimizerConfig(
            lr=lr, min_lr=1e-5, schedule="token_cosine",
            warmup_steps=8, warmup_tokens=8 * batch * seq,
            total_steps=steps, total_tokens=steps * batch * seq),
        slw=SLWConfig(pacing=pacing, start_seq_len=8,
                      duration_steps=steps // 2, round_multiple=8,
                      max_buckets=buckets, mode=mode),
        regulators=tuple(RegulatorSpec(k)
                         for k in (("seqlen", "lr") if slw else ("lr",))),
        seq_len=seq, global_batch=batch, remat="none",
        eval_interval=0, checkpoint_interval=10, checkpoint_dir=ckpt_dir)


def test_loss_decreases_and_buckets_bounded():
    res = train(_tc(steps=24, buckets=4), quiet=True)
    assert res.steps == 24
    assert not res.diverged
    first = np.mean(res.loss_history[:5])
    last = np.mean(res.loss_history[-5:])
    assert last < first  # learning
    assert res.n_compiles <= 4 + 1  # bounded by the bucket ladder
    # seqlen schedule is monotone and reaches full length
    assert res.seqlen_history[-1] == 128
    assert res.seqlen_history[0] <= 16


@pytest.mark.slow
def test_token_accounting_truncate_vs_repack():
    r_trunc = train(_tc(steps=20, mode="truncate"), quiet=True)
    r_pack = train(_tc(steps=20, mode="repack"), quiet=True)
    assert r_pack.tokens > r_trunc.tokens  # repack drops nothing


@pytest.mark.slow
def test_checkpoint_resume_exact(tmp_path):
    d = str(tmp_path / "ck")
    tc = _tc(steps=30, ckpt_dir=d)
    full = train(tc, quiet=True)
    # restart from step-20 checkpoint and finish
    tc2 = _tc(steps=30, ckpt_dir=d)
    part = train(tc2, resume=True, quiet=True)
    assert part.restored_from_step == 30  # the final checkpoint
    assert part.steps == 30  # nothing left to do


@pytest.mark.slow
def test_resume_mid_run_continues_schedule(tmp_path):
    d = str(tmp_path / "ck")
    tc = _tc(steps=18, ckpt_dir=d)
    r1 = train(tc, quiet=True)  # checkpoints at 10 and at end (18)
    tc_more = _tc(steps=36, ckpt_dir=d)
    r2 = train(tc_more, resume=True, quiet=True)
    assert r2.restored_from_step == 18
    assert r2.steps == 36
    # curriculum resumed, not restarted: first seqlen after resume >= before
    assert r2.seqlen_history[0] >= r1.seqlen_history[-1]


@pytest.mark.slow
def test_supervisor_recovers_from_injected_failure(tmp_path):
    d = str(tmp_path / "ck")
    sup = TrainSupervisor(max_restarts=2)

    def run(resume: bool) -> str:
        res = train(_tc(steps=25, ckpt_dir=d), resume=resume,
                    fail_at_step=None if resume else 15, quiet=True)
        return f"ok:{res.steps}"

    out = sup.run(run)
    assert out == "ok:25"
    assert sup.restarts == 1


def _composed_tc(steps, ckpt_dir="", seq=128, batch=8):
    """SLW + batch warmup + token-wise LR warmup, all at once — the paper's
    joint recipe, expressible since the regulator control plane."""
    tc = _tc(steps=steps, seq=seq, batch=batch, ckpt_dir=ckpt_dir)
    # schedule constants must not depend on `steps`, so the 8-step and
    # 16-step configs describe the *same* trajectory
    return dataclasses.replace(
        tc,
        slw=dataclasses.replace(tc.slw, duration_steps=12),
        batch_warmup=BatchWarmupConfig(start_batch=2, warmup_tokens=1000),
        regulators=(RegulatorSpec("seqlen"), RegulatorSpec("batch_warmup"),
                    RegulatorSpec("lr")),
        checkpoint_interval=8)


def _predict_composed(tc, n_steps, dp_size=1):
    """Per-step (seqlen, batch, lr) from each schedule's formula computed
    standalone (the pacing functions, GPT-3's linear batch ramp rounded
    down to the dp size, the LR schedule; not the stack) — the oracle the
    composed run must match."""
    bw, full = tc.batch_warmup, tc.global_batch
    tokens, rows = 0, []
    for i in range(n_steps):
        s = pacing.seqlen_at(tc.slw, i, tc.seq_len,
                             tc.optimizer.warmup_steps)
        raw = int(bw.start_batch + min(tokens / bw.warmup_tokens, 1.0)
                  * (full - bw.start_batch))
        b = min(max(raw - raw % dp_size, bw.start_batch, dp_size), full)
        rows.append((s, b, lr_at(tc.optimizer, i, tokens)))
        tokens += s * b
    return rows


def test_composed_recipe_matches_individual_regulators(tmp_path):
    """Acceptance: one TrainConfig runs SLW + batch warmup + token-wise LR
    simultaneously; the per-step (seqlen, batch, lr) trajectory equals the
    individual schedules' standalone predictions, across a mid-warmup
    checkpoint/restore, with dp-size batch quantization engaged."""
    d = str(tmp_path / "ck")
    steps, dp = 16, 2
    pred = _predict_composed(_composed_tc(steps), steps, dp_size=dp)

    r1 = train(_composed_tc(8, ckpt_dir=d), quiet=True, dp_size=dp)
    assert r1.steps == 8  # mid-warmup: both schedules still ramping
    assert r1.seqlen_history[-1] < 128 and r1.batch_history[-1] < 8
    r2 = train(_composed_tc(steps, ckpt_dir=d), resume=True, quiet=True,
               dp_size=dp)
    assert r2.restored_from_step == 8

    seqs = r1.seqlen_history + r2.seqlen_history
    batches = r1.batch_history + r2.batch_history
    lrs = r1.lr_history + r2.lr_history
    assert seqs == [p[0] for p in pred]
    assert batches == [p[1] for p in pred]
    assert all(b % dp == 0 for b in batches)  # paper's §5.1 dp constraint
    for got, (_, _, want) in zip(lrs, pred):
        assert got == pytest.approx(want, rel=1e-6)
    assert r2.tokens == sum(s * b for s, b, _ in pred)


def test_variance_gated_resume_roundtrip(tmp_path):
    """gate_level/var_trailing round-trip through ControllerState: a restart
    mid-warmup resumes the variance-gated curriculum at the same bucket."""
    d = str(tmp_path / "ck")
    tc = _tc(steps=10, pacing="variance_gated", ckpt_dir=d)
    tr1 = Trainer(tc, quiet=True)
    res1 = tr1.run()
    assert res1.steps == 10
    saved = tr1.stack["seqlen"].state_dict()
    assert saved["gate_level"] > 0  # the gate actually advanced
    assert saved["var_trailing"] > 0.0

    tc2 = _tc(steps=20, pacing="variance_gated", ckpt_dir=d)
    tr2 = Trainer(tc2, quiet=True)
    assert tr2.resume() == 10
    assert tr2.stack["seqlen"].state_dict() == saved
    assert tr2.step == tr1.step
    next_seq = [tr.stack.plan(StepTelemetry(step=tr.step)).seq_len
                for tr in (tr1, tr2)]
    assert next_seq[0] == next_seq[1]  # same bucket
    res2 = tr2.run()
    assert res2.steps == 20
    assert res2.seqlen_history[0] >= res1.seqlen_history[-1]


def test_drain_checkpoints_and_exits(tmp_path):
    d = str(tmp_path / "ck")
    drain = DrainSignal(install=False)
    calls = {"n": 0}

    def cb(step, metrics):
        calls["n"] += 1
        if step == 7:
            drain.trigger()

    res = train(_tc(steps=100, ckpt_dir=d), drain=drain, callback=cb,
                quiet=True)
    assert res.drained
    assert res.steps == 8
    from repro.checkpoint import latest_step
    assert latest_step(d) == 8  # checkpointed on the way out


def test_custom_hooks_extend_defaults():
    """Passing hooks= must not silently drop the drain/callback/eval
    concerns — extras append after the default hook set."""
    from repro.launch.train import (CheckpointHook, DrainHook, EvalHook,
                                    TelemetryHook, Trainer, TrainerHook,
                                    WatchdogHook)

    class Extra(TrainerHook):
        pass

    extra = Extra()
    tr = Trainer(_tc(steps=1), hooks=[extra])
    kinds = [type(h) for h in tr.hooks]
    assert kinds == [DrainHook, WatchdogHook, TelemetryHook, EvalHook,
                     CheckpointHook, Extra]
    assert tr.hooks[-1] is extra


def test_watchdog_flags_stragglers():
    wd = StepWatchdog(window=16, factor=2.0)
    import time
    for i in range(12):
        wd.start()
        if i == 10:
            time.sleep(0.05)
        else:
            time.sleep(0.001)
        wd.stop()
    assert len(wd.straggler_steps) >= 1
    assert wd.summary()["stragglers"] >= 1


@pytest.mark.slow
def test_variance_gated_pacing_runs():
    res = train(_tc(steps=20, pacing="variance_gated"), quiet=True)
    assert res.steps == 20
    assert not res.diverged


def test_watchdog_stop_without_start_is_noop():
    """Hook orders that skip start (drain/early-stop paths) used to crash
    on a None _t0; now the unmatched stop records nothing."""
    wd = StepWatchdog()
    assert wd.stop() is False
    assert wd.durations == []
    wd.start()
    assert wd.stop() is False  # first sample: no straggler baseline yet
    assert len(wd.durations) == 1
    assert wd.stop() is False  # second unmatched stop: still a no-op
    assert len(wd.durations) == 1


def test_retry_policy_exponential_backoff_with_cap():
    from repro.distributed.fault_tolerance import RetryPolicy
    pol = RetryPolicy(max_retries=5, backoff_s=0.5, backoff_factor=2.0,
                      backoff_cap_s=3.0)
    assert [pol.delay(a) for a in (1, 2, 3, 4, 5)] == \
        [0.5, 1.0, 2.0, 3.0, 3.0]  # capped from attempt 4
    assert RetryPolicy(backoff_s=0.0).delay(3) == 0.0  # no-sleep default


def test_supervisor_records_failures_and_backs_off():
    import time as _time
    from repro.distributed.fault_tolerance import RetryPolicy
    sup = TrainSupervisor(policy=RetryPolicy(max_retries=2, backoff_s=0.05,
                                             backoff_factor=2.0))
    attempts = []

    def run(resume):
        attempts.append(resume)
        if len(attempts) < 3:
            raise RuntimeError(f"boom {len(attempts)}")
        return "ok"

    t0 = _time.time()
    assert sup.run(run) == "ok"
    elapsed = _time.time() - t0
    assert attempts == [False, True, True]
    assert sup.restarts == 2
    assert [f["attempt"] for f in sup.failures] == [1, 2]
    assert [f["error"] for f in sup.failures] == \
        ["RuntimeError: boom 1", "RuntimeError: boom 2"]
    assert all(t0 <= f["time"] <= t0 + elapsed for f in sup.failures)
    assert elapsed >= 0.05 + 0.1  # 0.05, then 0.05 * 2


def test_drain_signal_uninstall_restores_handlers():
    import signal
    prev_term = signal.getsignal(signal.SIGTERM)
    prev_int = signal.getsignal(signal.SIGINT)
    ds = DrainSignal(install=True)
    assert signal.getsignal(signal.SIGTERM) == ds._handler
    ds.uninstall()
    assert signal.getsignal(signal.SIGTERM) is prev_term
    assert signal.getsignal(signal.SIGINT) is prev_int
    ds.uninstall()  # idempotent
    assert signal.getsignal(signal.SIGTERM) is prev_term
    # the Trainer teardown path: DrainHook.close() uninstalls, so handlers
    # never leak across Trainer instances
    from repro.launch.train import DrainHook
    ds2 = DrainSignal(install=True)
    hook = DrainHook(ds2)
    assert signal.getsignal(signal.SIGTERM) == ds2._handler
    hook.close()
    assert signal.getsignal(signal.SIGTERM) is prev_term


def test_divergence_detection():
    """Absurd LR must trip the NaN/divergence path, like the paper's 40x-LR
    baseline (Fig. 5)."""
    res = train(_tc(steps=40, slw=False, lr=80.0), quiet=True,
                stop_on_nan=True)
    assert res.diverged or res.tracker_summary["max_loss_ratio"] > 2.0
