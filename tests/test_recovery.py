"""Divergence-aware recovery: detector classification, the snapshot ring,
the intervention regulator, and end-to-end rollback under injected faults.

The end-to-end tests drive the real trainer with the real fault injector —
nothing here monkeypatches the recovery path itself; faults go in through
``FaultInjector`` exactly as the chaos benchmark injects them.
"""
import dataclasses
import math

import jax
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.configs.base import OptimizerConfig, SLWConfig, TrainConfig
from repro.core.recovery import (DivergenceDetector, DivergenceError,
                                 RecoveryConfig, RecoveryRegulator, StateRing)
from repro.core.regulators import StepPlan, StepTelemetry
from repro.distributed.fault_injection import FaultInjector, parse_faults
from repro.distributed.fault_tolerance import RetryPolicy, TrainSupervisor
from repro.launch.train import Trainer, train


def _tc(steps=20, seq=64, batch=4, lr=2e-3, ckpt_dir="", interval=0,
        vocab=128):
    cfg = reduced(get_arch("gpt2-117m").model).replace(vocab_size=vocab)
    return TrainConfig(
        model=cfg,
        optimizer=OptimizerConfig(
            lr=lr, min_lr=1e-5, schedule="token_cosine",
            warmup_steps=4, warmup_tokens=4 * batch * seq,
            total_steps=steps, total_tokens=steps * batch * seq),
        slw=SLWConfig(pacing="linear", start_seq_len=8,
                      duration_steps=steps // 2, round_multiple=8,
                      max_buckets=4),
        seq_len=seq, global_batch=batch, remat="none",
        eval_interval=0, checkpoint_interval=interval,
        checkpoint_dir=ckpt_dir)


def _tele(step, loss=2.0, ratio=1.0, grad=1.0, var=1e-6):
    return StepTelemetry(step=step, loss=loss, loss_ratio=ratio,
                         grad_norm=grad, var_max=var)


# ---------------------------------------------------------------------------
# detector
# ---------------------------------------------------------------------------

def test_detector_nan_fires_unconditionally():
    det = DivergenceDetector(RecoveryConfig(grace_steps=100,
                                            cooldown_steps=100))
    ev = det.update(_tele(0, loss=float("nan")))
    assert ev is not None and ev.kind == "nan_loss"
    ev = det.update(_tele(1, grad=float("inf")))
    assert ev is not None and ev.kind == "nan_grad"
    # NaN pierces even an active cooldown
    det.begin_cooldown()
    ev = det.update(_tele(2, loss=float("inf")))
    assert ev is not None and ev.kind == "nan_loss"


def test_detector_spike_respects_grace_and_cooldown():
    cfg = RecoveryConfig(spike_ratio=3.0, grace_steps=3, cooldown_steps=2)
    det = DivergenceDetector(cfg)
    for i in range(3):  # grace: a huge ratio does not fire yet
        assert det.update(_tele(i, ratio=50.0)) is None
    ev = det.update(_tele(3, ratio=50.0))
    assert ev is not None and ev.kind == "loss_spike"
    det.begin_cooldown()
    assert det.update(_tele(4, ratio=50.0)) is None  # cooldown 1
    assert det.update(_tele(5, ratio=50.0)) is None  # cooldown 2
    ev = det.update(_tele(6, ratio=50.0))
    assert ev is not None and ev.kind == "loss_spike"


def test_detector_var_excursion_needs_sustain():
    cfg = RecoveryConfig(var_gate=8.0, var_sustain=3, grace_steps=2)
    det = DivergenceDetector(cfg)
    for i in range(2):
        assert det.update(_tele(i, var=1.0)) is None
    base = det.var_trailing
    assert base > 0.0
    # two excursion steps: streak builds, no event, trailing frozen
    assert det.update(_tele(2, var=100.0)) is None
    assert det.update(_tele(3, var=100.0)) is None
    assert det.var_trailing == base  # the gate must not chase the spike
    ev = det.update(_tele(4, var=100.0))
    assert ev is not None and ev.kind == "var_excursion"
    # a clean sample resets the streak
    det2 = DivergenceDetector(cfg)
    for i in range(2):
        det2.update(_tele(i, var=1.0))
    det2.update(_tele(2, var=100.0))
    det2.update(_tele(3, var=1.0))   # streak broken
    det2.update(_tele(4, var=100.0))
    assert det2.update(_tele(5, var=100.0)) is None  # needs 3 again


# ---------------------------------------------------------------------------
# snapshot ring + intervention regulator
# ---------------------------------------------------------------------------

def test_state_ring_capacity_and_isolation():
    ring = StateRing(capacity=2)
    tr = Trainer(_tc(steps=2))
    for s in (0, 5, 10):
        tr.step = s
        ring.push(s, s * 100, tr.state, tr.controller_state(), tr._last)
    assert ring.steps == [5, 10]  # capacity 2, oldest evicted
    snap = ring.newest()
    restored = ring.materialize(snap)
    # materialize hands back fresh arrays each time — a restore that donates
    # its buffers to the train step must not poison the ring entry
    again = ring.materialize(snap)
    for a, b in zip(jax.tree_util.tree_leaves(restored),
                    jax.tree_util.tree_leaves(again)):
        assert a is not b
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ring.drop_newest()
    assert ring.steps == [5]


def test_recovery_regulator_plan_and_state_roundtrip():
    cfg = RecoveryConfig(lr_backoff=0.5, lr_floor=0.1, skip_window_steps=4)
    reg = RecoveryRegulator((8, 16, 32, 64), cfg)
    plan = StepPlan(seq_len=64, batch_size=8, lr=1e-3, grad_clip_scale=1.0)
    out = reg.plan(_tele(0), dataclasses.replace(plan))
    assert out.lr == 1e-3 and out.seq_len == 64  # identity before rollback

    reg.deepen_lr()
    out = reg.plan(_tele(0), dataclasses.replace(plan))
    assert out.lr == pytest.approx(5e-4)
    assert out.grad_clip_scale == pytest.approx(0.5)
    for _ in range(10):
        reg.deepen_lr()
    assert reg.lr_scale == pytest.approx(0.1)  # floor holds

    reg2 = RecoveryRegulator((8, 16, 32, 64), cfg)
    reg2.clamp_seq()
    out = reg2.plan(_tele(0), dataclasses.replace(plan))
    assert out.seq_len == 32  # one rung down from 64
    reg2.clamp_seq()
    assert reg2.plan(_tele(0),
                     dataclasses.replace(plan)).seq_len == 16
    # a plan already below the clamp is untouched
    low = dataclasses.replace(plan, seq_len=8)
    assert reg2.plan(_tele(0), low).seq_len == 8

    reg2.skip_data()
    d = reg2.state_dict()
    reg3 = RecoveryRegulator((8, 16, 32, 64), cfg)
    reg3.load_state_dict(d)
    assert reg3.seq_drop == 2 and reg3.data_offset == 4
    assert reg3.lr_scale == reg2.lr_scale


def test_recovery_regulator_checkpoints_through_controller_state(tmp_path):
    d = str(tmp_path / "ck")
    tr = Trainer(_tc(steps=10, ckpt_dir=d, interval=5),
                 recovery=RecoveryConfig())
    reg = tr.stack["recovery"]
    reg.deepen_lr()
    reg.clamp_seq()
    reg.skip_data()
    tr.step = 5
    tr.save_checkpoint()
    tr2 = Trainer(_tc(steps=10, ckpt_dir=d, interval=5),
                  recovery=RecoveryConfig())
    assert tr2.resume() == 5
    reg2 = tr2.stack["recovery"]
    assert reg2.lr_scale == reg.lr_scale
    assert reg2.seq_drop == 1 and reg2.data_offset == reg.data_offset


# ---------------------------------------------------------------------------
# end-to-end rollback under injected faults
# ---------------------------------------------------------------------------

def test_nan_fault_recovers_and_completes():
    inj = FaultInjector(parse_faults("nan_grad@8"), seed=0)
    res = train(_tc(steps=20), quiet=True, recovery=RecoveryConfig(),
                fault_injector=inj)
    assert res.steps == 20 and not res.diverged
    assert res.rollbacks == 1
    assert res.faults_fired == ["nan_grad@8"]
    assert any(e.startswith("nan_loss@8") or e.startswith("nan_grad@8")
               for e in res.recovery_events)
    assert any(e.startswith("restored@") for e in res.recovery_events)
    assert math.isfinite(res.loss_history[-1])


@pytest.mark.slow
def test_spike_rollback_resumes_schedules_exactly():
    """With a no-op intervention (lr_backoff=1), the replayed steps after a
    rollback are bitwise identical to the clean run: the snapshot re-seats
    params + ControllerState + tracker exactly."""
    clean = train(_tc(steps=20), quiet=True)
    inj = FaultInjector(parse_faults("spike@10:64.0"), seed=0)
    cfg = RecoveryConfig(lr_backoff=1.0, lr_floor=1.0)
    res = train(_tc(steps=20), quiet=True, recovery=cfg, fault_injector=inj)
    assert res.steps == 20 and not res.diverged and res.rollbacks == 1
    assert "restored@10" in res.recovery_events
    # histories: 10 clean + 1 spiked + 10 replayed = 21 entries; the replay
    # tail must equal the clean run's steps 10..19 exactly
    assert len(res.seqlen_history) == 21
    assert res.seqlen_history[-10:] == clean.seqlen_history[10:]
    assert res.batch_history[-10:] == clean.batch_history[10:]
    assert res.lr_history[-10:] == clean.lr_history[10:]
    np.testing.assert_array_equal(np.asarray(res.loss_history[-10:]),
                                  np.asarray(clean.loss_history[10:]))


def test_persistent_divergence_exhausts_budget_and_stops():
    res = train(_tc(steps=20, lr=2000.0), quiet=True,
                recovery=RecoveryConfig(policy=RetryPolicy(max_retries=2)))
    assert res.diverged
    assert res.rollbacks == 2  # the budget, not one extra
    assert any(e.startswith("gave_up@") for e in res.recovery_events)


def test_escalate_raise_pairs_with_supervisor(tmp_path):
    """In-process exhaustion hands off to the process-level supervisor via
    DivergenceError; the two layers share one RetryPolicy shape."""
    d = str(tmp_path / "ck")
    pol = RetryPolicy(max_retries=1)
    sup = TrainSupervisor(policy=pol)

    def run(resume):
        train(_tc(steps=20, lr=2000.0, ckpt_dir=d, interval=5),
              resume=resume, quiet=True,
              recovery=RecoveryConfig(policy=pol, escalate="raise"))
        return "ok"

    with pytest.raises(DivergenceError):
        sup.run(run)
    assert sup.restarts == 2  # initial + 1 retry, then re-raise
    assert [f["attempt"] for f in sup.failures] == [1, 2]
    assert all("DivergenceError" in f["error"] for f in sup.failures)


@pytest.mark.slow
def test_escalation_ladder_engages_in_order():
    """Repeated rollbacks walk the ladder: LR backoff first, then the
    seq-len clamp, then the data-window skip."""
    inj = FaultInjector(
        parse_faults("nan_grad@6,nan_grad@9,nan_grad@12"), seed=0)
    tr = Trainer(_tc(steps=20),
                 recovery=RecoveryConfig(policy=RetryPolicy(max_retries=5)),
                 fault_injector=inj)
    res = tr.run()
    assert res.steps == 20 and not res.diverged
    assert res.rollbacks == 3
    assert len(res.faults_fired) == 3
    # three rollbacks walk the whole ladder: LR backoff every time (0.5^3),
    # seq clamp at rollbacks 2 and 3, the data skip at rollback 3
    reg = tr.stack["recovery"]
    assert reg.lr_scale == pytest.approx(0.125)
    assert reg.seq_drop == 2
    assert reg.data_offset == RecoveryConfig().skip_window_steps


# ---------------------------------------------------------------------------
# ring persistence across a drain (preemption survival)
# ---------------------------------------------------------------------------

def test_state_ring_survives_drain_and_resume(tmp_path):
    """A drained run spills the in-run rollback ring to disk next to the
    checkpoint; --recover resume refills it with the same restore points
    (steps, state arrays, telemetry) it had when the preemption landed."""
    import os

    d = str(tmp_path / "ck")
    tc = _tc(steps=30, ckpt_dir=d, interval=0)

    class StopAt:
        def on_run_start(self, tr):
            pass

        def on_step_start(self, tr):
            if tr.step >= 9:
                tr.request_drain()

        def on_step_end(self, tr, tele, plan, metrics):
            pass

        def on_run_end(self, tr):
            pass

        def close(self):
            pass

    tr = Trainer(tc, recovery=RecoveryConfig(snapshot_interval=3),
                 hooks=[StopAt()])
    res = tr.run()
    assert res.drained
    ring_dir = os.path.join(d, "ring")
    assert sorted(os.listdir(ring_dir)) == [
        f"step_{s:012d}" for s in tr.recovery.ring.steps]

    tr2 = Trainer(tc, recovery=RecoveryConfig(snapshot_interval=3))
    assert tr2.resume() == 9
    assert tr2.recovery.ring.steps == tr.recovery.ring.steps
    a = tr.recovery.ring.newest()
    b = tr2.recovery.ring.newest()
    assert b.tokens_seen == a.tokens_seen
    assert b.telemetry.step == a.telemetry.step
    assert b.telemetry.loss == pytest.approx(a.telemetry.loss)
    for x, y in zip(jax.tree_util.tree_leaves(a.state),
                    jax.tree_util.tree_leaves(b.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # and the resumed run continues to completion from those restore points
    res2 = tr2.run()
    assert res2.steps == 30 and not res2.diverged


def test_state_ring_load_skips_corrupt_entry(tmp_path):
    """Ring restore is best-effort: a corrupt spilled snapshot is skipped,
    not fatal (the real checkpoint is the durable artifact)."""
    import os

    d = str(tmp_path / "ring")
    tc = _tc(steps=4)
    tr = Trainer(tc)
    ring = StateRing(capacity=3)
    for s in (2, 4):
        ring.push(s, s * 10, tr.state, tr.controller_state(), tr._last)
    ring.save(d)
    # corrupt the newest entry's payload
    inj = FaultInjector(seed=0)
    inj.corrupt_checkpoint(d, step=4)

    from repro.launch import steps as steps_lib
    like = steps_lib.abstract_train_state(tc.model, tc.optimizer)
    ring2 = StateRing(capacity=3)
    assert ring2.load(d, like) == 1
    assert ring2.steps == [2]
