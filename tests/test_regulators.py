"""The composable regulator control plane: composition semantics, the
adaptive beyond-paper regulators, and the unified ControllerState."""
import dataclasses

import numpy as np
import pytest

from repro.configs.base import (BatchWarmupConfig, OptimizerConfig,
                                RegulatorSpec, SLWConfig, TrainConfig)
from repro.configs import get_arch, reduced
from repro.core import pacing
from repro.core.regulators import (ControllerState, StepPlan, StepTelemetry,
                                   VarianceLRThrottle, build_stack,
                                   predict_trajectory)
from repro.launch.train import build_config, build_parser
from repro.optim import lr_at


def _tc(slw=True, batch_warmup=True, steps=40, seq=128, batch=8,
        regulators=None):
    if regulators is None:
        kinds = (("seqlen",) * slw + ("batch_warmup",) * batch_warmup
                 + ("lr",))
        regulators = tuple(RegulatorSpec(k) for k in kinds)
    cfg = reduced(get_arch("gpt2-117m").model).replace(vocab_size=256)
    return TrainConfig(
        model=cfg,
        optimizer=OptimizerConfig(
            lr=2e-3, min_lr=1e-5, schedule="token_cosine", warmup_steps=8,
            warmup_tokens=8 * batch * seq, total_steps=steps,
            total_tokens=steps * batch * seq),
        slw=SLWConfig(start_seq_len=8, duration_steps=steps // 2,
                      round_multiple=8, max_buckets=5),
        batch_warmup=BatchWarmupConfig(start_batch=2,
                                       warmup_tokens=steps * batch * seq // 4),
        regulators=regulators,
        seq_len=seq, global_batch=batch, remat="none", eval_interval=0)


# ---------------------------------------------------------------------------
# stack construction + composition
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("flags,kinds", [
    ([], ["lr"]),
    (["--slw"], ["seqlen", "lr"]),
    (["--batch-warmup"], ["batch_warmup", "lr"]),
    (["--slw", "--batch-warmup"], ["seqlen", "batch_warmup", "lr"]),
    (["--var-lr-throttle", "--gns-batch", "--slw"],
     ["seqlen", "lr", "critical_batch", "var_lr_throttle"]),
    (["--slw", "--batch-warmup", "--gns-batch", "--var-lr-throttle"],
     ["seqlen", "batch_warmup", "lr", "critical_batch", "var_lr_throttle"]),
])
def test_cli_flags_build_the_regulator_tuple(flags, kinds):
    """Each flag turns on exactly its regulator, in one fixed stack order
    (schedules first; the LR throttle multiplies the scheduled LR)."""
    tc = build_config(build_parser().parse_args(flags))
    assert [s.kind for s in tc.regulators] == kinds
    assert [r.name for r in build_stack(tc).regulators] == kinds


def test_default_stack_is_slw_and_lr():
    """A TrainConfig that names no stack runs SLW and the LR schedule."""
    tc = TrainConfig(model=_tc().model)
    assert [r.name for r in build_stack(tc).regulators] == ["seqlen", "lr"]


def test_unknown_kind_raises():
    tc = _tc(regulators=(RegulatorSpec(kind="nope"),))
    with pytest.raises(ValueError, match="unknown regulator"):
        build_stack(tc)


def test_composed_plan_matches_individual_schedules():
    """The stack's joint plan == each schedule's formula: the pacing
    function on the bucket ladder, GPT-3's linear batch ramp in tokens
    (rounded down, never below its start), the LR schedule."""
    tc = _tc(slw=True, batch_warmup=True)
    stack = build_stack(tc, warmup_steps_hint=tc.optimizer.warmup_steps)
    bw, full = tc.batch_warmup, tc.global_batch
    tokens = 0
    for step in range(30):
        plan = stack.plan(StepTelemetry(step=step, tokens_seen=tokens))
        assert plan.seq_len == pacing.seqlen_at(
            tc.slw, step, tc.seq_len, tc.optimizer.warmup_steps)
        ramp = bw.start_batch + min(tokens / bw.warmup_tokens, 1.0) * (
            full - bw.start_batch)
        assert plan.batch_size == min(max(int(ramp), bw.start_batch), full)
        assert plan.lr == pytest.approx(lr_at(tc.optimizer, step, tokens))
        t_step = plan.seq_len * plan.batch_size
        stack.observe(StepTelemetry(step=step, tokens_seen=tokens), t_step)
        tokens += t_step


def test_stack_apply_slices_batch_then_seq():
    tc = _tc()
    stack = build_stack(tc)
    b, s = 8, 128
    batch = {"tokens": np.arange(b * s, dtype=np.int32).reshape(b, s)}
    out, tokens = stack.apply(batch, StepPlan(seq_len=16, batch_size=4,
                                              lr=1e-3))
    assert out["tokens"].shape == (4, 16)
    assert tokens == 4 * 16


def test_predict_trajectory_is_open_loop_replay():
    tc = _tc()
    plans = predict_trajectory(tc, 60,
                               warmup_steps_hint=tc.optimizer.warmup_steps)
    assert len(plans) == 60
    # monotone warmup on both axes, reaching the full shape
    seqs = [p.seq_len for p in plans]
    batches = [p.batch_size for p in plans]
    assert seqs == sorted(seqs) and batches == sorted(batches)
    assert seqs[0] == 8 and seqs[-1] == tc.seq_len
    assert batches[-1] == tc.global_batch


def test_predict_trajectory_variance_gated_reaches_full():
    """Open-loop replay feeds calm telemetry, so the variance gate advances
    along its calm-run envelope instead of pinning the smallest bucket."""
    tc = _tc(batch_warmup=False)
    tc = dataclasses.replace(
        tc, slw=dataclasses.replace(tc.slw, pacing="variance_gated"))
    plans = predict_trajectory(tc, 60,
                               warmup_steps_hint=tc.optimizer.warmup_steps)
    seqs = [p.seq_len for p in plans]
    assert seqs == sorted(seqs)
    assert seqs[-1] == tc.seq_len  # gate advanced to full length


# ---------------------------------------------------------------------------
# dp_size quantization (the paper's §5.1 structural constraint)
# ---------------------------------------------------------------------------

def test_dp_size_wired_into_batch_warmup():
    tc = _tc(slw=False, batch_warmup=True, batch=32)
    tc = dataclasses.replace(
        tc, batch_warmup=BatchWarmupConfig(start_batch=4,
                                           warmup_tokens=10_000))
    stack = build_stack(tc, dp_size=8)
    assert stack["batch_warmup"].dp_size == 8
    for tokens in (0, 2_000, 5_000, 9_000, 50_000):
        plan = stack.plan(StepTelemetry(step=0, tokens_seen=tokens))
        assert plan.batch_size % 8 == 0


# ---------------------------------------------------------------------------
# adaptive regulators (beyond-paper scenario clients)
# ---------------------------------------------------------------------------

def test_var_lr_throttle_backs_off_and_recovers():
    spec = RegulatorSpec(kind="var_lr_throttle", gate=2.0, floor=0.1,
                         backoff=0.5, recovery=1.5)
    reg = VarianceLRThrottle(spec)
    plan = reg.plan(StepTelemetry(), StepPlan(seq_len=8, batch_size=8, lr=1.0))
    assert plan.lr == 1.0 and plan.grad_clip_scale == 1.0
    reg.observe(StepTelemetry(var_max=1.0), 0)  # seeds trailing
    reg.observe(StepTelemetry(var_max=100.0), 0)  # spike -> backoff
    assert reg.scale == 0.5
    for i in range(20):  # escalating spikes: floor holds
        reg.observe(StepTelemetry(var_max=100.0 * 10.0 ** i), 0)
    assert reg.scale == pytest.approx(0.1)
    trailing = reg.trailing
    for _ in range(50):  # calm again: full recovery, capped at 1
        reg.observe(StepTelemetry(var_max=trailing), 0)
    assert reg.scale == 1.0
    plan = reg.plan(StepTelemetry(), StepPlan(seq_len=8, batch_size=8, lr=2.0))
    assert plan.lr == 2.0


def test_throttle_multiplies_scheduled_lr_in_stack():
    tc = _tc(slw=False, batch_warmup=False,
             regulators=(RegulatorSpec(kind="lr"),
                         RegulatorSpec(kind="var_lr_throttle", backoff=0.5)))
    stack = build_stack(tc)
    stack["var_lr_throttle"].scale = 0.5
    tele = StepTelemetry(step=100, tokens_seen=10**6)
    plan = stack.plan(tele)
    assert plan.lr == pytest.approx(
        0.5 * lr_at(tc.optimizer, 100, 10**6))
    assert plan.grad_clip_scale == 0.5


# ---------------------------------------------------------------------------
# unified controller state
# ---------------------------------------------------------------------------

def test_controller_state_roundtrip_all_regulators():
    tc = _tc(regulators=(RegulatorSpec(kind="seqlen"),
                         RegulatorSpec(kind="batch_warmup"),
                         RegulatorSpec(kind="lr"),
                         RegulatorSpec(kind="var_lr_throttle")))
    stack = build_stack(tc)
    # advance everything off its initial state
    for step in range(12):
        tele = StepTelemetry(step=step, tokens_seen=step * 256,
                             grad_norm=1.0 if step % 2 else 5.0,
                             var_max=1.0 if step % 3 else 50.0)
        stack.observe(tele, 256)
    cs = stack.controller_state(12, 12 * 256, {"min_loss": 3.0})
    # through the (JSON-able) host dict, like the checkpoint does
    import json
    cs2 = ControllerState.from_host(json.loads(json.dumps(cs.to_host())))
    stack2 = build_stack(tc)
    stack2.load_controller_state(cs2)
    assert cs2.step == 12 and cs2.tokens_seen == 12 * 256
    for name in ("seqlen", "batch_warmup", "lr", "var_lr_throttle"):
        assert stack2[name].state_dict() == stack[name].state_dict()
    # the restored stack plans identically
    tele = StepTelemetry(step=12, tokens_seen=12 * 256)
    p1, p2 = stack.plan(tele), stack2.plan(tele)
    assert (p1.seq_len, p1.batch_size, p1.lr) == \
        (p2.seq_len, p2.batch_size, p2.lr)


def test_duplicate_regulator_names_rejected():
    tc = _tc(regulators=(RegulatorSpec(kind="lr"), RegulatorSpec(kind="lr")))
    with pytest.raises(ValueError, match="duplicate"):
        build_stack(tc)


def test_legacy_host_state_migrates():
    from repro.checkpoint import migrate_host_state
    legacy = {"step": 7, "tokens_seen": 4096,
              "curriculum": {"step": 7, "tokens_seen": 4096, "gate_level": 2,
                             "var_trailing": 0.5},
              "tracker": {"min_loss": 2.5}}
    host = migrate_host_state(legacy)
    cs = ControllerState.from_host(host["controller"])
    assert cs.step == 7 and cs.tokens_seen == 4096
    assert cs.regulators["seqlen"]["gate_level"] == 2
    assert cs.tracker["min_loss"] == 2.5
    # new-format dicts pass through untouched
    assert migrate_host_state(host) is host


_GATE_SLOT = {"step": 6, "tokens_seen": 4096, "gate_level": 2,
              "var_trailing": 1.0}


@pytest.mark.parametrize("host", [
    # a checkpoint or ring snapshot whose seqlen slot still carries the
    # counters the regulator used to keep itself
    {"controller": {"step": 6, "tokens_seen": 4096,
                    "regulators": {"seqlen": dict(_GATE_SLOT), "lr": {}},
                    "tracker": {}}},
    # a pre-regulator host dict: the curriculum's step is the only step
    {"curriculum": dict(_GATE_SLOT), "tokens_seen": 4096, "tracker": {}},
], ids=["controller", "curriculum"])
def test_older_host_dicts_restore_and_plan_as_before(host):
    from repro.checkpoint import migrate_host_state
    args = build_parser().parse_args(
        ["--batch", "16", "--seq", "256", "--slw", "--pacing",
         "variance_gated", "--start-seq", "8", "--duration", "8",
         "--max-buckets", "12", "--warmup", "4"])
    stack = build_stack(build_config(args), warmup_steps_hint=args.warmup)
    cs = ControllerState.from_host(migrate_host_state(host)["controller"])
    assert (cs.step, cs.tokens_seen) == (6, 4096)
    stack.load_controller_state(cs)
    assert stack["seqlen"].state_dict() == {"gate_level": 2,
                                            "var_trailing": 1.0}
    seqs = []
    for k in range(cs.step, cs.step + 4):
        tele = StepTelemetry(step=k, tokens_seen=cs.tokens_seen,
                             var_max=1.0, var_l1=1.0, grad_norm=1.0)
        seqs.append(stack.plan(tele).seq_len)
        stack.observe(tele, 0)
    # the restored gate (rung 2 = 56) holds the envelope (176 at step 6)
    # down, then climbs one rung per calm step — as before the change
    assert seqs == [56, 80, 104, 128]
