"""SLW on the regulator stack: truncate/repack transforms, the schedule and
its variance gate, and batch warmup's dp-size rounding."""
import numpy as np
import pytest

from repro.configs.base import BatchWarmupConfig, SLWConfig
from repro.core import apply_seqlen
from repro.core.regulators import (BatchSizeRegulator, RegulatorStack,
                                   SeqLenRegulator, StepPlan, StepTelemetry)


def _batch(b=4, s=256):
    x = np.arange(b * s, dtype=np.int32).reshape(b, s)
    return {"tokens": x, "labels": x + 1}


def _stack(*regs, full_seq=256, full_batch=4):
    return RegulatorStack(regs, full_seq=full_seq, full_batch=full_batch,
                          base_lr=1e-3)


def _plan(seq, batch=4):
    return StepPlan(seq_len=seq, batch_size=batch, lr=1e-3)


def test_truncate_keeps_prefix():
    cfg = SLWConfig(start_seq_len=8, duration_steps=100, round_multiple=8)
    stack = _stack(SeqLenRegulator(cfg, 256))
    out, tokens = stack.apply(_batch(), _plan(64))
    assert out["tokens"].shape == (4, 64)
    np.testing.assert_array_equal(out["tokens"], _batch()["tokens"][:, :64])
    assert tokens == 4 * 64


def test_repack_conserves_tokens():
    cfg = SLWConfig(start_seq_len=8, duration_steps=100, mode="repack")
    stack = _stack(SeqLenRegulator(cfg, 256))
    out, tokens = stack.apply(_batch(), _plan(64))
    assert out["tokens"].shape == (16, 64)  # 4 * 256//64
    assert tokens == 4 * 256  # nothing dropped
    # data preserved in order
    np.testing.assert_array_equal(out["tokens"].reshape(4, 256),
                                  _batch()["tokens"])


def test_full_length_is_identity():
    cfg = SLWConfig(start_seq_len=8, duration_steps=10)
    stack = _stack(SeqLenRegulator(cfg, 256))
    plan = stack.plan(StepTelemetry(step=10_000))
    assert plan.seq_len == 256
    out, tokens = stack.apply(_batch(), plan)
    assert out["tokens"].shape == (4, 256)


def test_vision_prefix_not_truncated():
    cfg = SLWConfig(start_seq_len=8, duration_steps=100)
    reg = SeqLenRegulator(cfg, 256, prefix_tokens=16)
    assert reg.ladder[-1] == 256 - 16  # SLW warms up the text alone
    batch = dict(_batch(), patch_embeds=np.zeros((4, 16, 32), np.float32))
    out, tokens = _stack(reg).apply(batch, _plan(64))
    assert out["patch_embeds"].shape == (4, 16, 32)  # untouched
    assert out["tokens"].shape == (4, 64)
    assert tokens == 4 * 64 + 4 * 16  # text + prefix tokens both counted


def test_token_accounting_and_state_roundtrip():
    cfg = SLWConfig(start_seq_len=8, duration_steps=100)
    stack = _stack(SeqLenRegulator(cfg, 256))
    seen = 0
    for step in range(5):
        tele = StepTelemetry(step=step, tokens_seen=seen)
        plan = stack.plan(tele)
        _, tokens = stack.apply(_batch(), plan)
        assert tokens == 4 * plan.seq_len
        stack.observe(tele, tokens)
        seen += tokens
    cs = stack.controller_state(5, seen)
    stack2 = _stack(SeqLenRegulator(cfg, 256))
    stack2.load_controller_state(cs)
    tele = StepTelemetry(step=cs.step, tokens_seen=cs.tokens_seen)
    assert stack2.plan(tele).seq_len == stack.plan(tele).seq_len


def test_variance_gate_blocks_advance():
    cfg = SLWConfig(start_seq_len=8, duration_steps=10,
                    pacing="variance_gated", variance_gate=1.5)
    spiky, calm = SeqLenRegulator(cfg, 256), SeqLenRegulator(cfg, 256)
    lo = _stack(spiky).plan(StepTelemetry(step=0)).seq_len
    for step in range(20):
        # spiking variance holds the gate down; calm variance advances it
        spiky.observe(StepTelemetry(step=step, var_max=1e9 * (1 + step)), 32)
        calm.observe(StepTelemetry(step=step, var_max=1.0), 32)
    assert calm.gate_level > spiky.gate_level
    tele = StepTelemetry(step=20)
    assert lo <= _stack(spiky).plan(tele).seq_len \
        < _stack(calm).plan(tele).seq_len


def test_apply_seqlen_standalone_matches_curriculum():
    """The standalone transform is exactly what the stack executes for a
    plan."""
    cfg = SLWConfig(start_seq_len=8, duration_steps=100, mode="repack")
    via_stack, t1 = _stack(SeqLenRegulator(cfg, 256)).apply(_batch(),
                                                            _plan(64))
    direct, t2 = apply_seqlen(_batch(), 64, mode="repack")
    assert t1 == t2
    np.testing.assert_array_equal(via_stack["tokens"], direct["tokens"])
    with pytest.raises(ValueError, match="unknown SLW mode"):
        apply_seqlen(_batch(), 64, mode="bogus")


def test_batch_warmup_multiple_of_dp():
    reg = BatchSizeRegulator(BatchWarmupConfig(start_batch=4,
                                               warmup_tokens=1000),
                             full_batch=32, dp_size=8)
    stack = _stack(reg, full_seq=16, full_batch=32)
    batch = _batch(b=32, s=16)
    out, tokens = stack.apply(batch, stack.plan(StepTelemetry(
        tokens_seen=500)))
    assert out["tokens"].shape[0] % 8 == 0  # the paper's §5.1 constraint
    assert out["tokens"].shape[0] < 32
    assert tokens == out["tokens"].shape[0] * 16
    out_full, _ = stack.apply(batch, stack.plan(StepTelemetry(
        tokens_seen=10_000)))
    assert out_full["tokens"].shape[0] == 32
