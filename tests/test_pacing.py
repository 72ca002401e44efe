"""Property tests for the pacing functions.

Runs under hypothesis when it is installed; otherwise falls back to a
deterministic built-in case sweep over the same config domains (this
container does not ship hypothesis, and the invariants are cheap enough
to check on a few hundred sampled configs either way).
"""
import random

import pytest

from repro.configs import get_arch
from repro.configs.base import RegulatorSpec, SLWConfig, TrainConfig
from repro.core import pacing
from repro.core.regulators import StepTelemetry, build_stack

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

FULLS = [256, 1024, 2048, 4096, 32768]
# 12 and 100 are deliberately not multiples of the rounding values: the
# ladder must keep the rounded-down arithmetic anchor below them
STARTS = [4, 8, 12, 16, 64, 100]
PACINGS = ["linear", "root", "two_stage"]
ROUNDS = [8, 128]


def _builtin_cases(n=96, seed=0):
    """Deterministic stand-in for the hypothesis strategy."""
    rng = random.Random(seed)
    cases = []
    for _ in range(n):
        full = rng.choice(FULLS)
        s0 = rng.choice(STARTS)
        cfg = SLWConfig(
            pacing=rng.choice(PACINGS),
            start_seq_len=min(s0, full),
            duration_steps=rng.randint(1, 50_000),
            round_multiple=rng.choice(ROUNDS),
            max_buckets=rng.randint(4, 64),
        )
        cases.append((cfg, full))
    return cases


# ---------------------------------------------------------------------------
# invariant bodies (shared by both drivers)
# ---------------------------------------------------------------------------

def _ladder(cfg, full):
    return pacing.bucket_ladder(cfg.start_seq_len, full, cfg.round_multiple,
                                cfg.max_buckets)


def _check_ladder_invariants(cfg, full):
    ladder = _ladder(cfg, full)
    assert len(ladder) <= cfg.max_buckets + 8  # geometric prefix allowance
    assert ladder == tuple(sorted(set(ladder)))
    # round-*down* semantics: the smallest bucket never exceeds the
    # configured start, and sits no further below it than one multiple
    s0 = min(cfg.start_seq_len, full)
    floor = s0 if s0 < cfg.round_multiple else s0 - s0 % cfg.round_multiple
    assert floor <= ladder[0] <= s0
    assert ladder[-1] == full


def _check_seqlen_bounds(cfg, full, step):
    s = pacing.seqlen_at(cfg, step, full)
    assert cfg.start_seq_len <= s + cfg.round_multiple  # never far below s0
    assert s <= full


def _check_monotone_nondecreasing(cfg, full):
    if cfg.pacing == "two_stage":
        return  # discrete jump is monotone by construction, tested below
    ladder = _ladder(cfg, full)
    prev = 0
    for t in range(0, cfg.duration_steps + 10,
                   max(cfg.duration_steps // 50, 1)):
        s = pacing.seqlen_at(cfg, t, full, ladder=ladder)
        assert s >= prev
        prev = s


def _check_reaches_full_length(cfg, full):
    assert pacing.seqlen_at(cfg, cfg.duration_steps + 1, full) == full


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @st.composite
    def slw_configs(draw):
        full = draw(st.sampled_from(FULLS))
        s0 = draw(st.sampled_from(STARTS))
        return SLWConfig(
            pacing=draw(st.sampled_from(PACINGS)),
            start_seq_len=min(s0, full),
            duration_steps=draw(st.integers(1, 50_000)),
            round_multiple=draw(st.sampled_from(ROUNDS)),
            max_buckets=draw(st.integers(4, 64)),
        ), full

    @given(slw_configs())
    @settings(max_examples=200, deadline=None)
    def test_ladder_invariants(cfg_full):
        _check_ladder_invariants(*cfg_full)

    @given(slw_configs(), st.integers(0, 100_000))
    @settings(max_examples=200, deadline=None)
    def test_seqlen_bounds(cfg_full, step):
        _check_seqlen_bounds(cfg_full[0], cfg_full[1], step)

    @given(slw_configs())
    @settings(max_examples=100, deadline=None)
    def test_monotone_nondecreasing(cfg_full):
        _check_monotone_nondecreasing(*cfg_full)

    @given(slw_configs())
    @settings(max_examples=100, deadline=None)
    def test_reaches_full_length_after_duration(cfg_full):
        _check_reaches_full_length(*cfg_full)

else:
    CASES = _builtin_cases()

    def test_ladder_invariants():
        for cfg, full in CASES:
            _check_ladder_invariants(cfg, full)

    def test_seqlen_bounds():
        rng = random.Random(1)
        for cfg, full in CASES:
            for step in (0, 1, cfg.duration_steps // 2, cfg.duration_steps,
                         cfg.duration_steps + 1, rng.randint(0, 100_000)):
                _check_seqlen_bounds(cfg, full, step)

    def test_monotone_nondecreasing():
        for cfg, full in CASES[:48]:
            _check_monotone_nondecreasing(cfg, full)

    def test_reaches_full_length_after_duration():
        for cfg, full in CASES:
            _check_reaches_full_length(cfg, full)


# ---------------------------------------------------------------------------
# exact-value tests (no property machinery)
# ---------------------------------------------------------------------------

def test_paper_linear_formula_exact():
    """seqlen_t = s0 + (s1-s0)*min(t/T,1), rounded down to the ladder."""
    cfg = SLWConfig(start_seq_len=8, duration_steps=100, round_multiple=8,
                    max_buckets=10_000)  # ladder fine enough to be exact-ish
    raw = pacing.raw_seqlen(cfg, 50, 1024)
    assert raw == pytest.approx(8 + (1024 - 8) * 0.5)
    s = pacing.seqlen_at(cfg, 50, 1024)
    assert s <= raw < s + 8 + 1  # round-down semantics


def test_non_multiple_start_keeps_rounded_anchor():
    """start_seq_len=12 with round_multiple=8: the ladder keeps the
    rounded-down anchor (8), so the earliest warmup steps never run
    *longer* than configured (the old filter deleted it, making the
    smallest bucket 16)."""
    cfg = SLWConfig(start_seq_len=12, duration_steps=100, round_multiple=8,
                    max_buckets=16)
    ladder = _ladder(cfg, 256)
    assert ladder[0] == 8
    assert pacing.seqlen_at(cfg, 0, 256) <= 12


def test_two_stage_is_shortformer():
    cfg = SLWConfig(pacing="two_stage", two_stage_short_len=128,
                    duration_steps=1000)
    assert pacing.raw_seqlen(cfg, 999, 1024) == 128
    assert pacing.raw_seqlen(cfg, 1000, 1024) == 1024


def test_stack_without_seqlen_plans_full_length():
    """SLW is off exactly when the stack has no seqlen regulator: every
    step then plans the full length, whatever ``TrainConfig.slw`` says."""
    from repro.configs import get_arch
    from repro.configs.base import RegulatorSpec, TrainConfig
    from repro.core.regulators import StepTelemetry, build_stack
    tc = TrainConfig(model=get_arch("gpt2-117m").model, seq_len=2048,
                     slw=SLWConfig(start_seq_len=8, duration_steps=100),
                     regulators=(RegulatorSpec("lr"),))
    stack = build_stack(tc)
    for t in (0, 10, 10_000):
        assert stack.plan(StepTelemetry(step=t)).seq_len == 2048
