"""The gradient-noise-scale subsystem (repro.gns): estimator math against
the analytic noise scale, the direction-sketch precursor, the measured
critical-batch regulator, recovery's per-leaf/precursor surfaces, and the
end-to-end trainer wiring (including the gns-off bitwise default path and
the --metrics-jsonl per-leaf round-trip)."""
import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_arch, reduced
from repro.configs.base import (GNSConfig, OptimizerConfig, RegulatorSpec,
                                SLWConfig, TrainConfig)
from repro.core.recovery import RecoveryConfig, RecoveryRegulator
from repro.core.regulators import (ControllerState, StepPlan, StepTelemetry,
                                   build_stack)
from repro.core.telemetry import read_metrics_jsonl
from repro.data import DataPipeline, SyntheticCorpus
from repro.distributed.fault_injection import FaultInjector
from repro.distributed.fault_tolerance import RetryPolicy
from repro.gns import GNSEstimator, gns_estimates
from repro.gns.precursor import GradientPrecursor
from repro.gns.regulator import CriticalBatchRegulator
from repro.launch import steps as steps_lib
from repro.launch.train import MetricsJsonlHook, train
from repro.models import model_zoo


# ---------------------------------------------------------------------------
# estimator math
# ---------------------------------------------------------------------------

def test_gns_estimates_invert_expectations_exactly():
    # feed the *expected* values of the biased norm pair — the unbiased
    # formulas must return the underlying (|G|^2, tr(Sigma)) exactly
    g_sq_true, tr_true, b, B = 2.0, 48.0, 4, 32
    small_sq = g_sq_true + tr_true / b
    big_sq = g_sq_true + tr_true / B
    g_sq, tr = gns_estimates(small_sq, big_sq, b, B)
    assert g_sq == pytest.approx(g_sq_true)
    assert tr == pytest.approx(tr_true)
    # elementwise on vectors too
    g_sq, tr = gns_estimates(np.array([small_sq, small_sq]),
                             np.array([big_sq, big_sq]), b, B)
    assert np.allclose(g_sq, g_sq_true) and np.allclose(tr, tr_true)


def test_estimator_matches_analytic_noise_scale():
    """Acceptance criterion: on a synthetic problem with known gradient
    mean/covariance (g = mu + sigma*eps, B_noise = n*sigma^2/|mu|^2) the
    EMA estimate lands within tolerance of the analytic value."""
    rng = np.random.RandomState(0)
    n, sigma, big, k = 128, 0.5, 64, 8
    mu = rng.randn(n)
    mu /= np.linalg.norm(mu)              # |G|^2 = 1
    true_b_noise = n * sigma ** 2
    est = GNSEstimator(ema_window=64, warmup_obs=8)
    for _ in range(300):
        samples = mu + sigma * rng.randn(big, n)
        shard_means = samples.reshape(k, big // k, n).mean(axis=1)
        est.update(float(np.mean(np.sum(shard_means ** 2, axis=1))),
                   float(np.sum(samples.mean(axis=0) ** 2)),
                   big // k, big)
    assert est.ready
    assert abs(est.b_noise - true_b_noise) / true_b_noise < 0.15
    # the efficiency curve rides the estimate: monotone in B, -> 1
    effs = [est.efficiency(b) for b in (1, 8, 64, 512, 1e6)]
    assert all(a < b for a, b in zip(effs, effs[1:]))
    assert effs[-1] == pytest.approx(1.0, abs=1e-3)
    assert est.critical_batch() == pytest.approx(est.b_noise)


def test_estimator_per_leaf_vectors_recompose_global_ratio():
    est = GNSEstimator(ema_window=8, warmup_obs=2)
    # two leaves with expected pairs for (g_sq, tr) = (1, 10) and (3, 2)
    b, B = 2, 16
    small = np.array([1 + 10 / b, 3 + 2 / b])
    big = np.array([1 + 10 / B, 3 + 2 / B])
    for _ in range(4):
        est.update(small, big, b, B)
    leaf = est.leaf_b_noise
    assert leaf is not None and leaf.shape == (2,)
    assert np.allclose(leaf, [10.0, 2.0 / 3.0])
    assert est.b_noise == pytest.approx((10 + 2) / (1 + 3))


def test_estimator_state_roundtrip_resumes_ema_exactly():
    rng = np.random.RandomState(1)
    a = GNSEstimator(ema_window=16, warmup_obs=4)
    for _ in range(10):
        s = float(rng.rand() + 1.0)
        a.update(s, s * 0.5, 4, 32)
    b = GNSEstimator(ema_window=16, warmup_obs=4)
    b.load_state_dict(json.loads(json.dumps(a.state_dict())))
    assert b.ready == a.ready and b.b_noise == pytest.approx(a.b_noise)
    for _ in range(5):  # continued updates stay in lockstep
        s = float(rng.rand() + 1.0)
        a.update(s, s * 0.5, 4, 32)
        b.update(s, s * 0.5, 4, 32)
    assert b.b_noise == pytest.approx(a.b_noise)


def test_estimator_ignores_degenerate_observations():
    est = GNSEstimator(ema_window=8, warmup_obs=1)
    est.update(1.0, 1.0, 8, 8)              # b == B: no system to solve
    est.update(float("nan"), 1.0, 4, 32)    # non-finite
    assert est.n_obs == 0 and not est.ready


# ---------------------------------------------------------------------------
# precursor (synthetic sketch streams)
# ---------------------------------------------------------------------------

def _pre_cfg(**kw):
    base = dict(enabled=True, precursor_window=6, precursor_dim=16,
                precursor_lags=2, precursor_gate=0.8, precursor_rise=0.25,
                precursor_grace=4, precursor_cooldown_steps=4)
    base.update(kw)
    return GNSConfig(**base)


_LABELS = ("blk0/attn", "blk0/mlp", "pos_embed")


def _noise_sketch(rng, n_leaves=3, d=16):
    return rng.randn(n_leaves, d)


def test_precursor_fires_on_rising_correlation_and_cools_down():
    rng = np.random.RandomState(0)
    pre = GradientPrecursor(_pre_cfg())
    for step in range(12):   # healthy: near-orthogonal directions
        assert pre.observe(step, _noise_sketch(rng), _LABELS) is None
    # leaf 1's direction freezes (the post-spike Adam state): its lagged
    # autocorrelation goes to ~1 while the others stay ambient
    frozen = rng.randn(16)
    events = []
    for step in range(12, 24):
        sk = _noise_sketch(rng)
        sk[1] = frozen + 0.05 * rng.randn(16)
        ev = pre.observe(step, sk, _LABELS)
        if ev is not None:
            events.append(ev)
    assert events, "precursor never fired on a frozen leaf direction"
    assert events[0].leaf == "blk0/mlp"
    assert events[0].score > 0.8 and events[0].score > events[0].baseline
    # refire cooldown: one sustained excursion != an event stream
    steps_between = [e.step for e in events]
    assert all(b - a > pre.cfg.precursor_cooldown_steps
               for a, b in zip(steps_between, steps_between[1:]))


def test_precursor_silent_on_noise():
    rng = np.random.RandomState(7)
    pre = GradientPrecursor(_pre_cfg())
    for step in range(60):
        assert pre.observe(step, _noise_sketch(rng), _LABELS) is None


def test_precursor_grace_absorbs_persistently_correlated_leaf():
    """A leaf that is direction-correlated from step 0 (positional
    embeddings under a fixed-format corpus) must be absorbed into the
    baseline during grace, not fired on at grace expiry."""
    rng = np.random.RandomState(3)
    pre = GradientPrecursor(_pre_cfg())
    fixed = rng.randn(16)
    for step in range(40):
        sk = _noise_sketch(rng)
        sk[2] = fixed + 0.05 * rng.randn(16)
        assert pre.observe(step, sk, _LABELS) is None, \
            f"fired on an always-correlated leaf at step {step}"
    # ...but the baseline it learned is honest: trailing[2] is high
    assert pre.trailing[2] > 0.8


def test_precursor_nan_sketch_clears_direction_history():
    rng = np.random.RandomState(5)
    pre = GradientPrecursor(_pre_cfg())
    for step in range(8):
        pre.observe(step, _noise_sketch(rng), _LABELS)
    assert len(pre.ring) > 0
    bad = _noise_sketch(rng)
    bad[0, 0] = float("nan")
    assert pre.observe(8, bad, _LABELS) is None
    assert len(pre.ring) == 0   # poisoned history dropped, then refills
    for step in range(9, 15):
        pre.observe(step, _noise_sketch(rng), _LABELS)
    assert len(pre.ring) > 0


# ---------------------------------------------------------------------------
# critical-batch regulator on synthetic telemetry
# ---------------------------------------------------------------------------

def _gns_tele(step, small, big, b=2.0, B=8.0):
    return StepTelemetry(step=step, gns_small_sq=small, gns_big_sq=big,
                         gns_b_small=b, gns_b_big=B)


def test_critical_batch_grows_under_noise_holds_when_flat():
    cfg = GNSConfig(enabled=True, min_batch=2, headroom=2.0, growth=2.0,
                    ema_window=4, warmup_obs=2)
    reg = CriticalBatchRegulator(cfg, full_batch=32, dp_size=2)
    assert reg.batch == 2
    # noise-dominated telemetry: B_noise >> batch -> monotone growth to cap
    seen = [reg.batch]
    for step in range(12):
        reg.observe(_gns_tele(step, small=100.0, big=25.5), 0)
        seen.append(reg.batch)
    assert all(b2 >= b1 for b1, b2 in zip(seen, seen[1:]))
    assert all(b % 2 == 0 for b in seen)
    assert seen[-1] == 32
    # zero-noise telemetry (S_small == S_big -> tr(Sigma)=0): batch holds
    reg2 = CriticalBatchRegulator(cfg, full_batch=32, dp_size=2)
    for step in range(12):
        reg2.observe(_gns_tele(step, small=10.0, big=10.0), 0)
    assert reg2.batch == 2


def test_critical_batch_prefers_per_leaf_vectors():
    cfg = GNSConfig(enabled=True, min_batch=2, headroom=2.0, growth=2.0,
                    ema_window=4, warmup_obs=2)
    reg = CriticalBatchRegulator(cfg, full_batch=16, dp_size=1)
    tele = dataclasses.replace(
        _gns_tele(0, small=200.0, big=51.0),
        per_leaf={"gns_small_sq": np.array([100.0, 100.0], np.float32),
                  "gns_big_sq": np.array([25.5, 25.5], np.float32)},
        leaf_labels=("a", "b"))
    for _ in range(6):
        reg.observe(tele, 0)
    assert reg.est.leaf_b_noise is not None          # fed the vectors
    assert reg.est.leaf_b_noise.shape == (2,)
    assert reg.batch > 2                             # and still grew


def test_critical_batch_state_roundtrip():
    cfg = GNSConfig(enabled=True, min_batch=2, headroom=2.0, growth=2.0,
                    ema_window=4, warmup_obs=2)
    a = CriticalBatchRegulator(cfg, full_batch=32, dp_size=2)
    for step in range(5):
        a.observe(_gns_tele(step, small=100.0, big=25.5), 0)
    b = CriticalBatchRegulator(cfg, full_batch=32, dp_size=2)
    b.load_state_dict(json.loads(json.dumps(a.state_dict())))
    assert b.batch == a.batch
    assert b.est.b_noise == pytest.approx(a.est.b_noise)
    p1 = a.plan(StepTelemetry(), StepPlan(seq_len=8, batch_size=32, lr=1.0))
    p2 = b.plan(StepTelemetry(), StepPlan(seq_len=8, batch_size=32, lr=1.0))
    assert p1.batch_size == p2.batch_size


# ---------------------------------------------------------------------------
# recovery surfaces: per-leaf LR backoff + precursor cool-down
# ---------------------------------------------------------------------------

def _rr():
    return RecoveryRegulator(ladder=(8, 16, 32),
                             cfg=RecoveryConfig(lr_backoff=0.5, lr_floor=0.1))


def test_deepen_lr_blamed_leaf_before_global():
    reg = _rr()
    assert reg.leaf_lr_vector(("a", "b")) is None    # inactive -> None
    reg.deepen_lr("b")
    assert reg.lr_scale == 1.0                       # global untouched
    vec = reg.leaf_lr_vector(("a", "b"))
    assert vec is not None and vec.dtype == np.float32
    assert list(vec) == [1.0, 0.5]
    reg.deepen_lr("b")
    reg.deepen_lr("b")
    reg.deepen_lr("b")
    assert reg.leaf_lr_scales["b"] == pytest.approx(0.1)   # floor holds
    reg.deepen_lr()                                  # no blame -> global
    assert reg.lr_scale == 0.5
    plan = reg.plan(StepTelemetry(), StepPlan(seq_len=32, batch_size=8,
                                              lr=1.0))
    assert plan.lr == pytest.approx(0.5)


def test_precursor_cooldown_is_temporary_and_merges_most_severe():
    reg = _rr()
    reg.precursor_cooldown(0.5, 3)
    reg.precursor_cooldown(0.8, 2)   # weaker: scale keeps 0.5, ttl keeps 3
    assert reg.cool_scale == 0.5 and reg.cool_ttl == 3
    plan = reg.plan(StepTelemetry(), StepPlan(seq_len=32, batch_size=8,
                                              lr=1.0))
    assert plan.lr == pytest.approx(0.5)
    for _ in range(3):
        reg.observe(StepTelemetry(), 0)
    assert reg.cool_ttl == 0 and reg.cool_scale == 1.0
    plan = reg.plan(StepTelemetry(), StepPlan(seq_len=32, batch_size=8,
                                              lr=1.0))
    assert plan.lr == pytest.approx(1.0)             # cool-down expired


def test_recovery_state_roundtrip_including_new_keys():
    reg = _rr()
    reg.deepen_lr("blk0")
    reg.precursor_cooldown(0.25, 5)
    reg.deepen_lr()
    d = json.loads(json.dumps(reg.state_dict()))
    reg2 = _rr()
    reg2.load_state_dict(d)
    assert reg2.state_dict() == reg.state_dict()
    # pre-PR-9 checkpoints (3 legacy keys) still load, new surfaces idle
    reg3 = _rr()
    reg3.load_state_dict({"lr_scale": 0.5, "seq_drop": 1, "data_offset": 4})
    assert reg3.leaf_lr_scales == {} and reg3.cool_ttl == 0
    assert reg3.cool_scale == 1.0


# ---------------------------------------------------------------------------
# train-step wiring
# ---------------------------------------------------------------------------

_MODEL_CFG = None


def _model_cfg():
    global _MODEL_CFG
    if _MODEL_CFG is None:
        _MODEL_CFG = reduced(get_arch("gpt2-117m").model).replace(
            vocab_size=128)
    return _MODEL_CFG


def _step_fixture(gns, seq=32, batch=8):
    cfg = _model_cfg()
    opt = OptimizerConfig(lr=1e-3, schedule="constant", grad_clip=1.0)
    model = model_zoo.build_model(cfg, dtype=jnp.float32, remat="none")
    fn = jax.jit(steps_lib.make_train_step(model, opt, gns=gns),
                 donate_argnums=(0,))
    state = steps_lib.init_train_state(jax.random.PRNGKey(0), cfg, opt)
    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, seq_len=seq, seed=0)
    b = DataPipeline(corpus, batch, model_cfg=cfg).batch_at(0)
    return fn, state, b


def test_gns_off_step_is_bitwise_identical_to_legacy():
    """Acceptance criterion: the default path (gns disabled) must produce
    exactly the legacy step — same metrics, same params — whether the
    config is absent or present-but-disabled."""
    outs = []
    for gns in (None, GNSConfig(enabled=False)):
        fn, state, batch = _step_fixture(gns)
        state, metrics = fn(state, batch, np.float32(1e-3), np.float32(1.0))
        outs.append((jax.device_get(state["params"]), jax.device_get(metrics)))
    (p0, m0), (p1, m1) = outs
    assert set(m0) == set(m1)
    assert not any(k.startswith("gns") for k in m0)
    for k in m0:
        np.testing.assert_array_equal(np.asarray(m0[k]), np.asarray(m1[k]))
    for a, b in zip(jax.tree_util.tree_leaves(p0),
                    jax.tree_util.tree_leaves(p1)):
        np.testing.assert_array_equal(a, b)


def test_gns_step_emits_consistent_measurement():
    fn, state, batch = _step_fixture(GNSConfig(enabled=True, shards=4,
                                               precursor_window=12))
    base_fn, base_state, _ = _step_fixture(None)
    state, m = fn(state, batch, np.float32(1e-3), np.float32(1.0))
    base_state, bm = base_fn(base_state, batch, np.float32(1e-3),
                             np.float32(1.0))
    # scalar pair present, finite, and shard-consistent (B=8, k=4 -> b=2)
    assert float(m["gns_b_big"]) == 8.0 and float(m["gns_b_small"]) == 2.0
    small, big = float(m["gns_small_sq"]), float(m["gns_big_sq"])
    assert np.isfinite(small) and np.isfinite(big)
    assert small >= big > 0.0     # shard means are noisier than the mean
    # per-leaf vectors sum to the global pair; sketch has the fixed shape
    leaf_small = np.asarray(m["leaf_gns_small_sq"])
    n_leaves = leaf_small.shape[0]
    assert float(np.sum(leaf_small)) == pytest.approx(small, rel=1e-5)
    assert np.asarray(m["leaf_gns_sketch"]).shape == (n_leaves, 16)
    # measuring must not change what is learned: the combined gradient is
    # the token-weighted shard mean, so the realized loss matches the
    # single-pass step closely
    assert float(m["loss"]) == pytest.approx(float(bm["loss"]), rel=1e-4)


def test_gns_sketch_shape_tracks_precursor_dim():
    gns = GNSConfig(enabled=True, shards=2, precursor_window=6,
                    precursor_dim=8)
    fn, state, batch = _step_fixture(gns)
    _, m = fn(state, batch, np.float32(1e-3), np.float32(1.0))
    assert np.asarray(m["leaf_gns_sketch"]).shape[1] == 8


# ---------------------------------------------------------------------------
# end-to-end: trainer wiring, jsonl round-trip, composed checkpoint/resume
# ---------------------------------------------------------------------------

def _e2e_tc(steps=16, seq=64, batch=8, gns=None, regulators=None,
            ckpt_dir="", slw=False, interval=0):
    if regulators is None:
        regulators = (RegulatorSpec("seqlen"),) * slw + (RegulatorSpec("lr"),)
    cfg = _model_cfg()
    return TrainConfig(
        model=cfg,
        optimizer=OptimizerConfig(
            lr=1e-3, min_lr=1e-5, schedule="token_cosine", warmup_steps=4,
            warmup_tokens=4 * batch * seq, total_steps=steps,
            total_tokens=steps * batch * seq),
        slw=SLWConfig(pacing="linear", start_seq_len=8,
                      duration_steps=steps // 2, round_multiple=8,
                      max_buckets=4),
        regulators=regulators,
        gns=gns or GNSConfig(),
        seq_len=seq, global_batch=batch, remat="none", eval_interval=0,
        checkpoint_interval=interval, checkpoint_dir=ckpt_dir)


def test_metrics_jsonl_per_leaf_roundtrip(tmp_path):
    """Satellite: the --metrics-jsonl stream carries the one-time
    leaf_labels header plus per-step per-leaf vectors, and
    read_metrics_jsonl (the parse-back bench_gns reuses) restores them."""
    path = str(tmp_path / "metrics.jsonl")
    gns = GNSConfig(enabled=True, shards=4, precursor_window=6)
    res = train(_e2e_tc(steps=8, gns=gns), quiet=True,
                hooks=[MetricsJsonlHook(path)])
    assert res.steps == 8
    labels, rows = read_metrics_jsonl(path)
    assert len(rows) == 8
    assert labels and all(isinstance(l, str) for l in labels)
    # the header is written exactly once
    with open(path) as f:
        raw = [json.loads(line) for line in f]
    assert sum("leaf_labels" in r for r in raw) == 1
    for r in rows:
        assert {"gns_small_sq", "gns_big_sq", "gns_b_small",
                "gns_b_big"} <= set(r)
        pl = r["per_leaf"]
        assert pl["gns_small_sq"].shape == (len(labels),)
        assert pl["gns_small_sq"].dtype == np.float32
        assert pl["gns_sketch"].shape == (len(labels), gns.precursor_dim)
    # and the streamed scalars replay into the same estimate the live
    # regulator would have formed
    est = GNSEstimator(ema_window=8, warmup_obs=2)
    for r in rows:
        est.update(r["gns_small_sq"], r["gns_big_sq"],
                   r["gns_b_small"], r["gns_b_big"])
    assert est.ready and np.isfinite(est.b_noise)


def test_metrics_jsonl_default_rows_unchanged_without_gns(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    train(_e2e_tc(steps=4), quiet=True, hooks=[MetricsJsonlHook(path)])
    labels, rows = read_metrics_jsonl(path)
    assert labels == () and len(rows) == 4
    for r in rows:
        assert not any(k.startswith("gns_") for k in r)
        assert "per_leaf" not in r


def test_critical_batch_composes_with_slw_through_resume(tmp_path):
    """Acceptance criterion: CriticalBatchRegulator + SLW + token-wise LR
    through a mid-warmup checkpoint/restore — the resumed run continues
    the batch/seq/LR trajectory instead of restarting any schedule."""
    gns = GNSConfig(enabled=True, shards=4, precursor_window=0,
                    warmup_obs=2, ema_window=8)
    regs = (RegulatorSpec(kind="seqlen"), RegulatorSpec(kind="lr"),
            RegulatorSpec(kind="critical_batch"))

    def tc(d):
        # one config for every run (schedule constants must not depend on
        # the run length — the interrupted run is cut short via max_steps,
        # not a different schedule)
        return _e2e_tc(steps=24, gns=gns, regulators=regs, slw=True,
                       ckpt_dir=str(tmp_path / d), interval=8)

    full = train(tc("full"), quiet=True)
    assert full.steps == 24 and not full.diverged
    # the measured warmup actually engaged: batch started below full and
    # is monotone non-decreasing
    assert full.batch_history[0] < 8
    assert all(b2 >= b1 for b1, b2 in
               zip(full.batch_history, full.batch_history[1:]))

    interrupted = train(tc("part"), max_steps=16, quiet=True)
    resumed = train(tc("part"), resume=True, quiet=True)
    assert resumed.restored_from_step == 16
    assert resumed.steps == 24
    # every schedule continued: the resumed trajectory matches the
    # uninterrupted run step for step (batch from the restored estimator
    # EMAs, seqlen from SLW, lr from the token-wise schedule)
    tail = slice(16, 24)
    assert resumed.batch_history == full.batch_history[tail]
    assert resumed.seqlen_history == full.seqlen_history[tail]
    assert np.allclose(resumed.lr_history, full.lr_history[tail])
    assert interrupted.batch_history == full.batch_history[:16]


def test_gns_off_trainer_has_no_gns_surface(tmp_path):
    res = train(_e2e_tc(steps=4), quiet=True)
    assert res.precursor_events == []


@pytest.mark.slow
def test_precursor_leads_detector_on_injected_fault_matrix():
    """The bench scenario as a regression test: a sub-threshold episode at
    12 then an overt spike at 22 — the precursor must fire from measured
    gradient directions strictly before the detector, and a clean arm
    stays silent."""
    from benchmarks.common import bench_config
    steps = 32

    def tc():
        return dataclasses.replace(
            bench_config(slw=False, steps=steps, lr=1e-3),
            gns=GNSConfig(enabled=True, shards=4))

    rec = RecoveryConfig(policy=RetryPolicy(max_retries=3))
    res = train(tc(), quiet=True, recovery=rec,
                fault_injector=FaultInjector.from_cli(
                    "spike@12:2.0,spike@22:32.0", seed=0))
    assert res.steps == steps
    assert res.precursor_events, "precursor silent on the fault matrix"
    assert res.recovery_events, "detector never fired"
    pre_step = int(res.precursor_events[0].split("@")[1].split("(")[0])
    det_step = int(res.recovery_events[0].split("@")[1].split("(")[0])
    assert 12 < pre_step < det_step   # fired in the window, before the spike

    clean = train(tc(), quiet=True, recovery=rec)
    assert clean.precursor_events == [] and clean.rollbacks == 0


def test_build_stack_critical_batch_kind():
    tc = _e2e_tc(gns=GNSConfig(enabled=True),
                 regulators=(RegulatorSpec(kind="lr"),
                             RegulatorSpec(kind="critical_batch")))
    stack = build_stack(tc, dp_size=2)
    assert "critical_batch" in stack
    assert stack["critical_batch"].dp_size == 2
    # round-trips through ControllerState with the rest of the stack
    cs = ControllerState.from_host(json.loads(json.dumps(
        stack.controller_state(3, 3 * 512, {}).to_host())))
    stack2 = build_stack(tc, dp_size=2)
    stack2.load_controller_state(cs)
    assert stack2["critical_batch"].state_dict() == \
        stack["critical_batch"].state_dict()
