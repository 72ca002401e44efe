"""Fault injector semantics + the crash/corruption recovery drills.

The headline drill is the issue's crash-mid-checkpoint satellite: kill the
writer between the tmp write and the atomic rename, verify ``latest_step``
never sees the partial directory, and verify a resume restores the prior
step with regulator schedules bitwise identical to an uninterrupted run.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step
from repro.configs import get_arch, reduced
from repro.configs.base import OptimizerConfig, SLWConfig, TrainConfig
from repro.distributed.fault_injection import (FaultInjector, InjectedCrash,
                                               parse_faults)
from repro.launch.train import Trainer, train


def _tc(steps=12, seq=64, batch=4, ckpt_dir="", interval=4):
    cfg = reduced(get_arch("gpt2-117m").model).replace(vocab_size=128)
    return TrainConfig(
        model=cfg,
        optimizer=OptimizerConfig(
            lr=2e-3, min_lr=1e-5, schedule="token_cosine",
            warmup_steps=4, warmup_tokens=4 * batch * seq,
            total_steps=steps, total_tokens=steps * batch * seq),
        slw=SLWConfig(pacing="linear", start_seq_len=8,
                      duration_steps=steps // 2, round_multiple=8,
                      max_buckets=4),
        seq_len=seq, global_batch=batch, remat="none",
        eval_interval=0, checkpoint_interval=interval,
        checkpoint_dir=ckpt_dir)


# ---------------------------------------------------------------------------
# spec parsing
# ---------------------------------------------------------------------------

def test_parse_faults_roundtrip():
    specs = parse_faults("nan_grad@12, spike@20:8.0,crash@30:post_tmp,"
                         "stall@8:0.25")
    assert [s.kind for s in specs] == ["nan_grad", "spike", "crash", "stall"]
    assert [s.step for s in specs] == [12, 20, 30, 8]
    assert specs[1].arg == "8.0" and specs[2].arg == "post_tmp"
    # str() round-trips through the parser
    assert parse_faults(",".join(str(s) for s in specs)) == specs
    assert parse_faults("") == ()


@pytest.mark.parametrize("bad", [
    "bogus@3",            # unknown kind
    "nan_grad@x",         # malformed step
    "nan_grad",           # missing step
    "crash@5:mid_write",  # unknown crash point
])
def test_parse_faults_rejects(bad):
    with pytest.raises(ValueError):
        parse_faults(bad)


# ---------------------------------------------------------------------------
# deterministic placement + fire-once
# ---------------------------------------------------------------------------

def _toy_state():
    return {"params": {"w": jnp.ones((4, 8)), "b": jnp.ones(16),
                       "count": jnp.int32(3)},
            "opt": {"m": jnp.zeros(5)}}


def test_poison_params_is_seeded_and_minimal():
    a = FaultInjector(seed=7).poison_params(_toy_state(), step=12)
    b = FaultInjector(seed=7).poison_params(_toy_state(), step=12)
    mask_a = [np.isnan(np.asarray(x, np.float64)).ravel()
              for x in jax.tree_util.tree_leaves(a["params"])]
    mask_b = [np.isnan(np.asarray(x, np.float64)).ravel()
              for x in jax.tree_util.tree_leaves(b["params"])]
    assert sum(m.sum() for m in mask_a) == 1  # exactly one element
    for ma, mb in zip(mask_a, mask_b):
        np.testing.assert_array_equal(ma, mb)  # same element both times
    c = FaultInjector(seed=8).poison_params(_toy_state(), step=12)
    mask_c = np.concatenate([np.isnan(np.asarray(x, np.float64)).ravel()
                             for x in jax.tree_util.tree_leaves(c["params"])])
    assert mask_c.sum() == 1
    # int leaves are never poisoned
    assert int(a["params"]["count"]) == 3


def test_scale_params_touches_only_params():
    out = FaultInjector().scale_params(_toy_state(), step=3, factor=4.0)
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                  4.0 * np.ones((4, 8)))
    np.testing.assert_array_equal(np.asarray(out["opt"]["m"]), np.zeros(5))


def test_pre_step_fires_each_spec_once():
    class Dummy:
        step = 5
        state = _toy_state()

    inj = FaultInjector(parse_faults("spike@5:2.0"), seed=0)
    tr = Dummy()
    inj.pre_step(tr)
    assert inj.fired == ["spike@5:2.0"]
    w1 = np.asarray(tr.state["params"]["w"]).copy()
    inj.pre_step(tr)  # replayed step index after a rollback: no re-fire
    assert inj.fired == ["spike@5:2.0"]
    np.testing.assert_array_equal(np.asarray(tr.state["params"]["w"]), w1)


def test_maybe_crash_matches_point_and_step():
    inj = FaultInjector(parse_faults("crash@30:post_rename"))
    inj.maybe_crash("post_tmp", 30)     # wrong point: no-op
    inj.maybe_crash("post_rename", 29)  # wrong step: no-op
    with pytest.raises(InjectedCrash):
        inj.maybe_crash("post_rename", 30)
    inj.maybe_crash("post_rename", 30)  # fire-once


# ---------------------------------------------------------------------------
# crash-mid-checkpoint (the issue's satellite drill)
# ---------------------------------------------------------------------------

def test_crash_between_tmp_and_rename_resumes_exactly(tmp_path):
    d_clean = str(tmp_path / "clean")
    d_crash = str(tmp_path / "crash")
    clean = train(_tc(ckpt_dir=d_clean), quiet=True)
    assert clean.steps == 12

    inj = FaultInjector(parse_faults("crash@8:post_tmp"), seed=0)
    with pytest.raises(InjectedCrash):
        train(_tc(ckpt_dir=d_crash), quiet=True, fault_injector=inj)
    # the partial tmp dir is on disk but latest_step never trusts it
    assert os.path.isdir(os.path.join(d_crash, "tmp.8"))
    assert latest_step(d_crash) == 4

    res = train(_tc(ckpt_dir=d_crash), resume=True, quiet=True)
    assert res.restored_from_step == 4
    assert res.steps == 12
    # regulator schedules resume bitwise identically to the clean run
    assert res.seqlen_history == clean.seqlen_history[4:]
    assert res.batch_history == clean.batch_history[4:]
    assert res.lr_history == clean.lr_history[4:]
    np.testing.assert_array_equal(np.asarray(res.loss_history),
                                  np.asarray(clean.loss_history[4:]))


def test_crash_after_rename_leaves_valid_checkpoint(tmp_path):
    d = str(tmp_path / "ck")
    inj = FaultInjector(parse_faults("crash@8:post_rename"), seed=0)
    with pytest.raises(InjectedCrash):
        train(_tc(ckpt_dir=d), quiet=True, fault_injector=inj)
    # the rename completed: step 8 is valid and restorable
    assert latest_step(d) == 8
    tr = Trainer(_tc(ckpt_dir=d))
    assert tr.resume() == 8


def test_bitflip_quarantines_and_falls_back(tmp_path):
    d = str(tmp_path / "ck")
    res = train(_tc(ckpt_dir=d), quiet=True)
    assert res.steps == 12
    mgr_steps = sorted(int(n.split("_")[1]) for n in os.listdir(d)
                       if n.startswith("step_"))
    assert mgr_steps == [4, 8, 12]  # keep=3

    inj = FaultInjector(seed=3)
    target = inj.corrupt_checkpoint(d)  # newest (12)
    assert "step_000000000012" in target
    assert any(f.startswith("bitflip@12") for f in inj.fired)

    tr = Trainer(_tc(ckpt_dir=d))
    assert tr.resume() == 8  # fell back past the corrupt newest
    assert [q[0] for q in tr.ckpt.quarantined] == [12]
    assert os.path.isdir(os.path.join(d, "corrupt.step_000000000012"))
    assert not os.path.isdir(os.path.join(d, "step_000000000012"))


def test_corrupt_checkpoint_requires_a_checkpoint(tmp_path):
    with pytest.raises(ValueError):
        FaultInjector().corrupt_checkpoint(str(tmp_path))
