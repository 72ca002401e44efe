"""Optimizer-chain benchmark: per-arm step time + optimizer-state memory vs
AdamW, chain-vs-legacy parity, and a table1-style stability arm where
AGC + the per-leaf variance throttle survive an aggressive-LR spike regime
that the plain-AdamW baseline does not.

Rows:
  optim/step_<arm>       us/step of the training loop under each chain arm
                         (adamw is the baseline; derived carries the state
                         memory in KiB and the ratio vs adamw)
  optim/parity           max |param delta| between the default chain and the
                         legacy fused clip+AdamW after a shared trajectory
                         (must be 0.0 — the acceptance contract)
  optim/stability_*      spike/divergence stats at aggressive LR: baseline
                         vs AGC + per-leaf var-throttle (the survival arm
                         self-gates in its derived column)
  optim/shampoo_staleness  steps since the last preconditioner eigh refresh
                         as reported by the chain's telemetry — must sweep
                         0..interval-1 and reset on refresh steps
"""
from __future__ import annotations

import dataclasses
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, bench_config, run_arm, stability_row
from repro.configs.base import OptimizerConfig, RegulatorSpec
from repro.optim import (adamw_update, apply_updates, build_optimizer,
                         clip_by_global_norm, init_opt_state)

AGGRESSIVE_LR = 0.5  # calibrated with bench_table1_stability


def _arm_cfg(steps: int, lr: float = 1e-3, **opt_kw):
    tc = bench_config(slw=False, lr=lr, steps=steps)
    return dataclasses.replace(
        tc, optimizer=dataclasses.replace(tc.optimizer, **opt_kw))


def _opt_state_kib(tc) -> float:
    from repro.launch import steps as steps_lib
    abs_state = steps_lib.abstract_train_state(tc.model, tc.optimizer)
    return sum(np.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(abs_state["opt"])) / 1024.0


def _parity_row(steps: int = 30) -> Row:
    """Max |param delta| chain vs legacy after a shared random trajectory."""
    cfg = OptimizerConfig(lr=3e-3, weight_decay=0.01, grad_clip=1.0)
    tx = build_optimizer(cfg)
    rng = np.random.RandomState(0)
    p = {"w": jnp.asarray(rng.randn(64, 64), jnp.float32),
         "b": jnp.asarray(rng.randn(64), jnp.float32)}
    pl, pc = p, p
    ol, oc = init_opt_state(p), tx.init(p)
    t0 = time.time()
    for s in range(steps):
        g = jax.tree_util.tree_map(
            lambda x: jnp.asarray(rng.randn(*x.shape), jnp.float32), p)
        clipped, _ = clip_by_global_norm(g, cfg.grad_clip)
        pl, ol, _ = adamw_update(pl, clipped, ol, jnp.float32(cfg.lr), cfg)
        u, oc, _ = tx.update(g, oc, pc, {"lr": jnp.float32(cfg.lr),
                                         "clip_scale": jnp.float32(1.0)})
        pc = apply_updates(pc, u)
    delta = max(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree_util.tree_leaves(pl),
                                jax.tree_util.tree_leaves(pc)))
    us = (time.time() - t0) / steps * 1e6
    ok = "OK" if delta == 0.0 else "FAIL"
    return ("optim/parity", us,
            f"max_param_delta={delta:.3g} over {steps} steps [{ok}]")


def _shampoo_staleness_row(steps: int = 12, interval: int = 5) -> Row:
    """Drive the shampoo chain and read back the ``shampoo_staleness``
    telemetry: steps since the last eigh refresh.  All blocks share the
    count-keyed refresh cadence, so the scalar must sweep 0..interval-1
    and snap back to 0 on every refresh step."""
    cfg = OptimizerConfig(optimizer="shampoo", shampoo_interval=interval)
    tx = build_optimizer(cfg)
    rng = np.random.RandomState(1)
    p = {"w": jnp.asarray(rng.randn(32, 32), jnp.float32)}
    st = tx.init(p)
    series = []
    t0 = time.time()
    for _ in range(steps):
        g = {"w": jnp.asarray(rng.randn(32, 32), jnp.float32)}
        u, st, tel = tx.update(g, st, p, {"lr": jnp.float32(1e-3),
                                          "clip_scale": jnp.float32(1.0)})
        p = apply_updates(p, u)
        series.append(int(tel["shampoo_staleness"]))
    us = (time.time() - t0) / steps * 1e6
    want = [s % interval for s in range(steps)]
    ok = "OK" if series == want else "FAIL"
    return ("optim/shampoo_staleness", us,
            f"interval={interval} max_staleness={max(series)} "
            f"series_head={series[:interval + 1]} [{ok}]")


def _with_throttle(tc):
    return dataclasses.replace(
        tc, regulators=tc.regulators
        + (RegulatorSpec(kind="var_lr_throttle"),))


def run(quick: bool = False) -> List[Row]:
    steps = 40 if quick else 80
    rows: List[Row] = []

    # -- step time + state memory per chain arm ------------------------------
    arms = [
        ("adamw", {}),
        ("adamw_agc", {"agc_clip": 0.05}),
        ("adamw_per_leaf_tel", {"telemetry_level": "per_leaf"}),
        ("sm3", {"optimizer": "sm3"}),
        ("shampoo", {"optimizer": "shampoo", "shampoo_interval": 10}),
    ]
    base_kib = base_us = None
    for name, opt_kw in arms:
        tc = _arm_cfg(steps, **opt_kw)
        kib = _opt_state_kib(tc)
        _, res, wall = run_arm(name, tc)
        us = wall / max(res.steps, 1) * 1e6
        if name == "adamw":
            base_kib, base_us = kib, us
        rows.append((
            f"optim/step_{name}", us,
            f"opt_state={kib:.0f}KiB ({kib / base_kib:.2f}x adamw) "
            f"step={us / base_us:.2f}x adamw "
            f"final_loss={res.loss_history[-1]:.3f} "
            f"diverged={res.diverged}"))

    # -- chain-vs-legacy parity ----------------------------------------------
    rows.append(_parity_row())

    # -- shampoo preconditioner staleness ------------------------------------
    rows.append(_shampoo_staleness_row())

    # -- stability: AGC + per-leaf throttle vs baseline at aggressive LR -----
    base_tc = _arm_cfg(steps, lr=AGGRESSIVE_LR)
    guard_tc = _with_throttle(_arm_cfg(
        steps, lr=AGGRESSIVE_LR, agc_clip=0.05,
        telemetry_level="per_leaf"))
    _, res_b, wall_b = run_arm("stability_baseline", base_tc)
    rows.append(stability_row("optim/stability_baseline", res_b, wall_b))
    _, res_g, wall_g = run_arm("stability_agc_throttle", guard_tc)
    row = stability_row("optim/stability_agc_throttle", res_g, wall_g)
    # self-gate: the guarded arm must be strictly more stable than baseline
    b, g = res_b.tracker_summary, res_g.tracker_summary
    survived = (not res_g.diverged) and (
        res_b.diverged or g["spikes"] < b["spikes"])
    rows.append((row[0], row[1],
                 row[2] + f" survives_vs_baseline={survived}"))
    return rows
