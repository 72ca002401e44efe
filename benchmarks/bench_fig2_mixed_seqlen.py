"""Fig. 2: early long sequences drive instability.

Three arms at aggressive LR: short-only (seqlen 1/8 of full — stable),
full-length (unstable), and mixed 9:1 short/long (spikes cluster at the
long-sequence steps)."""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from benchmarks.common import BATCH, SEQ, Row, bench_config, run_arm
from repro.configs.base import SLWConfig


def run(quick: bool = False) -> List[Row]:
    steps = 60 if quick else 150
    lr = 0.5
    rows: List[Row] = []

    # short-only: constant seqlen = SEQ/8 via a "two_stage" that never switches
    tc_short = bench_config(slw=True, lr=lr, steps=steps, pacing="two_stage")
    tc_short = dataclasses.replace(
        tc_short, slw=SLWConfig(pacing="two_stage",
                                two_stage_short_len=SEQ // 8,
                                two_stage_switch_step=10 * steps,
                                duration_steps=10 * steps,
                                round_multiple=8))
    name, res, wall = run_arm("fig2/short_only", tc_short)
    rows.append((name, wall / max(res.steps, 1) * 1e6,
                 f"spikes={res.tracker_summary['spikes']} "
                 f"max_ratio={res.tracker_summary['max_loss_ratio']:.2f}"))

    name, res_full, wall = run_arm(
        "fig2/full_length", bench_config(slw=False, lr=lr, steps=steps))
    rows.append((name, wall / max(res_full.steps, 1) * 1e6,
                 f"spikes={res_full.tracker_summary['spikes']} "
                 f"max_ratio={res_full.tracker_summary['max_loss_ratio']:.2f}"))

    # mixed: 9 short steps then 1 full step, repeating (paper: 900/100)
    from repro.configs import get_arch
    from repro.launch.train import train
    tc = bench_config(slw=False, lr=lr, steps=steps)
    import repro.launch.train as train_mod
    from repro.core import LossRatioTracker
    from repro.data import DataPipeline, SyntheticCorpus
    import jax, jax.numpy as jnp
    from repro.launch import steps as steps_lib
    from repro.models import model_zoo
    from repro.optim import lr_at
    import time as _t

    model = model_zoo.build_model(tc.model, dtype=jnp.float32, remat="none")
    state = steps_lib.init_train_state(jax.random.PRNGKey(0), tc.model)
    corpus = SyntheticCorpus(vocab_size=tc.model.vocab_size, seq_len=SEQ)
    pipe = DataPipeline(corpus, BATCH, model_cfg=tc.model)
    step_fn = jax.jit(steps_lib.make_train_step(model, tc.optimizer),
                      donate_argnums=(0,))
    tracker = LossRatioTracker()
    long_step_spikes = 0
    t0 = _t.time()
    tokens = 0
    for step in range(steps):
        long_step = (step % 10) == 9
        batch = pipe.batch_at(step)
        s_t = SEQ if long_step else SEQ // 8
        batch = {k: v[:, :s_t] for k, v in batch.items()}
        lr_now = lr_at(tc.optimizer, step, tokens)
        state, metrics = step_fn(state, batch, np.float32(lr_now))
        tokens += BATCH * s_t
        loss = float(metrics["loss"])
        ratio = tracker.update(loss) if np.isfinite(loss) else 10.0
        if ratio > 1.2 and long_step:
            long_step_spikes += 1
    s = tracker.summary()
    rows.append(("fig2/mixed_9short_1long", (_t.time() - t0) / steps * 1e6,
                 f"spikes={s['spikes']} at_long_steps={long_step_spikes} "
                 f"max_ratio={s['max_loss_ratio']:.2f} "
                 f"(paper: spikes cluster at long-seq steps)"))
    return rows
