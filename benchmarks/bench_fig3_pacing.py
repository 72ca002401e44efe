"""Fig. 3 / Table 6: pacing-duration sweep + the low-cost tuning heuristic.

Sweeps T, detects "significant fluctuation" (>1.3x previous best val ppl)
in the early probe window, and checks the paper's claim that the longest
calm T is a good choice — without full trainings for tuning.
"""
from __future__ import annotations

from typing import List

import numpy as np

from benchmarks.common import Row, bench_config, final_ppl, run_arm
from repro.configs.base import SLWConfig
from repro.core import significant_fluctuation, tune_slw


def run(quick: bool = False) -> List[Row]:
    steps = 80 if quick else 200
    warmup = 15
    lr = 6e-2
    rows: List[Row] = []
    sweep = [warmup, 3 * warmup, 6 * warmup] if quick else \
        [warmup, 2 * warmup, 4 * warmup, 8 * warmup]

    results = {}
    for t_dur in sweep:
        name, res, wall = run_arm(
            f"fig3/slw_T{t_dur}",
            bench_config(slw=True, lr=lr, steps=steps, duration=t_dur,
                         warmup_steps=warmup))
        probe_window = [p for st, p in res.val_ppl_history
                        if st <= 3 * warmup + 10]
        fluct = significant_fluctuation(probe_window)
        results[t_dur] = (res, fluct)
        rows.append((name, wall / max(res.steps, 1) * 1e6,
                     f"final_ppl={final_ppl(res):.1f} "
                     f"early_fluctuation={fluct} "
                     f"spikes={res.tracker_summary['spikes']}"))

    # the tuner itself, driven by short probes only
    def probe(slw_cfg: SLWConfig):
        tc = bench_config(slw=True, lr=lr, steps=3 * warmup,
                          warmup_steps=warmup)
        import dataclasses
        tc = dataclasses.replace(tc, slw=slw_cfg, eval_interval=5)
        from repro.launch.train import train
        res = train(tc, quiet=True, stop_on_nan=False)
        return [p for _, p in res.val_ppl_history]

    tuned = tune_slw(probe, SLWConfig(round_multiple=8, max_buckets=12),
                     warmup_steps=warmup, seqlen_s_grid=(8, 16, 32),
                     t_multiple_range=(1, 8))
    # open-loop replay of the tuned schedule through the regulator stack:
    # the exact warmup token cost, no training needed
    import dataclasses
    from repro.core.regulators import predict_trajectory
    tc_tuned = dataclasses.replace(
        bench_config(slw=True, lr=lr, steps=steps, warmup_steps=warmup),
        slw=SLWConfig(start_seq_len=tuned.seqlen_s,
                      duration_steps=tuned.duration, round_multiple=8,
                      max_buckets=12))
    plans = predict_trajectory(tc_tuned, tuned.duration)
    warmup_tokens = sum(p.batch_size * p.seq_len for p in plans)
    rows.append(("fig3/low_cost_tuner", 0.0,
                 f"chose seqlen_s={tuned.seqlen_s} T={tuned.duration} "
                 f"after {tuned.probe_runs} short probes "
                 f"(no full trainings); predicted warmup cost "
                 f"{warmup_tokens} tokens"))
    return rows
