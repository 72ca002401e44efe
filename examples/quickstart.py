"""Quickstart: train a small GPT-2-style model with the paper's joint
recipe — Sequence Length Warmup composed with batch-size and LR warmup
through the regulator control plane.

    PYTHONPATH=src python examples/quickstart.py [--steps 120]

What you should see: the per-step sequence length ramping 8 -> 256 on the
paper's linear pacing function while the batch ramps up alongside it, the
loss-ratio tracker staying spike-free, and validation perplexity (always
full-length) dropping.
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.configs import get_arch, reduced
from repro.configs.base import (BatchWarmupConfig, OptimizerConfig,
                                RegulatorSpec, SLWConfig, TrainConfig)
from repro.launch.train import train


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=120)
    p.add_argument("--preset", default="tiny", choices=["tiny", "100m"],
                   help="'100m' trains the real gpt2-117m config "
                   "(slow on CPU; sized for a real accelerator)")
    args = p.parse_args()

    if args.preset == "100m":
        model = get_arch("gpt2-117m").model
        seq, batch = 1024, 16
    else:
        model = reduced(get_arch("gpt2-117m").model).replace(
            n_layers=3, d_model=96, d_ff=384, vocab_size=512)
        seq, batch = 256, 8

    steps = args.steps
    tc = TrainConfig(
        model=model,
        optimizer=OptimizerConfig(
            lr=6e-3, min_lr=2e-4, schedule="token_cosine",
            warmup_steps=15, warmup_tokens=15 * batch * seq,
            total_steps=steps, total_tokens=steps * batch * seq),
        slw=SLWConfig(pacing="linear", start_seq_len=8,
                      duration_steps=steps // 3, round_multiple=8,
                      max_buckets=12),
        batch_warmup=BatchWarmupConfig(start_batch=batch // 2,
                                       warmup_tokens=steps * batch * seq // 8),
        # batch warmup composes with SLW through the regulator stack (the
        # paper's joint recipe: short sequences make the warming batch/LR
        # safe)
        regulators=(RegulatorSpec("seqlen"), RegulatorSpec("batch_warmup"),
                    RegulatorSpec("lr")),
        seq_len=seq, global_batch=batch, remat="none", eval_interval=20)

    res = train(tc, quiet=False)
    print("\n== quickstart summary ==")
    print(f"steps={res.steps} tokens={res.tokens} "
          f"compiles={res.n_compiles} (bounded by the bucket ladder)")
    print(f"seqlen schedule: {res.seqlen_history[0]} -> "
          f"{res.seqlen_history[-1]}")
    print(f"batch schedule:  {res.batch_history[0]} -> "
          f"{res.batch_history[-1]}")
    print(f"stability: {res.tracker_summary}")
    print(f"val ppl: {[f'{p:.1f}' for _, p in res.val_ppl_history]}")


if __name__ == "__main__":
    main()
