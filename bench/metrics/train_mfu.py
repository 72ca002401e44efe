"""Model FLOP utilisation of the training window.

The model operations of every step in the window, each at its own
sequence length (the architecture's step work,
``bench/work/<model_type>_step.py``: for GPT-2 6N plus the causal half of
attention, no recomputation), over the window's seconds and the chip's
bf16 peak."""


def read(ctx):
    if ctx.kind != "train" or not ctx.peaks or not ctx.steps:
        return None
    step_flops = ctx.cell.arch("work").train_step_flops
    flops = sum(step_flops(ctx.dims, rows, seq) for rows, seq in ctx.steps)
    return 100.0 * flops / ctx.window_s / ctx.peaks["bf16_flops_per_s"]
