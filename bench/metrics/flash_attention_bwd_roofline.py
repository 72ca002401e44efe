"""Share of its roofline that the flash-attention backward kernels reach in
training.

The least time of the backward of every call of every step in the window
(``bench/work/flash_attention.py``: forward and backward, less the
forward; the calls of a step are the architecture's ``attention_calls``),
over the device seconds of the custom calls named
``flash_attention_delta``, ``flash_attention_dq`` and
``flash_attention_dkv``."""
from bench.work import flash_attention as work

KERNELS = ("flash_attention_delta", "flash_attention_dq",
           "flash_attention_dkv")


def read(ctx):
    if ctx.kind != "train" or ctx.trace is None or not ctx.peaks:
        return None
    spent = ctx.trace.kernel_s(KERNELS)
    if spent <= 0:
        return None
    calls = ctx.cell.arch("work").attention_calls
    item = ctx.cell.dtype.itemsize
    least = sum(work.step_least_seconds(calls(ctx.dims, rows, seq), item,
                                        ctx.peaks, "bwd")
                for rows, seq in ctx.steps)
    return 100.0 * least / spent
