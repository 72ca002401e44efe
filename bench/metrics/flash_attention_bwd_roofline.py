"""Share of its roofline that the flash-attention backward kernels reach in
training.

The least time of the backward of every layer of every step in the window
(``bench/work/flash_attention.py``: forward and backward, less the
forward), over the device seconds of the custom calls named
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
    d, item = ctx.dims, ctx.cell.dtype.itemsize
    least = sum(d.n_layers * (
        work.least_seconds(rows, d.n_heads, seq, d.head_dim, item, ctx.peaks)
        - work.least_seconds(rows, d.n_heads, seq, d.head_dim, item,
                             ctx.peaks, backward=False))
        for rows, seq in ctx.steps)
    return 100.0 * least / spent
