"""Share of its roofline that the flash-attention forward kernel reaches in
training.

The least time of the forward of every layer of every step in the window
(``bench/work/flash_attention.py``, without the backward), over the device
seconds of the custom calls named ``flash_attention_fwd``.  Under
``remat full`` the forward runs twice, the first pass and the backward's
recompute, so recomputation shows as this share halved."""
from bench.work import flash_attention as work

KERNELS = ("flash_attention_fwd",)


def read(ctx):
    if ctx.kind != "train" or ctx.trace is None or not ctx.peaks:
        return None
    spent = ctx.trace.kernel_s(KERNELS)
    if spent <= 0:
        return None
    d, item = ctx.dims, ctx.cell.dtype.itemsize
    least = sum(d.n_layers * work.least_seconds(
        rows, d.n_heads, seq, d.head_dim, item, ctx.peaks, backward=False)
        for rows, seq in ctx.steps)
    return 100.0 * least / spent
