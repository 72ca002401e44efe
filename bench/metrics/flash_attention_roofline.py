"""Share of its roofline that flash attention reaches in training.

The least time the chip could take for the attention work the window's
steps need (``bench/work/flash_attention.py``: forward and backward of
every layer of every step, from its shapes), over the summed device time
of the forward, delta, dQ and dK/dV kernels in the trace."""
from bench.work import flash_attention as work


def read(ctx):
    if ctx.kind != "train" or ctx.trace is None or not ctx.peaks:
        return None
    spent = ctx.trace.kernel_s(work.KERNELS)
    if spent <= 0:
        return None
    d, item = ctx.dims, ctx.cell.dtype.itemsize
    least = sum(d.n_layers * work.least_seconds(rows, d.n_heads, seq,
                                                d.head_dim, item, ctx.peaks)
                for rows, seq in ctx.steps)
    return 100.0 * least / spent
