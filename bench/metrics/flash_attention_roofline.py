"""Share of its roofline that flash attention reaches in training.

The least time the chip could take for the attention work the window's
steps need (``bench/work/flash_attention.py``: forward and backward of
every call of every step, from its shapes; the calls of a step are the
architecture's ``attention_calls``), over the summed device time of the
forward, delta, dQ and dK/dV kernels in the trace."""
from bench.work import flash_attention as work


def read(ctx):
    if ctx.kind != "train" or ctx.trace is None or not ctx.peaks:
        return None
    spent = ctx.trace.kernel_s(work.KERNELS)
    if spent <= 0:
        return None
    calls = ctx.cell.arch("work").attention_calls
    item = ctx.cell.dtype.itemsize
    least = sum(work.step_least_seconds(calls(ctx.dims, rows, seq), item,
                                        ctx.peaks, "both")
                for rows, seq in ctx.steps)
    return 100.0 * least / spent
