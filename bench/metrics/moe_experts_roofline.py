"""Share of its roofline that the held experts' grouped products reach in
training.

The least time of the held experts' products, forward and backward, of
every step in the window (``bench/work/moe_experts.py``; the calls of a
step are the architecture's ``expert_calls``), over the device seconds of
the custom calls named ``moe_gmm`` and ``moe_tgmm``.  The rows counted
are each step's expected load (each token chooses ``top_k`` of the
router's published experts), not the rows that step routed here, which
the program reports as ``moe_held_rows``: the share reads high by the
ratio of the expected rows to the routed ones.  Under ``remat full`` the
forward runs twice, so recomputation lowers this share.  None where no
such kernel ran, or the architecture has no experts."""
from bench.work import moe_experts as work


def read(ctx):
    if ctx.kind != "train" or ctx.trace is None or not ctx.peaks:
        return None
    calls = getattr(ctx.cell.arch("work"), "expert_calls", None)
    spent = ctx.trace.kernel_s(work.KERNELS)
    if calls is None or spent <= 0:
        return None
    item = ctx.cell.dtype.itemsize
    least = sum(work.step_least_seconds(calls(ctx.dims, rows, seq), item,
                                        ctx.peaks)
                for rows, seq in ctx.steps)
    return 100.0 * least / spent
