"""Share of the training window in which no operation ran on the device.

1 - (union of the device's operation intervals in the window) / window,
from the profiler trace.  Moves ``train_tokens_per_s``."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.device_ops:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)
