"""The program under test, as a configuration file says to run it."""
from __future__ import annotations

import gc
from typing import Dict

import jax
import numpy as np

from bench.harness.cell import Cell


def model_config(cell: Cell):
    """The program's ``ModelConfig`` for the cell's configuration file,
    as ``bench/programs/<model_type>.py`` maps it."""
    return cell.arch("programs").model_config(cell.config_name, cell.dims,
                                              cell.program)


def free(*trees) -> None:
    """Delete the device buffers of ``trees`` now, not at the next GC."""
    for t in trees:
        for x in jax.tree_util.tree_leaves(t):
            if isinstance(x, jax.Array) and not x.is_deleted():
                x.delete()
    gc.collect()


def largest_step(trainer, steps) -> Dict[str, int]:
    """The compiler's memory analysis of the train step at the largest
    (rows, seq) of ``steps``, for the trainer's own jitted step (it comes
    from the persistent cache: the window ran it)."""
    from repro.core.curriculum import apply_seqlen
    lower = getattr(trainer.step_fn, "lower", None)
    if lower is None:  # a test's fault has wrapped the step
        return {}
    rows, seq = max(steps, key=lambda rs: rs[0] * rs[1])
    full = trainer.pipeline.batch(0)
    batch, _ = apply_seqlen({k: v[:rows] for k, v in full.items()}, seq,
                            trainer.stack.seq_mode)
    m = lower(trainer.state, batch, np.float32(0.0),
              np.float32(1.0)).compile().memory_analysis()
    if m is None:
        return {}
    return {"temp": int(m.temp_size_in_bytes),
            "argument": int(m.argument_size_in_bytes),
            "output": int(m.output_size_in_bytes),
            "alias": int(m.alias_size_in_bytes)}


def peak_bytes(step: Dict[str, int]) -> int:
    """The device's peak: the allocator's peak of live buffers, plus the
    temporaries of the largest step program, which the v5e's allocator
    does not count (PERF.md, section 4)."""
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)) + step.get("temp", 0)
