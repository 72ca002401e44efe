"""SLW's batch transform: apply a step's sequence length to a host batch.

Two modes:

* ``truncate`` — paper-faithful (§4): keep the first ``s_t`` tokens of each
  pre-indexed full-length sequence; the rest of the step's tokens are dropped
  (the paper accepts this and notes the index-recording alternative).
* ``repack`` — beyond-paper: reshape ``(B, S) -> (B * S//s_t, s_t)`` so no
  token is dropped and tokens/step stays constant during warmup.  This
  removes the "fewer tokens per step" side of the recipe (token-wise LR decay
  then coincides with step-wise), trading data-order fidelity for constant
  throughput.

All slicing happens host-side on numpy arrays *before* device transfer, so a
warmup step moves only ``B * s_t`` tokens over PCIe/ICI, not the full batch.
The schedule that picks ``s_t`` is ``core.regulators.SeqLenRegulator``.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def apply_seqlen(batch: Dict[str, np.ndarray], s_t: int,
                 mode: str = "truncate") -> Tuple[Dict[str, np.ndarray], int]:
    """Apply sequence length ``s_t`` to a host-side batch.

    What ``RegulatorStack.apply`` runs for a ``StepPlan``.  Sequence-axis
    keys are truncated/repacked; a vision-patch prefix (``patch_embeds``)
    is passed through untouched (SLW warms up only the text segment).
    Returns (batch, tokens_this_step), prefix tokens included in the
    count.
    """
    seq_keys = [k for k in ("tokens", "labels", "loss_mask", "frames")
                if k in batch]
    full = batch[seq_keys[0]].shape[1]
    s_t = min(s_t, full)
    out = dict(batch)
    if mode == "truncate" or s_t == full:
        for k in seq_keys:
            out[k] = batch[k][:, :s_t]
    elif mode == "repack":
        folds = full // s_t
        for k in seq_keys:
            v = batch[k][:, :folds * s_t]
            out[k] = v.reshape((v.shape[0] * folds, s_t) + v.shape[2:])
        if "patch_embeds" in out:
            out["patch_embeds"] = np.repeat(out["patch_embeds"], folds,
                                            axis=0)
    else:
        raise ValueError(f"unknown SLW mode {mode!r}")
    tokens = int(np.prod(out[seq_keys[0]].shape[:2]))
    if "patch_embeds" in out:
        tokens += int(out["patch_embeds"].shape[0]
                      * out["patch_embeds"].shape[1])
    return out, tokens
