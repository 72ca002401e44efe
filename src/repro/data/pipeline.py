"""Shard-aware, resumable data pipeline.

The paper's implementation pre-indexes raw text into full-length sequences
once and lets the curriculum truncate per step (Section 4) — re-indexing per
length would be prohibitive at 157B tokens.  This pipeline mirrors that: it
always yields full-length ``(B, S)`` batches; `RegulatorStack.apply`
truncates or repacks them host-side.

Determinism/elasticity: batch `step` is sequence indices
``[step*B_global + r] for r in rank's slice``, pure arithmetic over
(step, dp_rank, dp_size).  Changing dp_size on an elastic restart
re-partitions the stream with no overlap or gap.  The only pipeline state is
the step counter, carried in the checkpoint.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import numpy as np

from repro.configs.base import ModelConfig
from repro.data.synthetic import SyntheticCorpus


@dataclass
class DataPipeline:
    corpus: SyntheticCorpus
    global_batch: int
    dp_rank: int = 0
    dp_size: int = 1
    model_cfg: Optional[ModelConfig] = None

    def __post_init__(self):
        assert self.global_batch % self.dp_size == 0
        self.local_batch = self.global_batch // self.dp_size

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        base = step * self.global_batch + self.dp_rank * self.local_batch
        batch = self.corpus.batch(base, self.local_batch)
        cfg = self.model_cfg
        if cfg is not None and cfg.frontend == "vision_patches":
            # stub frontend: deterministic pseudo patch embeddings
            rng = np.random.Generator(np.random.Philox(key=10_000_019 + step))
            batch["patch_embeds"] = rng.standard_normal(
                (self.local_batch, cfg.prefix_tokens, cfg.d_model),
                dtype=np.float32) * 0.02
        if cfg is not None and cfg.frontend == "audio_frames":
            rng = np.random.Generator(np.random.Philox(key=20_000_003 + step))
            batch["frames"] = rng.standard_normal(
                (self.local_batch, self.corpus.seq_len, cfg.d_model),
                dtype=np.float32) * 0.02
        return batch

    # validation stream: disjoint index space (negative side of the corpus)
    def eval_batch(self, step: int, batch_size: int) -> Dict[str, np.ndarray]:
        base = 1_000_000_000 + step * batch_size
        return self.corpus.batch(base, batch_size)
