"""A tiny benchmark tree, made from files alone, for tests on the CPU.

It holds its own ``BENCHMARK.json``, configuration, traffic and limits
files under a temporary root, and copies of the repository's
architecture files, metric readers and peaks; the harness finds all of it
by name, as it finds the real cells."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY = {
    "source": "https://huggingface.co/openai-community/gpt2",
    "model_type": "gpt2", "n_layer": 2, "n_embd": 128, "n_head": 4,
    "n_inner": None, "n_positions": 128, "n_ctx": 128, "vocab_size": 16384,
    "layer_norm_epsilon": 1e-5, "activation_function": "gelu_new",
    "reduced": ["n_layer", "n_embd", "n_head", "n_positions", "vocab_size"],
    "assumed": [],
    "program": {
        "dtype": "float32", "matmul_precision": "default",
        "attn_backend": "flash", "decode_backend": "kernel",
        "remat": "full",
    },
}

TRAFFIC = {
    "tiny-slw": {
        "kind": "train", "rows": 4, "seq": 32, "cycle_steps": 6,
        "ref_rows_per_block": 2,
        "argv": ["--batch", "4", "--seq", "32", "--steps", "100000",
                 "--slw", "--start-seq", "8", "--duration", "4",
                 "--max-buckets", "3", "--warmup", "4", "--lr", "1e-3",
                 "--eval-interval", "0", "--ckpt-interval", "0"]},
}

# Set from CPU readings of this tiny tree, as the chip's limits are set
# from chip readings at the cells' sizes: float32 on the CPU reads loss
# 1e-7, gradient 1e-6, change 7e-6; the bfloat16 control 3e-5, 5e-4, 5e-4.
LIMITS = {
    "tiny.tiny-slw": {"loss_gap": {"limit": 3e-6},
                      "grad_gap": {"limit": 5e-5},
                      "update_gap": {"limit": 1e-4}},
}


def make_root(tmp: Path) -> Path:
    """Write the tiny tree under ``tmp`` and return it."""
    root = Path(tmp)
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "limits").mkdir()
    for part in ("metrics", "reference", "programs", "work"):
        shutil.copytree(REPO / "bench" / part, root / "bench" / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "bench" / "peaks.json", root / "bench" / "peaks.json")
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(TINY))
    for name, mix in TRAFFIC.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    for cell, lim in LIMITS.items():
        (root / "bench" / "limits" / f"{cell}.json").write_text(
            json.dumps(lim))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": TINY["source"],
                         "file": "bench/configs/tiny.json",
                         "reduced": TINY["reduced"], "why": "CPU test"}]
    bench["workloads"] = [
        {"name": f"tiny.{t}", "config": "tiny", "traffic": t, "chips": 1,
         "why": "CPU test"} for t in TRAFFIC]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.tiny-slw"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
