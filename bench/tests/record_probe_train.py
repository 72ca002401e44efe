#!/usr/bin/env python3
"""Record ``data/probe_train.xplane.pb`` on one TPU chip, for
``test_program_trace.py``:

  python3 bench/tests/record_probe_train.py [--out PATH]

GPT-2 117M cut to 2 layers, as the benchmark's configuration runs it
(float32, flash attention, remat full), 8 rows at S = 256 with no
warm-up of the length.  Two steps compile and warm the program; then two
``Trainer.run_step`` calls are traced, each under ``bench.step`` inside
``bench.window``, as the harness's window runs them.
"""
import argparse
import dataclasses
import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

ARGV = ["--batch", "8", "--seq", "256", "--steps", "100", "--warmup", "4",
        "--eval-interval", "0", "--ckpt-interval", "0"]


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--out", default=str(Path(__file__).parent / "data"
                                        / "probe_train.xplane.pb"))
    args = p.parse_args()

    import jax
    from bench.harness.cell import load_cell
    from bench.harness.spans import Spans
    from bench.harness.train import build_trainer

    if jax.devices()[0].platform != "tpu":
        print("record_probe_train: no TPU; nothing was recorded",
              file=sys.stderr)
        return 1
    cell = load_cell(ROOT, "gpt2-117m.train-slw")
    cell = dataclasses.replace(
        cell, config={**cell.config, "n_layer": 2},
        traffic={**cell.traffic, "rows": 8, "seq": 256, "argv": ARGV})
    trainer = build_trainer(cell, seed=0)
    for _ in range(2):
        trainer.run_step()
    spans = Spans(True)
    tmp = tempfile.mkdtemp(prefix="probe-train-")
    try:
        jax.profiler.start_trace(tmp)
        with spans("bench.window"):
            for _ in range(2):
                with spans("bench.step"):
                    trainer.run_step()
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True)
        shutil.copy(path, args.out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"recorded {args.out} ({os.path.getsize(args.out)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
