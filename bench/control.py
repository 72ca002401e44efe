#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from; not a benchmark run.

  python3 bench/control.py --workload <cell> --seeds 1,2,3 \
      [--what program,bfloat16,int8,half_batch]

For each seed, in one process, each against the float32 reference over
the runs that set-up checks (``bench/harness/train.py``):

* ``program``: the program's own readings, set-up's own path;
* ``bfloat16`` and ``int8``: the controls, the reference computed in
  bfloat16, or with int8 matrix products, put in the program's place;
* ``half_batch``: the program with half of each batch left out (its mean
  taken over the rest).

A state left unchanged reads 1 by the comparison's measure and needs no
run.  Prints one JSON line per seed with every number (the worst, and each
run's own), and the cell's limits.  The benchmark's own runs never run
this.
"""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def half_batch(trainer):
    """Fault: the step sees the first half of each batch's rows."""
    step = trainer.step_fn

    def run(state, batch, *a, **kw):
        half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
        return step(state, half, *a, **kw)

    trainer.step_fn = run


def unchanged_state(trainer):
    """Fault: the step returns the state it was given."""
    import jax
    import jax.numpy as jnp
    step = trainer.step_fn

    def run(state, batch, *a, **kw):
        keep = jax.tree_util.tree_map(jnp.copy, state)
        _, metrics = step(state, batch, *a, **kw)
        return keep, metrics

    trainer.step_fn = run


WHAT = ("program", "bfloat16", "int8", "half_batch")


def train_readings(cell, seed, what=WHAT):
    from bench.harness import program
    from bench.harness import train as tr

    def program_runs(faults):
        trainer = tr.build_trainer(cell, seed)
        for f in faults:
            f(trainer)
        opt_cfg = trainer.tc.optimizer
        runs = tr.checked_runs(trainer, int(cell.traffic.get("cycle_steps",
                                                               0)))
        program.free(trainer.state)
        return runs, opt_cfg

    def reading(runs, refs):
        worst, per_run = tr.numbers(runs, refs)
        return {"worst": worst, "runs": per_run}

    out = {}
    runs, opt_cfg = program_runs(())
    refs = tr.reference_runs(cell, seed, runs, opt_cfg)
    if "program" in what:
        out["program"] = reading(runs, refs)
    for prec in ("bfloat16", "int8"):
        if prec not in what:
            continue
        low = tr.reference_runs(cell, seed, runs, opt_cfg, prec)
        control = [dict(r, steps=[dict(s, loss=x) for s, x in
                                   zip(r["steps"], f["losses"])],
                        grad=f["grad"], delta=f["delta"])
                   for r, f in zip(runs, low)]
        out[f"control_{prec}"] = reading(control, refs)
    if "half_batch" in what:
        runs, opt_cfg = program_runs((half_batch,))
        out["half_batch"] = reading(
            runs, tr.reference_runs(cell, seed, runs, opt_cfg))
    return out


def main(argv=None) -> int:
    import argparse
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--what", default=",".join(WHAT))
    args = p.parse_args(argv)
    what = tuple(args.what.split(","))
    unknown = set(what) - set(WHAT)
    if unknown:
        p.error(f"unknown readings {sorted(unknown)} (want {WHAT})")
    from bench.harness.cell import load_cell
    from bench.harness.runner import configure_compile_cache
    cell = load_cell(ROOT, args.workload)
    import jax
    if jax.devices()[0].platform == "tpu":
        configure_compile_cache(ROOT)
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "limits": cell.limits}), flush=True)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        got = train_readings(cell, seed, what)
        print(json.dumps({"seed": seed, "seconds": time.perf_counter() - t0,
                          **got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
