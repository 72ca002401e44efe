"""Training cells: the program's ``Trainer``, driven by ``run_step``.

Set-up builds one trainer from the train CLI's own parser (the argv of the
traffic file) and puts the benchmark's seeded weights and rows into it.
The numbers ``correct`` compares are read from steps of that trainer, each
a run from the seeded state S0:

* for an SLW cell, one step in every bucket of the ramp after the first:
  the controller state of the bucket's first step is loaded (walked once
  on the trainer's own regulator stack, as the program's
  ``predict_trajectory`` replays it), the step runs from a copy of S0, and
  its copy is dropped.  These steps also warm every bucket's program;
* then the first three steps of the ramp, from S0 itself.

A cell of one sequence length runs the three steps and ``warm_steps``
more.  An SLW cell then restores the controller state of step 0, in
set-up and at the end of each ramp in the window, so the window runs
whole ramps back to back and its mix of sequence lengths does not depend
on how many steps fit in it.

The window is whole steps (whole ramps for an SLW cell) until ``seconds``
have passed.  ``run_step`` reads the loss back, so each step has ended on
the device when it returns.  Once the window has closed and the peak
memory is read, the trainer is freed and the plain reference follows
every checked run from the same weights and rows.
"""
from __future__ import annotations

import copy
import dataclasses
import math
import time
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import compare, program, weights
from bench.harness import trace as trace_mod
from bench.harness.cell import Cell
from bench.harness.spans import Spans
from bench.reference import common

N_CHECK_STEPS = 3


class Feed:
    """The trainer's data: seeded random tokens, full length, every row of
    every batch different.  ``batch_at`` hands out batch 0, 1, 2, ... in
    turn, whatever step the trainer asks for; ``n`` is the next one."""

    def __init__(self, seed: int, rows: int, seq: int, vocab: int):
        self.seed, self.rows, self.seq, self.vocab = seed, rows, seq, vocab
        self.n = 0

    def batch(self, i: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([self.seed, i])
        t = rng.integers(0, self.vocab, (self.rows, self.seq + 1),
                         dtype=np.int32)
        return {"tokens": t[:, :-1], "labels": t[:, 1:]}

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        b = self.batch(self.n)
        self.n += 1
        return b


def build_trainer(cell: Cell, seed: int):
    from repro.launch import train as train_cli
    from repro.optim.transforms import build_optimizer

    argv = (list(cell.traffic["argv"])
            + ["--remat", cell.program["remat"], "--seed",
               str(seed % 2 ** 31)])
    tc = train_cli.build_config(train_cli.build_parser().parse_args(argv))
    tc = dataclasses.replace(tc, model=program.model_config(cell))
    trainer = train_cli.Trainer(tc, dp_size=1)
    program.free(trainer.state)
    params = weights.make(seed, cell)
    trainer.state = {"params": params,
                     "opt": build_optimizer(tc.optimizer).init(params),
                     "step": jnp.zeros((), jnp.int32)}
    trainer.pipeline = Feed(seed, cell.traffic["rows"], cell.traffic["seq"],
                            cell.dims.vocab)
    return trainer


def bucket_starts(trainer, cycle: int) -> List[Tuple[int, Dict[str, Any]]]:
    """(seq, controller state) at the first step of each bucket of the
    ramp, walked on the trainer's own stack with the calm telemetry of
    ``repro.core.regulators.predict_trajectory``; the stack is left where
    it was.  The steps that load these states check that they plan the
    same length."""
    from repro.core.regulators import StepTelemetry
    cs0 = copy.deepcopy(trainer.controller_state().to_host())
    out: List[Tuple[int, Dict[str, Any]]] = []
    tokens = 0
    for k in range(cycle):
        tele = StepTelemetry(step=k, tokens_seen=tokens, var_max=1.0,
                             var_l1=1.0, grad_norm=1.0)
        cs = copy.deepcopy(trainer.stack.controller_state(
            k, tokens, {}).to_host())
        plan = trainer.stack.plan(tele)
        if not out or plan.seq_len != out[-1][0]:
            out.append((plan.seq_len, cs))
        n = plan.batch_size * plan.seq_len
        trainer.stack.observe(tele, n)
        tokens += n
    load_state(trainer, cs0)
    return out


def load_state(trainer, host: Dict[str, Any]) -> None:
    from repro.core.regulators import ControllerState
    trainer.load_controller_state(
        ControllerState.from_host(copy.deepcopy(host)))


def drive(trainer, n_steps: int, keep_delta: bool) -> Dict[str, Any]:
    """``n_steps`` steps through ``run_step``: each loss and the batch it
    was on, the first gradient's leaf norms from the Adam state after one
    step, and (with ``keep_delta``) the leaf norms of the change."""
    b1 = trainer.tc.optimizer.beta1
    p0 = (jax.tree_util.tree_map(jnp.copy, trainer.state["params"])
          if keep_delta else None)
    steps, grad = [], None
    for k in range(n_steps):
        batch = trainer.pipeline.n
        post, plan, _ = trainer.run_step()
        steps.append({"loss": post.loss, "seq": plan.seq_len,
                      "rows": plan.batch_size, "lr": plan.lr,
                      "clip_scale": plan.grad_clip_scale, "batch": batch})
        if k == 0:
            m = trainer.state["opt"]["adam"]["m"]
            grad = [n / (1.0 - b1) for n in common.leaf_norms(m)]
    delta = None
    if keep_delta:
        delta = common.diff_norms(trainer.state["params"], p0)
        program.free(p0)
    return {"steps": steps, "grad": grad, "delta": delta}


def checked_runs(trainer, cycle: int) -> List[Dict[str, Any]]:
    """Set-up's steps, each run from the seeded state S0: one step at the
    start of every bucket after the first (from a copy of S0), then the
    first three steps (from S0 itself, which the window goes on from)."""
    runs = []
    starts = bucket_starts(trainer, cycle) if cycle else []
    cs0 = copy.deepcopy(trainer.controller_state().to_host())
    s0 = trainer.state
    for seq, cs in starts[1:]:
        trainer.state = jax.tree_util.tree_map(jnp.copy, s0)
        load_state(trainer, cs)
        run = drive(trainer, 1, keep_delta=False)
        if run["steps"][0]["seq"] != seq:
            raise RuntimeError(f"bucket {seq} planned as "
                               f"{run['steps'][0]['seq']} when run")
        program.free(trainer.state)
        runs.append(run)
    trainer.state = s0
    load_state(trainer, cs0)
    runs.insert(0, drive(trainer, N_CHECK_STEPS, keep_delta=True))
    return runs


def reference_runs(cell: Cell, seed: int, runs: List[Dict[str, Any]],
                   opt_cfg, prec: str = "float32") -> List[Dict[str, Any]]:
    """The plain reference over each checked run, from the same seeded
    weights and rows, in ``prec``."""
    dims = cell.dims
    ref = cell.arch("reference").Reference(
        dims, prec, rows_per_block=cell.traffic["ref_rows_per_block"])
    feed = Feed(seed, cell.traffic["rows"], cell.traffic["seq"], dims.vocab)
    p_init = weights.make(seed, cell)
    out = []
    for run in runs:
        params, opt = p_init, common.adamw_init(p_init)
        losses, grad = [], None
        for k, st in enumerate(run["steps"]):
            b = feed.batch(st["batch"])
            tok = b["tokens"][:st["rows"], :st["seq"]]
            lab = b["labels"][:st["rows"], :st["seq"]]
            loss, g = ref.loss_and_grad(params, tok, lab)
            params, clipped, opt = common.adamw_step(
                params, g, opt, lr=st["lr"],
                clip=opt_cfg.grad_clip * st["clip_scale"], b1=opt_cfg.beta1,
                b2=opt_cfg.beta2, eps=opt_cfg.eps,
                weight_decay=opt_cfg.weight_decay)
            losses.append(loss)
            if k == 0:
                grad = common.leaf_norms(clipped)
            program.free(g, clipped)
        delta = (common.diff_norms(params, p_init)
                 if run["delta"] is not None else None)
        program.free(params, opt["m"], opt["v"])
        out.append({"losses": losses, "grad": grad, "delta": delta})
    program.free(p_init)
    return out


def numbers(runs, refs) -> Tuple[Dict[str, float], List[Dict[str, Any]]]:
    """The compared numbers, worst over the runs, and each run's own."""
    return compare.train_numbers(
        [{"seq": r["steps"][0]["seq"],
          "prog_losses": [s["loss"] for s in r["steps"]],
          "ref_losses": f["losses"], "prog_grad": r["grad"],
          "ref_grad": f["grad"], "prog_delta": r["delta"],
          "ref_delta": f["delta"]} for r, f in zip(runs, refs)])


def run(cell: Cell, seed: int, seconds: float, tracing: bool,
        t_start: float, faults: Sequence[Callable] = ()) -> Dict[str, Any]:
    tr = cell.traffic
    spans = Spans(tracing)
    trainer = build_trainer(cell, seed)
    for fault in faults:
        fault(trainer)
    opt_cfg = trainer.tc.optimizer
    cycle = int(tr.get("cycle_steps", 0))
    cs0 = copy.deepcopy(trainer.controller_state().to_host())

    runs = checked_runs(trainer, cycle)
    if cycle:
        load_state(trainer, cs0)
    else:
        for _ in range(tr["warm_steps"]):
            trainer.run_step()

    steps: List[tuple] = []
    state = {"tokens": 0, "failed": 0}

    def window():
        t_open = time.perf_counter()
        n = 0
        with spans(trace_mod.WINDOW_SPAN):
            while True:
                before = trainer.tokens_seen
                with spans("bench.step"):
                    post, plan, _ = trainer.run_step()
                state["tokens"] += trainer.tokens_seen - before
                steps.append((plan.batch_size, plan.seq_len))
                if not math.isfinite(post.loss):
                    state["failed"] += 1
                n += 1
                if cycle and n % cycle == 0:
                    with spans("bench.restore"):
                        load_state(trainer, cs0)
                if (time.perf_counter() - t_open >= seconds
                        and (not cycle or n % cycle == 0)):
                    break
        return t_open, time.perf_counter()

    box: Dict[str, Any] = {}
    if tracing:
        with trace_mod.capture() as box:
            t_open, t_close = window()
    else:
        t_open, t_close = window()
    window_s = t_close - t_open
    peak = program.peak_bytes(program.largest_step(trainer, steps))
    program.free(trainer.state)
    del trainer

    refs = reference_runs(cell, seed, runs, opt_cfg)
    nums, per_run = numbers(runs, refs)
    return {
        "setup_s": t_open - t_start,
        "window_s": window_s,
        "window": (t_open, t_close),
        "end_to_end": {"train_tokens_per_s": state["tokens"] / window_s},
        "attempted": len(steps),
        "failed": state["failed"],
        "memory_peak_bytes": peak,
        "numbers": nums,
        "checked": {"runs": per_run},
        "trace": box.get("trace"),
        "ctx": {"kind": "train", "steps": steps, "tokens": state["tokens"],
                "window_s": window_s},
    }
