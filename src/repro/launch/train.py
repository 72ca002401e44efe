"""End-to-end trainer on the composable regulator control plane.

Usable as a library (`train(cfg, ...)` — the benchmarks drive tiny replicas
of the paper's experiments through this exact loop) and as a CLI:

  PYTHONPATH=src python -m repro.launch.train --arch gpt2-117m --reduced \
      --steps 200 --batch 16 --seq 256 --slw --batch-warmup --duration 100

The loop is the paper's *joint* recipe end to end:
  regulator stack plans the step (seqlen bucket + batch size + LR +
  grad-clip scale, from shared StepTelemetry) -> batch (full length,
  pre-indexed) row-sliced and truncated/repacked host-side -> jitted train
  step (one executable per (seqlen, batch) bucket) -> loss-ratio +
  Adam-variance telemetry fed back into the stack -> token-budget
  termination,
with checkpoint/restart (one unified ControllerState), drain-on-signal and
a straggler watchdog as hooks around it.

The `Trainer` class is the control plane host: eval, checkpointing, drain,
the watchdog and telemetry recording are `TrainerHook`s, so deployments can
add/remove concerns without forking the loop; `train(tc, ...)` stays as the
thin functional wrapper every benchmark/test entry point uses.
"""
from __future__ import annotations

import argparse
import dataclasses
import math
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.configs import get_arch, reduced as reduce_cfg
from repro.configs.base import (
    BatchWarmupConfig, GNSConfig, OptimizerConfig, RegulatorSpec, SLWConfig,
    TrainConfig)
from repro.core import LossRatioTracker
from repro.core import telemetry as telemetry_lib
from repro.core.recovery import (RecoveryConfig, RecoveryHook,
                                 RecoveryRegulator, RollbackController)
from repro.core.regulators import (ControllerState, RegulatorStack, StepPlan,
                                   StepTelemetry, build_stack)
from repro.checkpoint import CheckpointManager, migrate_host_state
from repro.compile_cache import enable_compile_cache
from repro.data import DataPipeline, SyntheticCorpus
from repro.distributed.fault_injection import (FaultInjectionHook,
                                               FaultInjector)
from repro.distributed.fault_tolerance import (DrainSignal, RetryPolicy,
                                               StepWatchdog)
from repro.launch import steps as steps_lib
from repro.models import model_zoo


@dataclass
class TrainResult:
    steps: int = 0
    tokens: int = 0
    diverged: bool = False
    drained: bool = False
    wall_time_s: float = 0.0
    loss_history: List[float] = field(default_factory=list)
    loss_ratios: List[float] = field(default_factory=list)
    lr_history: List[float] = field(default_factory=list)
    seqlen_history: List[int] = field(default_factory=list)
    batch_history: List[int] = field(default_factory=list)
    var_max_history: List[float] = field(default_factory=list)
    var_l1_history: List[float] = field(default_factory=list)
    grad_norm_history: List[float] = field(default_factory=list)
    val_ppl_history: List[Tuple[int, float]] = field(default_factory=list)
    tracker_summary: Dict[str, float] = field(default_factory=dict)
    watchdog_summary: Dict[str, float] = field(default_factory=dict)
    n_compiles: int = 0
    restored_from_step: Optional[int] = None
    # divergence-aware recovery accounting (core.recovery)
    rollbacks: int = 0
    recovery_events: List[str] = field(default_factory=list)
    faults_fired: List[str] = field(default_factory=list)
    # gradient-direction early warnings (repro.gns.precursor)
    precursor_events: List[str] = field(default_factory=list)


# ---------------------------------------------------------------------------
# hooks
# ---------------------------------------------------------------------------

class TrainerHook:
    """Cross-cutting trainer concern.  ``on_step_start`` runs before the
    plan is made (and may call ``trainer.request_drain()``);
    ``on_step_end`` runs after the regulators observed the completed step.
    When ``trainer.stopping`` is set (divergence with stop_on_nan), interval
    work (eval/checkpoint) should be skipped."""

    def on_run_start(self, tr: "Trainer") -> None:
        pass

    def on_step_start(self, tr: "Trainer") -> None:
        pass

    def on_step_end(self, tr: "Trainer", tele: StepTelemetry, plan: StepPlan,
                    metrics: Dict[str, float]) -> None:
        pass

    def on_run_end(self, tr: "Trainer") -> None:
        """Normal-completion epilogue (summaries, final checkpoint)."""

    def close(self) -> None:
        """Resource cleanup only — also runs when the loop exits via an
        exception (on_run_end does not: saving checkpoints or summaries
        during unwind would record a state no real preemption could)."""


class DrainHook(TrainerHook):
    """Preemption-safe exit: checkpoint at the next step boundary."""

    def __init__(self, drain: Optional[DrainSignal]):
        self.drain = drain

    def on_step_start(self, tr: "Trainer") -> None:
        if self.drain is not None and self.drain.should_drain:
            tr.request_drain()

    def close(self) -> None:
        # restore whatever handlers preceded this trainer — installed
        # handlers used to leak across Trainer instances and tests
        if self.drain is not None:
            self.drain.uninstall()


class WatchdogHook(TrainerHook):
    def on_step_start(self, tr: "Trainer") -> None:
        tr.watchdog.start()

    def on_step_end(self, tr, tele, plan, metrics) -> None:
        tr.watchdog.stop()

    def on_run_end(self, tr: "Trainer") -> None:
        tr.result.watchdog_summary = tr.watchdog.summary()


class TelemetryHook(TrainerHook):
    """Records the per-step histories and drives the user callback."""

    def __init__(self, callback: Optional[Callable[[int, Dict[str, float]],
                                                   None]] = None):
        self.callback = callback

    def on_step_end(self, tr, tele, plan, metrics) -> None:
        res = tr.result
        res.loss_history.append(tele.loss)
        res.loss_ratios.append(tele.loss_ratio)
        res.lr_history.append(plan.lr)
        res.seqlen_history.append(plan.seq_len)
        res.batch_history.append(plan.batch_size)
        res.var_max_history.append(tele.var_max)
        res.var_l1_history.append(tele.var_l1)
        res.grad_norm_history.append(tele.grad_norm)
        if self.callback is not None:
            self.callback(tele.step, {k: float(v) for k, v in metrics.items()})

    def on_run_end(self, tr: "Trainer") -> None:
        tr.result.tracker_summary = tr.tracker.summary()


class MetricsJsonlHook(TrainerHook):
    """Appends one JSON row per step (StepPlan + StepTelemetry) to a file.

    The ROADMAP's "surface Trainer hooks in the CLI" follow-on: a
    deployment-grade telemetry tap (``--metrics-jsonl PATH``) that records
    exactly what the regulator stack planned and observed, without touching
    the loop.  Rows are flushed per step so a crashed/drained run keeps its
    telemetry up to the last completed step.
    """

    def __init__(self, path: str):
        self.path = path
        self._fh = None
        self._wrote_labels = False

    def on_run_start(self, tr: "Trainer") -> None:
        self._fh = open(self.path, "a", buffering=1)

    def on_step_end(self, tr, tele, plan, metrics) -> None:
        import json
        row = {
            "step": tele.step, "tokens_seen": tele.tokens_seen,
            "loss": tele.loss, "loss_ratio": tele.loss_ratio,
            "grad_norm": tele.grad_norm, "var_max": tele.var_max,
            "var_l1": tele.var_l1,
            "plan": {"seq_len": plan.seq_len, "batch_size": plan.batch_size,
                     "lr": plan.lr,
                     "grad_clip_scale": plan.grad_clip_scale},
        }
        # optional scalar channels: written only when the step emitted
        # them (finite), so pre-PR-9 row shapes are unchanged
        for k in ("grad_norm_clipped", "gns_small_sq", "gns_big_sq",
                  "gns_b_small", "gns_b_big"):
            v = getattr(tele, k)
            if math.isfinite(v):
                row[k] = v
        if tele.per_leaf is not None:
            # per-leaf vectors in leaf_labels order; the labels themselves
            # are written once (first per-leaf row), not per step
            row["per_leaf"] = telemetry_lib.per_leaf_to_host(tele.per_leaf)
            if not self._wrote_labels:
                row["leaf_labels"] = list(tele.leaf_labels)
                self._wrote_labels = True
        self._fh.write(json.dumps(row) + "\n")

    def on_run_end(self, tr: "Trainer") -> None:
        self.close()

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None


class EvalHook(TrainerHook):
    """Full-length validation every ``eval_interval`` steps."""

    def __init__(self, eval_batch: int = 8, quiet: bool = True):
        self.eval_batch = eval_batch
        self.quiet = quiet

    def on_step_end(self, tr, tele, plan, metrics) -> None:
        interval = tr.tc.eval_interval
        if tr.stopping or not interval or tr.step % interval != 0:
            return
        ev = tr.pipeline.eval_batch(tr.step // interval, self.eval_batch)
        ppl = float(np.exp(min(float(tr.eval_fn(tr.state["params"], ev)),
                               30.0)))
        tr.result.val_ppl_history.append((tr.step, ppl))
        if not self.quiet:
            print(f"step {tr.step} tokens {tr.tokens_seen} "
                  f"loss {tele.loss:.4f} val_ppl {ppl:.2f} "
                  f"seqlen {plan.seq_len} batch {plan.batch_size} "
                  f"lr {plan.lr:.2e}", flush=True)


class CheckpointHook(TrainerHook):
    """Periodic + final checkpointing (the drain path saves on its own)."""

    def on_step_end(self, tr, tele, plan, metrics) -> None:
        if tr.stopping or tr.ckpt is None or not tr.tc.checkpoint_interval:
            return
        if tr.step % tr.tc.checkpoint_interval == 0:
            tr.save_checkpoint()

    def on_run_end(self, tr: "Trainer") -> None:
        if tr.ckpt is not None and not tr.result.drained:
            tr.save_checkpoint()


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------

class Trainer:
    """Host-side training control plane around the regulator stack.

    Owns model/optimizer state, the data pipeline, the regulator stack, the
    loss-ratio tracker and the checkpoint manager; everything else (eval,
    checkpoint cadence, drain, watchdog, telemetry) is a hook.
    """

    def __init__(self, tc: TrainConfig, *, dp_size: int = 1,
                 eval_batch: int = 8, stop_on_nan: bool = True,
                 drain: Optional[DrainSignal] = None,
                 callback: Optional[Callable[[int, Dict[str, float]],
                                             None]] = None,
                 fail_at_step: Optional[int] = None, quiet: bool = True,
                 hooks: Optional[List[TrainerHook]] = None,
                 recovery: Optional[RecoveryConfig] = None,
                 fault_injector: Optional[FaultInjector] = None):
        """`hooks` are appended after the default hook set (drain, watchdog,
        telemetry, eval, checkpoint).  ``recovery`` enables the in-process
        divergence rollback controller (core.recovery); ``fault_injector``
        arms deterministic fault injection for this run
        (distributed.fault_injection)."""
        self.tc = tc
        self.dp_size = max(dp_size, 1)
        self.stop_on_nan = stop_on_nan
        self.fail_at_step = fail_at_step
        cfg = tc.model
        self.model = model_zoo.build_model(cfg, dtype=jnp.float32,
                                           remat=tc.remat)
        rng = jax.random.PRNGKey(tc.seed)
        self.state = steps_lib.init_train_state(rng, cfg, tc.optimizer)
        # leaf labels for per-parameter telemetry / per-layer blame: fixed
        # for the run (tree structure never changes), computed once
        self.leaf_labels = telemetry_lib.param_labels(self.state["params"])

        corpus = SyntheticCorpus(vocab_size=cfg.vocab_size,
                                 seq_len=tc.seq_len, seed=tc.seed)
        self.pipeline = DataPipeline(corpus, tc.global_batch, model_cfg=cfg)
        self.stack: RegulatorStack = build_stack(
            tc, dp_size=self.dp_size,
            warmup_steps_hint=tc.optimizer.warmup_steps,
            prefix_tokens=cfg.prefix_tokens)
        self.tracker = LossRatioTracker()
        self.watchdog = StepWatchdog()
        self.ckpt = (CheckpointManager(tc.checkpoint_dir, tc.keep_checkpoints)
                     if tc.checkpoint_dir else None)

        self.step_fn = jax.jit(steps_lib.make_train_step(self.model,
                                                         tc.optimizer,
                                                         gns=tc.gns),
                               donate_argnums=(0,))
        self.eval_fn = jax.jit(lambda p, b: self.model.loss(p, b)[1]["loss"])

        self.result = TrainResult()
        self.step = 0
        self.tokens_seen = 0
        self.stopping = False
        self._drain_requested = False
        self._last = StepTelemetry()
        self._seen_shapes = set()
        # set by the fault injector (grad_spike) for the next step only
        self.fault_injector = fault_injector
        self._pending_grad_fault: Optional[Tuple[float, str]] = None

        # divergence-aware recovery: the intervention regulator joins the
        # stack (so its state checkpoints through ControllerState) and the
        # rollback controller rides the hook list
        self.recovery: Optional[RollbackController] = None
        self._recovery_reg: Optional[RecoveryRegulator] = None
        self._ring_dir = ""
        if recovery is not None:
            ladder = (self.stack["seqlen"].curriculum.ladder
                      if "seqlen" in self.stack else (tc.seq_len,))
            self._recovery_reg = RecoveryRegulator(ladder, recovery)
            self.stack.regulators.append(self._recovery_reg)
            self.recovery = RollbackController(recovery)
            self._ring_dir = recovery.ring_dir or (
                os.path.join(tc.checkpoint_dir, "ring")
                if tc.checkpoint_dir else "")

        # `hooks` extends the defaults (it does not replace them — drain/
        # callback/eval would silently stop working otherwise)
        self.hooks: List[TrainerHook] = [
            DrainHook(drain),
            WatchdogHook(),
            TelemetryHook(callback),
            EvalHook(eval_batch=eval_batch, quiet=quiet),
            CheckpointHook(),
        ]
        if self.recovery is not None:
            self.hooks.append(RecoveryHook(self.recovery))
        if fault_injector is not None:
            self.hooks.append(FaultInjectionHook(fault_injector))
        # GNS precursor: direction-sketch early warning, wired into the
        # rollback controller (proactive snapshot + LR cool-down) when
        # recovery is on; pure telemetry otherwise
        if tc.gns.enabled and tc.gns.precursor_window > 0:
            from repro.gns.precursor import GradientPrecursor, PrecursorHook
            self.hooks.append(PrecursorHook(
                GradientPrecursor(tc.gns), controller=self.recovery,
                cool=(tc.gns.precursor_cooldown_factor,
                      tc.gns.precursor_cooldown_steps)))
        self.hooks += list(hooks or [])

    # -- control signals -----------------------------------------------------
    def request_drain(self) -> None:
        self._drain_requested = True

    # -- unified controller state (checkpoint payload) -----------------------
    def controller_state(self) -> ControllerState:
        return self.stack.controller_state(self.step, self.tokens_seen,
                                           self.tracker.state_dict())

    def load_controller_state(self, cs: ControllerState) -> None:
        self.step = cs.step
        self.tokens_seen = cs.tokens_seen
        if cs.tracker:
            self.tracker.load_state_dict(cs.tracker)
        self.stack.load_controller_state(cs)

    def save_checkpoint(self) -> None:
        if self.ckpt is None:
            return
        # the controller dict is the single source of truth for host state
        # (step/tokens_seen live inside it; the manifest's own "step" field
        # covers human inspection)
        self.ckpt.save(self.step, self.state,
                       {"controller": self.controller_state().to_host()})

    def resume(self) -> Optional[int]:
        """Restore the latest checkpoint, if any.  Returns its step."""
        if self.ckpt is None:
            return None
        like = steps_lib.abstract_train_state(self.tc.model,
                                              self.tc.optimizer)
        got_step, got_state, host = self.ckpt.restore_latest(like)
        if got_step is None:
            return None
        self.state = got_state
        host = migrate_host_state(host)
        self.load_controller_state(ControllerState.from_host(
            host["controller"]))
        self.result.restored_from_step = got_step
        # a drained run spilled its in-run rollback ring next to the
        # checkpoint — refill it so recovery resumes with the same restore
        # points it had when the preemption landed
        if self.recovery is not None and self._ring_dir \
                and os.path.isdir(self._ring_dir):
            self.recovery.ring.load(self._ring_dir, like)
        return got_step

    # -- one training step ---------------------------------------------------
    def run_step(self) -> Tuple[StepTelemetry, StepPlan, Dict[str, Any]]:
        """One step.  Its host phases are profiler spans (``train.step``
        around ``train.plan``, ``train.batch``, ``train.launch``,
        ``train.wait`` and ``train.observe``), recorded while a
        ``jax.profiler`` trace is running and close to free otherwise."""
        with TraceAnnotation("train.step"):
            tele = dataclasses.replace(self._last, step=self.step,
                                       tokens_seen=self.tokens_seen)
            with TraceAnnotation("train.plan"):
                plan = self.stack.plan(tele)
            with TraceAnnotation("train.batch"):
                # the recovery regulator's data offset skips past a data
                # window the rollback controller blamed for a divergence
                offset = (self._recovery_reg.data_offset
                          if self._recovery_reg is not None else 0)
                batch = self.pipeline.batch_at(self.step + offset)
                batch, tokens_step = self.stack.apply(batch, plan)

                shape_key = tuple(sorted((k, v.shape)
                                         for k, v in batch.items()))
                if shape_key not in self._seen_shapes:
                    self._seen_shapes.add(shape_key)
                    self.result.n_compiles += 1

            with TraceAnnotation("train.launch"):
                # grad_spike fault: a one-step (n_leaves,) multiplier on the
                # raw per-leaf gradients (None on clean steps keeps the
                # common trace)
                grad_scale = None
                if self._pending_grad_fault is not None \
                        and self.fault_injector is not None:
                    factor, substr = self._pending_grad_fault
                    self._pending_grad_fault = None
                    grad_scale = self.fault_injector.grad_scale_vector(
                        self.leaf_labels, self.step, factor, substr)
                # optional runtime vectors: only passed when active, so the
                # common trace (no fault, no per-leaf backoff) stays
                # byte-identical
                extra: Dict[str, Any] = {}
                if grad_scale is not None:
                    extra["grad_scale"] = grad_scale
                if self._recovery_reg is not None \
                        and self._recovery_reg.leaf_lr_scales:
                    extra["leaf_lr"] = self._recovery_reg.leaf_lr_vector(
                        self.leaf_labels)
                self.state, metrics = self.step_fn(
                    self.state, batch, np.float32(plan.lr),
                    np.float32(plan.grad_clip_scale), **extra)
            with TraceAnnotation("train.wait"):
                loss = float(metrics["loss"])
            with TraceAnnotation("train.observe"):
                # per-leaf vectors (telemetry_level == "per_leaf") ride
                # StepTelemetry, not the scalar metrics dict the hooks float()
                metrics, per_leaf = telemetry_lib.split_metrics(metrics)
                ratio = (self.tracker.update(loss) if math.isfinite(loss)
                         else float("inf"))
                nan = float("nan")
                post = dataclasses.replace(
                    tele, loss=loss, loss_ratio=ratio,
                    grad_norm=float(metrics["grad_norm"]),
                    grad_norm_clipped=float(metrics.get("grad_norm_clipped",
                                                        nan)),
                    var_max=float(metrics["var_max"]),
                    var_l1=float(metrics["var_l1"]),
                    gns_small_sq=float(metrics.get("gns_small_sq", nan)),
                    gns_big_sq=float(metrics.get("gns_big_sq", nan)),
                    gns_b_small=float(metrics.get("gns_b_small", nan)),
                    gns_b_big=float(metrics.get("gns_b_big", nan)),
                    per_leaf=per_leaf,
                    leaf_labels=(self.leaf_labels if per_leaf is not None
                                 else ()))
                self.stack.observe(post, tokens_step)
                self.step += 1
                self.tokens_seen += tokens_step
                self._last = post
        return post, plan, metrics

    # -- the loop -------------------------------------------------------------
    def run(self, max_steps: Optional[int] = None) -> TrainResult:
        opt_cfg = self.tc.optimizer
        total_steps = opt_cfg.total_steps or 10**9
        total_tokens = opt_cfg.total_tokens or 10**18
        if max_steps is not None:
            total_steps = min(total_steps, self.step + max_steps)

        t_start = time.time()
        for h in self.hooks:
            h.on_run_start(self)
        try:
            while self.step < total_steps and self.tokens_seen < total_tokens:
                for h in self.hooks:
                    h.on_step_start(self)
                if self._drain_requested:
                    self.save_checkpoint()
                    # spill the in-run rollback ring next to the checkpoint:
                    # the restore points survive the preemption (resume()
                    # refills the ring on --recover)
                    if self.recovery is not None and self._ring_dir:
                        self.recovery.ring.save(self._ring_dir)
                    self.result.drained = True
                    break
                if (self.fail_at_step is not None
                        and self.step == self.fail_at_step):
                    raise RuntimeError(f"injected failure at step {self.step}")

                tele, plan, metrics = self.run_step()

                if not math.isfinite(tele.loss):
                    self.result.diverged = True
                    self.stopping = self.stop_on_nan
                for h in self.hooks:
                    h.on_step_end(self, tele, plan, metrics)
                if self.stopping:
                    break
        except BaseException:
            # crash path: resource cleanup only — no checkpoints/summaries
            # during unwind (a real preemption couldn't write them either,
            # and self.state may hold donated buffers)
            for h in self.hooks:
                h.close()
            raise
        for h in self.hooks:
            h.on_run_end(self)
        for h in self.hooks:
            h.close()
        self.result.steps = self.step
        self.result.tokens = self.tokens_seen
        self.result.wall_time_s = time.time() - t_start
        return self.result


def train(tc: TrainConfig,
          max_steps: Optional[int] = None,
          eval_batch: int = 8,
          resume: bool = False,
          stop_on_nan: bool = True,
          drain: Optional[DrainSignal] = None,
          callback: Optional[Callable[[int, Dict[str, float]], None]] = None,
          fail_at_step: Optional[int] = None,
          quiet: bool = True,
          dp_size: int = 1,
          hooks: Optional[List[TrainerHook]] = None,
          recovery: Optional[RecoveryConfig] = None,
          fault_injector: Optional[FaultInjector] = None) -> TrainResult:
    """Run the training loop on the local device(s). Returns full telemetry.

    Thin wrapper over :class:`Trainer` so existing entry points keep
    working.  `fail_at_step` injects a crash (fault-tolerance tests/drills);
    `fault_injector` injects the richer step-indexed fault matrix and
    `recovery` turns on divergence rollback.
    """
    trainer = Trainer(tc, dp_size=dp_size, eval_batch=eval_batch,
                      stop_on_nan=stop_on_nan, drain=drain, callback=callback,
                      fail_at_step=fail_at_step, quiet=quiet, hooks=hooks,
                      recovery=recovery, fault_injector=fault_injector)
    if resume:
        trainer.resume()
    return trainer.run(max_steps=max_steps)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def build_config(args) -> TrainConfig:
    spec = get_arch(args.arch)
    cfg = reduce_cfg(spec.model) if args.reduced else spec.model
    if args.vocab:
        cfg = cfg.replace(vocab_size=args.vocab)
    slw = SLWConfig(
        enabled=args.slw, pacing=args.pacing, start_seq_len=args.start_seq,
        duration_steps=args.duration, round_multiple=args.round_multiple,
        mode=args.slw_mode, max_buckets=args.max_buckets)
    opt = OptimizerConfig(
        lr=args.lr, min_lr=args.min_lr, warmup_steps=args.warmup,
        warmup_tokens=args.warmup * args.batch * args.seq,
        total_steps=args.steps,
        total_tokens=args.tokens or args.steps * args.batch * args.seq,
        schedule=args.schedule, grad_clip=args.clip,
        optimizer=args.optimizer, decay_mask=args.decay_mask,
        agc_clip=args.agc,
        telemetry_level=("per_leaf" if args.per_leaf_telemetry
                         else "scalar"))
    bw = BatchWarmupConfig(enabled=args.batch_warmup,
                           start_batch=max(args.batch // 8, 1),
                           warmup_tokens=(args.tokens or args.steps
                                          * args.batch * args.seq) // 20)
    gns = GNSConfig(enabled=args.gns or args.gns_batch,
                    shards=args.gns_shards,
                    precursor_window=args.gns_precursor_window,
                    headroom=args.gns_headroom)
    tc = TrainConfig(model=cfg, optimizer=opt, slw=slw, batch_warmup=bw,
                     gns=gns,
                     seq_len=args.seq, global_batch=args.batch,
                     seed=args.seed, remat=args.remat,
                     eval_interval=args.eval_interval,
                     checkpoint_interval=args.ckpt_interval,
                     checkpoint_dir=args.ckpt_dir)
    # adaptive regulators opt in via the explicit stack: the auto-derived
    # schedules first, the telemetry-driven ones after (order matters — the
    # LR throttle multiplies the scheduled LR).
    extra = []
    if args.grad_noise_batch:
        extra.append(RegulatorSpec(kind="grad_noise_batch"))
    if args.gns_batch:
        extra.append(RegulatorSpec(kind="critical_batch"))
    if args.var_lr_throttle:
        extra.append(RegulatorSpec(kind="var_lr_throttle"))
    if extra:
        from repro.core.regulators import auto_specs
        tc = dataclasses.replace(tc,
                                 regulators=auto_specs(tc) + tuple(extra))
    return tc


def build_parser() -> argparse.ArgumentParser:
    """The train CLI's arguments (``build_config`` reads the parsed result)."""
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="gpt2-117m")
    p.add_argument("--reduced", action="store_true",
                   help="reduced same-family config (CPU-trainable)")
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--tokens", type=int, default=0)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--vocab", type=int, default=0)
    p.add_argument("--lr", type=float, default=6e-4)
    p.add_argument("--min-lr", type=float, default=1e-5)
    p.add_argument("--warmup", type=int, default=20)
    p.add_argument("--clip", type=float, default=1.0)
    p.add_argument("--schedule", default="token_cosine",
                   choices=["token_cosine", "step_cosine", "constant"])
    p.add_argument("--optimizer", default="adamw",
                   choices=["adamw", "sm3", "shampoo"],
                   help="inner optimizer of the gradient-transform chain")
    p.add_argument("--decay-mask", default="all", choices=["all", "std"],
                   help="'std' exempts 1-D/scalar leaves (norm gains, "
                        "biases) from weight decay; 'all' is the legacy "
                        "decay-everything behavior")
    p.add_argument("--agc", type=float, default=0.0,
                   help="adaptive gradient clipping threshold (per-leaf "
                        "grad/param norm ratio; 0 disables)")
    p.add_argument("--per-leaf-telemetry", action="store_true",
                   help="per-parameter-group telemetry vectors (var_max/"
                        "grad/update/param norms per labeled leaf) — feeds "
                        "per-layer blame in regulators and recovery")
    p.add_argument("--slw", action="store_true")
    p.add_argument("--pacing", default="linear",
                   choices=["linear", "root", "two_stage", "variance_gated",
                            "constant"])
    p.add_argument("--start-seq", type=int, default=8)
    p.add_argument("--duration", type=int, default=0)
    p.add_argument("--round-multiple", type=int, default=8)
    p.add_argument("--max-buckets", type=int, default=16)
    p.add_argument("--slw-mode", default="truncate",
                   choices=["truncate", "repack"])
    p.add_argument("--batch-warmup", action="store_true",
                   help="composes with --slw (the paper's joint recipe)")
    p.add_argument("--grad-noise-batch", action="store_true",
                   help="adaptive batch sizing from grad-norm noise")
    p.add_argument("--gns", action="store_true",
                   help="gradient-noise-scale measurement: per-shard grad "
                        "norms inside the jitted step -> unbiased B_noise "
                        "estimate + direction-sketch spike precursor "
                        "(repro.gns)")
    p.add_argument("--gns-shards", type=int, default=4,
                   help="emulated data-parallel shards for the GNS pair "
                        "(largest divisor of the realized batch is used)")
    p.add_argument("--gns-batch", action="store_true",
                   help="B_noise-measured batch warmup (critical_batch "
                        "regulator; implies --gns)")
    p.add_argument("--gns-precursor-window", type=int, default=12,
                   help="direction-sketch ring length for the spike "
                        "precursor (0 disables the precursor)")
    p.add_argument("--gns-headroom", type=float, default=2.0,
                   help="grow the batch while B_noise > headroom * batch")
    p.add_argument("--var-lr-throttle", action="store_true",
                   help="LR backoff while Adam variance-max spikes")
    p.add_argument("--dp-size", type=int, default=0,
                   help="data-parallel size for batch quantization "
                        "(0 = jax.device_count())")
    p.add_argument("--remat", default="none",
                   choices=["none", "full", "dots"])
    p.add_argument("--seed", type=int, default=1234)
    p.add_argument("--eval-interval", type=int, default=50)
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--ckpt-interval", type=int, default=100)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--metrics-jsonl", default="",
                   help="append per-step StepPlan/StepTelemetry rows to "
                        "this JSONL file (telemetry TrainerHook)")
    p.add_argument("--recover", action="store_true",
                   help="divergence-aware recovery: detect NaN/spike/"
                        "variance excursions, roll back to an in-run "
                        "snapshot, intervene (LR backoff -> seq clamp -> "
                        "data skip)")
    p.add_argument("--max-rollbacks", type=int, default=3,
                   help="in-process rollback budget before hard failure")
    p.add_argument("--inject-faults", default="",
                   help="deterministic fault matrix, e.g. "
                        "'nan_grad@12,spike@20:8.0,crash@30:post_tmp,"
                        "stall@8:0.25' (kind@step[:arg], comma-separated)")
    p.add_argument("--inject-seed", type=int, default=0,
                   help="seed for fault placement (which leaf/byte)")
    return p


def main(argv=None) -> int:
    enable_compile_cache()
    args = build_parser().parse_args(argv)
    tc = build_config(args)
    drain = DrainSignal()
    dp = args.dp_size or jax.device_count()
    hooks = ([MetricsJsonlHook(args.metrics_jsonl)]
             if args.metrics_jsonl else None)
    recovery = (RecoveryConfig(policy=RetryPolicy(
        max_retries=args.max_rollbacks)) if args.recover else None)
    injector = (FaultInjector.from_cli(args.inject_faults,
                                       seed=args.inject_seed)
                if args.inject_faults else None)
    res = train(tc, resume=args.resume, drain=drain, quiet=False, dp_size=dp,
                hooks=hooks, recovery=recovery, fault_injector=injector)
    print(f"\ndone: steps={res.steps} tokens={res.tokens} "
          f"diverged={res.diverged} compiles={res.n_compiles}")
    print("stability:", res.tracker_summary)
    print("watchdog:", res.watchdog_summary)
    if recovery is not None or injector is not None:
        print(f"recovery: rollbacks={res.rollbacks} "
              f"events={res.recovery_events} faults={res.faults_fired}")
    return 0 if not res.diverged else 1


if __name__ == "__main__":
    raise SystemExit(main())
