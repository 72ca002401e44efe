"""Composable training control plane: regulators -> one StepPlan per step.

The paper's recipe is a *joint* schedule — sequence-length warmup is what
makes the aggressive 8x-batch / 4x-40x-LR recipe trainable — yet the seed
trainer hardcoded SLW and batch warmup as mutually exclusive branches and
computed the LR out of band.  This module turns each schedule into a
``Regulator``: a small host-side state machine that reads the shared
per-step :class:`StepTelemetry` and contributes to the :class:`StepPlan`
(sequence-length bucket, batch size, LR, grad-clip scale) that the trainer
then executes mechanically.

Composition semantics (deliberately simple, so stacks stay predictable):

* ``seq_len`` and ``batch_size`` contributions fold by **min** — any
  regulator may hold the step shorter/smaller, none may exceed the full
  shape (which bounds the jit compile cache exactly as before);
* the LR schedule regulator **sets** the scheduled value; modifiers after
  it in the stack (e.g. :class:`VarianceLRThrottle`) **multiply** it.

Regulators run in stack order for both ``plan`` (before the step) and
``observe`` (after the step, with the step's realized telemetry).  All of
their state round-trips through one :class:`ControllerState`, which is the
single host-state payload the checkpoint carries — a restart mid-warmup
resumes every schedule exactly.

Every regulator plans from the stack's counters in the telemetry
(``tele.step`` / ``tele.tokens_seen``); none keeps a step counter of its
own.  Which regulators run is ``TrainConfig.regulators`` and nothing else.

Beyond-paper clients of the same protocol (see PAPERS.md):

* :class:`VarianceLRThrottle` — Kosson et al.-style warmup-free LR
  control: multiplicatively back off the LR (and grad clip) while the
  Adam variance max spikes above its trailing mean, recover when calm.
* ``critical_batch`` (``repro.gns.regulator``) — batch warmup driven by
  the measured gradient noise scale (Lau et al., *Adaptive Batch Size
  Schedules for Distributed Training of Language Models*).
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.configs.base import (BatchWarmupConfig, OptimizerConfig,
                                RegulatorSpec, SLWConfig, TrainConfig)
from repro.core import pacing
from repro.core.curriculum import apply_seqlen
from repro.optim.schedule import lr_at


# ---------------------------------------------------------------------------
# shared step records
# ---------------------------------------------------------------------------

@dataclass
class StepTelemetry:
    """What every regulator sees.  ``step``/``tokens_seen`` are the exact
    host-side counters; the float fields are the *last completed* step's
    observations when planning (NaN before the first step) and the current
    step's observations in ``observe``.

    ``per_leaf`` (opt-in via ``OptimizerConfig.telemetry_level ==
    "per_leaf"``) carries the fixed-size named vectors the optimizer chain
    reduced inside the jitted step — ``var_max`` / ``grad_norm`` /
    ``update_norm`` / ``param_norm`` / ``grad_to_weight``, each
    ``(n_leaves,)`` in ``leaf_labels`` order — so regulators can act on
    *which* parameter group is excursing rather than one global scalar."""

    step: int = 0
    tokens_seen: int = 0
    loss: float = float("nan")
    loss_ratio: float = float("nan")
    # grad_norm is the RAW pre-clip global norm (measured before the clip
    # scales anything) — the variance signal regulators act on.
    # grad_norm_clipped is the post-clip norm, reported separately so the
    # two can never be conflated again: under sustained clipping it
    # saturates at the clip limit and carries no noise information.
    grad_norm: float = float("nan")
    grad_norm_clipped: float = float("nan")
    var_max: float = float("nan")
    var_l1: float = float("nan")
    # gradient-noise-scale pair (NaN unless TrainConfig.gns is enabled and
    # the step realized >= 2 emulated shards): mean per-shard / full-batch
    # squared gradient norms and the shard/batch sizes they were measured
    # at — everything GNSEstimator needs for the unbiased B_noise estimate
    gns_small_sq: float = float("nan")
    gns_big_sq: float = float("nan")
    gns_b_small: float = float("nan")
    gns_b_big: float = float("nan")
    per_leaf: Optional[Dict[str, np.ndarray]] = None
    leaf_labels: Tuple[str, ...] = ()


@dataclass
class StepPlan:
    """The control decision for one step, executed by the trainer."""

    seq_len: int
    batch_size: int
    lr: float
    grad_clip_scale: float = 1.0


@dataclass
class ControllerState:
    """Unified checkpointable state of the whole control plane: the one
    step/token counter pair, each regulator's slice and the loss-ratio
    tracker."""

    step: int = 0
    tokens_seen: int = 0
    regulators: Dict[str, Dict[str, Any]] = field(default_factory=dict)
    tracker: Dict[str, Any] = field(default_factory=dict)

    def to_host(self) -> Dict[str, Any]:
        return {"step": self.step, "tokens_seen": self.tokens_seen,
                "regulators": self.regulators, "tracker": self.tracker}

    @classmethod
    def from_host(cls, d: Dict[str, Any]) -> "ControllerState":
        return cls(step=int(d.get("step", 0)),
                   tokens_seen=int(d.get("tokens_seen", 0)),
                   regulators=dict(d.get("regulators", {})),
                   tracker=dict(d.get("tracker", {})))


# ---------------------------------------------------------------------------
# the protocol
# ---------------------------------------------------------------------------

class Regulator:
    """Base class; regulators override what they need.  ``name`` keys the
    regulator's slice of :class:`ControllerState` and must be unique within
    a stack."""

    name: str = "regulator"

    def plan(self, tele: StepTelemetry, plan: StepPlan) -> StepPlan:
        return plan

    def observe(self, tele: StepTelemetry, tokens_step: int) -> None:
        pass

    def state_dict(self) -> Dict[str, Any]:
        return {}

    def load_state_dict(self, d: Dict[str, Any]) -> None:
        pass


def quantize_batch(raw: float, dp_size: int, min_batch: int,
                   full_batch: int) -> int:
    """Round down to a multiple of the data-parallel size and clip to
    [max(min_batch, dp_size), full_batch] — the paper's §5.1 structural
    constraint, shared by every batch-sizing regulator."""
    b = int(raw) - int(raw) % dp_size
    return int(np.clip(b, max(min_batch, dp_size), full_batch))


class SeqLenRegulator(Regulator):
    """SLW (paper §4) on the protocol: the pacing function at ``tele.step``,
    quantized down onto the bucket ladder, and for ``variance_gated``
    pacing (beyond-paper) the gate that holds it at or below the rung the
    observed Adam variance max has allowed.  Its state is the gate alone,
    so a restart mid-warmup resumes the schedule exactly (re-running long
    sequences early after a crash would reintroduce the very instability
    SLW removes)."""

    name = "seqlen"

    def __init__(self, cfg: SLWConfig, full_seq: int,
                 warmup_steps_hint: int = 0, prefix_tokens: int = 0):
        self.cfg = cfg
        # SLW warms up the text only: a vlm's image-patch prefix is frozen
        self.text_len = full_seq - prefix_tokens
        self.warmup_steps_hint = warmup_steps_hint
        self.ladder = pacing.bucket_ladder(
            cfg.start_seq_len, cfg.end_seq_len or self.text_len,
            cfg.round_multiple, cfg.max_buckets)
        self.gate_level = 0  # index into the ladder (variance_gated)
        self.var_trailing = 0.0  # trailing mean of the Adam variance max

    @property
    def mode(self) -> str:
        return self.cfg.mode

    def plan(self, tele: StepTelemetry, plan: StepPlan) -> StepPlan:
        s = pacing.seqlen_at(self.cfg, tele.step, self.text_len,
                             self.warmup_steps_hint, self.ladder)
        if self.cfg.pacing == "variance_gated":
            # step 0 runs the smallest bucket: nothing has been observed
            rung = min(self.gate_level, len(self.ladder) - 1) \
                if tele.step else 0
            s = min(s, self.ladder[rung])
        plan.seq_len = min(plan.seq_len, s)
        return plan

    def observe(self, tele: StepTelemetry, tokens_step: int) -> None:
        """variance_gated: climb one rung only while the Adam variance max
        stays below gate x its trailing mean (closes the loop on the
        paper's §3 correlation)."""
        v = tele.var_max
        if self.cfg.pacing != "variance_gated" or not math.isfinite(v):
            return
        if self.var_trailing == 0.0:
            self.var_trailing = v
        ok = v <= self.cfg.variance_gate * self.var_trailing
        self.var_trailing = 0.9 * self.var_trailing + 0.1 * v
        if ok and self.gate_level < len(self.ladder) - 1:
            self.gate_level += 1

    def state_dict(self) -> Dict[str, Any]:
        return {"gate_level": self.gate_level,
                "var_trailing": self.var_trailing}

    def load_state_dict(self, d: Dict[str, Any]) -> None:
        # slices written before the regulator planned from the stack's
        # counters also carry "step" and "tokens_seen"; the stack's own
        # (ControllerState) are the ones that count
        self.gate_level = int(d.get("gate_level", 0))
        self.var_trailing = float(d.get("var_trailing", 0.0))


class BatchSizeRegulator(Regulator):
    """GPT-3-style batch warmup — the related work the paper compares
    against (§5.1) and finds provides *no* stability benefit: start at
    ``start_batch`` and grow linearly in tokens to the full batch over
    ``warmup_tokens``.  The batch must be a multiple of the data-parallel
    size, so on a large mesh the warmup is quantized coarsely (vs SLW's
    fixed "multiple of 8/128", which is independent of the mesh)."""

    name = "batch_warmup"

    def __init__(self, cfg: BatchWarmupConfig, full_batch: int,
                 dp_size: int = 1):
        self.cfg = cfg
        self.full_batch = full_batch
        self.dp_size = dp_size

    def plan(self, tele: StepTelemetry, plan: StepPlan) -> StepPlan:
        cfg = self.cfg
        frac = min(tele.tokens_seen / max(cfg.warmup_tokens, 1), 1.0)
        raw = cfg.start_batch + frac * (self.full_batch - cfg.start_batch)
        plan.batch_size = min(plan.batch_size, quantize_batch(
            raw, self.dp_size, cfg.start_batch, self.full_batch))
        return plan


class LRScheduleRegulator(Regulator):
    """Token-wise (paper A.2) / step-wise / constant LR schedule.  Sets the
    scheduled value; place multiplicative modifiers after it."""

    name = "lr"

    def __init__(self, cfg: OptimizerConfig):
        self.cfg = cfg

    def plan(self, tele: StepTelemetry, plan: StepPlan) -> StepPlan:
        plan.lr = lr_at(self.cfg, tele.step, tele.tokens_seen)
        return plan


class VarianceLRThrottle(Regulator):
    """Warmup-free LR control (beyond-paper): back the LR off
    multiplicatively while the Adam variance max spikes above ``gate`` x its
    trailing mean — the paper's §3 spike precursor — and recover when calm.
    Also tightens the grad clip by the same factor while throttled.

    When the step runs with per-leaf telemetry, the gate is evaluated
    *per parameter group* against per-leaf trailing means (Molybog et
    al.'s per-component precursor), and ``blamed`` names the group with
    the largest excursion ratio — the answer to "which layer is unstable"
    that the global scalar could never give."""

    name = "var_lr_throttle"

    # per-leaf vectors the gate watches: ``var_max`` is the paper's spike
    # precursor; ``grad_norm`` is reduced from the *raw* (pre-clip) grads,
    # so a gradient explosion the global clip normalizes away — invisible
    # to the Adam variance — still trips the gate and names its leaf
    GATE_KEYS = ("var_max", "grad_norm")

    def __init__(self, spec: RegulatorSpec):
        self.spec = spec
        self.scale = 1.0
        self.trailing = 0.0
        self.leaf_trailing: Dict[str, np.ndarray] = {}
        self.blamed = ""
        self.blamed_ratio = 0.0

    def plan(self, tele: StepTelemetry, plan: StepPlan) -> StepPlan:
        plan.lr *= self.scale
        plan.grad_clip_scale *= self.scale
        return plan

    def _observe_per_leaf(self, tele: StepTelemetry) -> Optional[bool]:
        """Per-leaf gate.  Returns None when per-leaf telemetry is absent
        or unusable, else whether any leaf excursed (and records blame)."""
        if tele.per_leaf is None:
            return None
        usable = spiking = False
        for key in self.GATE_KEYS:
            v = tele.per_leaf.get(key)
            if v is None:
                continue
            v = np.asarray(v, np.float64)
            if not np.all(np.isfinite(v)):
                continue
            usable = True
            trail = self.leaf_trailing.get(key)
            if trail is None or trail.shape != v.shape:
                self.leaf_trailing[key] = v.copy()
                continue
            ratios = v / np.maximum(trail, 1e-30)
            if bool(np.any(ratios > self.spec.gate)):
                spiking = True
                if float(np.max(ratios)) > self.blamed_ratio:
                    # keep the blame of the *largest* excursion seen, not
                    # the latest: the layer that started a divergence spikes
                    # orders of magnitude harder than the downstream
                    # turbulence it causes
                    from repro.core.telemetry import blame
                    worst = blame(tele.leaf_labels, ratios)
                    if worst:
                        self.blamed = worst
                        self.blamed_ratio = float(np.max(ratios))
            self.leaf_trailing[key] = 0.9 * trail + 0.1 * v
        return spiking if usable else None

    def observe(self, tele: StepTelemetry, tokens_step: int) -> None:
        spiking = self._observe_per_leaf(tele)
        v = tele.var_max
        if spiking is None:
            if not math.isfinite(v):
                return
            if self.trailing == 0.0:
                self.trailing = v
            spiking = v > self.spec.gate * self.trailing
            self.trailing = 0.9 * self.trailing + 0.1 * v
        if spiking:
            self.scale = max(self.scale * self.spec.backoff, self.spec.floor)
        else:
            self.scale = min(self.scale * self.spec.recovery, 1.0)

    def state_dict(self) -> Dict[str, Any]:
        return {"scale": self.scale, "trailing": self.trailing,
                "leaf_trailing": {k: v.tolist()
                                  for k, v in self.leaf_trailing.items()},
                "blamed": self.blamed, "blamed_ratio": self.blamed_ratio}

    def load_state_dict(self, d: Dict[str, Any]) -> None:
        self.scale = float(d["scale"])
        self.trailing = float(d["trailing"])
        lt = d.get("leaf_trailing")
        self.leaf_trailing = ({k: np.asarray(v, np.float64)
                               for k, v in lt.items()}
                              if isinstance(lt, dict) else {})
        self.blamed = str(d.get("blamed", ""))
        self.blamed_ratio = float(d.get("blamed_ratio", 0.0))


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------

class RegulatorStack:
    """Ordered regulators + plan execution.  The trainer's whole control
    surface: ``plan`` before the step, ``apply`` the plan to the host batch,
    ``observe`` after, ``controller_state`` into the checkpoint."""

    def __init__(self, regulators: Sequence[Regulator], full_seq: int,
                 full_batch: int, base_lr: float):
        names = [r.name for r in regulators]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate regulator names: {names}")
        self.regulators = list(regulators)
        self.full_seq = full_seq
        self.full_batch = full_batch
        self.base_lr = base_lr

    def __getitem__(self, name: str) -> Regulator:
        for r in self.regulators:
            if r.name == name:
                return r
        raise KeyError(name)

    def __contains__(self, name: str) -> bool:
        return any(r.name == name for r in self.regulators)

    @property
    def seq_mode(self) -> str:
        return (self["seqlen"].mode if "seqlen" in self else "truncate")

    def plan(self, tele: StepTelemetry) -> StepPlan:
        p = StepPlan(seq_len=self.full_seq, batch_size=self.full_batch,
                     lr=self.base_lr)
        for r in self.regulators:
            p = r.plan(tele, p)
        return p

    def apply(self, batch: Dict[str, np.ndarray], plan: StepPlan
              ) -> Tuple[Dict[str, np.ndarray], int]:
        """Execute the plan host-side: row-slice to the batch size, then
        truncate/repack to the seqlen bucket.  Returns (batch, tokens)."""
        first = next(iter(batch.values()))
        if plan.batch_size < first.shape[0]:
            batch = {k: v[:plan.batch_size] for k, v in batch.items()}
        return apply_seqlen(batch, plan.seq_len, self.seq_mode)

    def observe(self, tele: StepTelemetry, tokens_step: int) -> None:
        for r in self.regulators:
            r.observe(tele, tokens_step)

    # -- unified checkpoint state -------------------------------------------
    def controller_state(self, step: int, tokens_seen: int,
                         tracker_state: Optional[Dict[str, Any]] = None
                         ) -> ControllerState:
        return ControllerState(
            step=step, tokens_seen=tokens_seen,
            regulators={r.name: r.state_dict() for r in self.regulators},
            tracker=tracker_state or {})

    def load_controller_state(self, cs: ControllerState) -> None:
        for r in self.regulators:
            if r.name in cs.regulators:
                r.load_state_dict(cs.regulators[r.name])


# ---------------------------------------------------------------------------
# construction from config
# ---------------------------------------------------------------------------

def build_stack(tc: TrainConfig, *, dp_size: int = 1,
                warmup_steps_hint: int = 0,
                prefix_tokens: int = 0) -> RegulatorStack:
    """Build the control plane for a TrainConfig: one regulator per entry of
    ``tc.regulators``, in order."""
    regs: List[Regulator] = []
    for spec in tc.regulators:
        if spec.kind == "seqlen":
            regs.append(SeqLenRegulator(
                tc.slw, tc.seq_len, warmup_steps_hint=warmup_steps_hint,
                prefix_tokens=prefix_tokens))
        elif spec.kind == "batch_warmup":
            regs.append(BatchSizeRegulator(tc.batch_warmup, tc.global_batch,
                                           dp_size=dp_size))
        elif spec.kind == "lr":
            regs.append(LRScheduleRegulator(tc.optimizer))
        elif spec.kind == "var_lr_throttle":
            regs.append(VarianceLRThrottle(spec))
        elif spec.kind == "critical_batch":
            # deferred import: repro.gns depends on this module's protocol
            from repro.gns.regulator import CriticalBatchRegulator
            regs.append(CriticalBatchRegulator(tc.gns, tc.global_batch,
                                               dp_size=dp_size))
        else:
            raise ValueError(f"unknown regulator kind {spec.kind!r}")
    return RegulatorStack(regs, full_seq=tc.seq_len,
                          full_batch=tc.global_batch, base_lr=tc.optimizer.lr)


def predict_trajectory(tc: TrainConfig, n_steps: int, *, dp_size: int = 1,
                       warmup_steps_hint: int = 0, prefix_tokens: int = 0
                       ) -> List[StepPlan]:
    """Replay the stack's open-loop trajectory without training: the exact
    (seq_len, batch, lr) sequence the trainer will execute when no
    telemetry-driven regulator intervenes.  Telemetry-driven regulators see
    *calm* synthetic telemetry (constant unit var_max/grad_norm), so e.g.
    variance_gated pacing replays its calm-run envelope rather than sitting
    at the smallest bucket forever on NaN observations.  Token accounting
    mirrors the trainer's truncate-mode counting (batch * seqlen per
    step)."""
    stack = build_stack(tc, dp_size=dp_size,
                        warmup_steps_hint=warmup_steps_hint,
                        prefix_tokens=prefix_tokens)
    plans: List[StepPlan] = []
    tokens = 0
    for step in range(n_steps):
        tele = StepTelemetry(step=step, tokens_seen=tokens,
                             var_max=1.0, var_l1=1.0, grad_norm=1.0)
        plan = stack.plan(tele)
        plans.append(plan)
        if stack.seq_mode == "repack":
            folds = max(tc.seq_len // plan.seq_len, 1)
            tokens_step = plan.batch_size * folds * plan.seq_len
        else:
            tokens_step = plan.batch_size * plan.seq_len
        stack.observe(dataclasses.replace(tele), tokens_step)
        tokens += tokens_step
    return plans
