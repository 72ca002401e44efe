"""EngineCore / Replica: continuous batching over the DecodeState protocol.

The serving stack is four explicit layers, each independently testable:

* :class:`EngineCore` (here) — the pure device layer: jitted prefill /
  fused decode / sample executables plus the ``DecodeState`` cache.  No
  scheduler knowledge; slots arrive as plain integers.  ``prefill_batch``
  runs the shared ``(k, bucket)`` prefill + per-row ragged replay +
  multi-row insert and reports per-row :class:`PrefillOutcome`s;
  ``decode_step`` is the device half of the fused step.
* ``AdmissionPolicy`` (serve/policies.py) — who gets the next free slots:
  fcfs (the legacy behavior, bitwise), shortest-prompt-first,
  budget-packing.
* :class:`Replica` (here) — slot ownership, retirement and containment
  (the per-slot try/except rings, :class:`EngineStats`) around one core.
  A ``role="decode"`` replica delegates admission prefills to a
  ``role="prefill"`` partner's core; the stacked rows + first tokens land
  in the decode core via the same ``insert_many`` path.
* ``Router`` (serve/router.py) — a request front-end over N replicas.

One core serves every backbone family through the same three jitted
executables: per-bucket **prefill** (shape-keyed jit cache bounded by the
prompt ladder; up to ``SchedulerConfig.prefill_batch`` same-bucket
requests stack into one ``(k, bucket)`` call) + exact decode replay of
each request's sub-bucket remainder, slot **insert/evict** surgery on the
donated state buffer, and one **fused decode step** for all slots at once.

The loop is host-driven: admit pending requests into free slots, step the
fused decode, retire finished slots, backfill.  Greedy outputs are
tokenwise identical to running each request alone through the legacy
static-batch path (tests/test_serve_engine.py pins this for dense and
recurrent backbones), and — because each request's stream never depends on
batch composition — identical again under any router/policy/role split
(tests/test_router.py).
"""
from __future__ import annotations

import logging
import time
from collections import deque
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Deque, Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.models import model_zoo
from repro.serve import sampling
from repro.serve.policies import make_policy
from repro.serve.scheduler import (QueueFull, Scheduler, SchedulerConfig,
                                   prefill_split)
from repro.serve.state import SlotDecodeState
from repro.serve.types import (GenerationResult, PrefillOutcome,
                               ReplicaTelemetry, Request)

OnToken = Callable[[int, int], None]  # (request uid, token id)

log = logging.getLogger(__name__)


# per-step decode latency samples kept for percentiles: a bounded ring,
# not a list — one float per fused step forever is a slow leak at
# production rates (a week at 100 steps/s is ~500 MB of pure bookkeeping)
STEP_TIME_WINDOW = 2048


@dataclass
class EngineStats:
    """Host wall-clock accounting for one replica lifetime."""

    prefill_s: float = 0.0
    prefill_tokens: int = 0
    decode_s: float = 0.0
    decode_steps: int = 0
    generated_tokens: int = 0
    admitted: int = 0
    step_times: Deque[float] = field(
        default_factory=lambda: deque(maxlen=STEP_TIME_WINDOW))
    # containment accounting: slots retired with reason="error" (the batch
    # kept going) and submissions shed at the bounded queue
    slot_errors: int = 0
    shed: int = 0

    @property
    def prefill_tok_s(self) -> float:
        return self.prefill_tokens / max(self.prefill_s, 1e-9)

    @property
    def decode_tok_s(self) -> float:
        """Useful fused-decode tokens per second of fused-decode wall time
        (each request's first token is emitted by its admission prefill and
        excluded here)."""
        return ((self.generated_tokens - self.admitted)
                / max(self.decode_s, 1e-9))

    def latency_percentile(self, p: float) -> float:
        """p-th percentile of per-step (== per-token) decode latency, s.

        Exact for runs up to ``STEP_TIME_WINDOW`` decode steps (every
        sample is still in the ring); beyond that it is the percentile of
        the trailing window — the production-relevant figure anyway."""
        if not self.step_times:
            return 0.0
        return float(np.percentile(
            np.fromiter(self.step_times, np.float64), p))


class EngineCore:
    """The pure device layer: jitted executables + the DecodeState cache.

    Knows nothing about schedulers, queues or retirement — callers hand it
    slot integers and it reports what the device did.  A
    ``role="prefill"`` core owns no slot cache at all (it only ever
    produces model-format rows for some other core's ``insert_rows``) and
    always uses the dense ``SlotDecodeState`` — prefill rows are dense
    model format regardless of how the decode side pages its pool.
    """

    def __init__(self, model, params, cfg: Optional[SchedulerConfig] = None,
                 rules=None, role: str = "both"):
        if role not in ("both", "prefill", "decode"):
            raise ValueError(f"unknown role {role!r}")
        self.model = model
        self.params = params
        self.cfg = cfg or SchedulerConfig()
        self.role = role
        if self.cfg.paged and role != "prefill":
            from repro.serve.paging import PagedDecodeState
            self.state = PagedDecodeState(
                model, page_size=self.cfg.page_size,
                n_pages=self.cfg.resolved_n_pages)
            # admission page budget: a request is only admitted once its
            # worst case (prompt + max_tokens) is reserved in the pool
            self.reserve = self.state.try_reserve
        else:
            self.state = SlotDecodeState(model)
            self.reserve = None
        self.ladder = self.cfg.ladder()
        if role == "prefill":
            self.cache = None
        else:
            self.cache = self.state.init_slots(self.cfg.n_slots,
                                               self.cfg.cache_len)
            if rules is not None:
                self.cache = jax.device_put(
                    self.cache,
                    self.state.shardings(rules, self.cfg.n_slots,
                                         self.cfg.cache_len))
        cache_len = self.cfg.cache_len
        self._prefill = jax.jit(
            lambda p, b: model.prefill(p, b, cache_len=cache_len))
        vocab = model.cfg.vocab_size
        self._sample = jax.jit(partial(sampling.sample_tokens,
                                       vocab_size=vocab))
        # fused-loop variant: per-slot base keys folded with the per-slot
        # token index *on device*, one executable call per step (no
        # host-side fold_in round-trips inside the timed decode loop)
        self._sample_at = jax.jit(
            lambda lg, keys, steps, t, k, p: sampling.sample_tokens(
                lg, jax.vmap(jax.random.fold_in)(keys, steps), t, k, p,
                vocab_size=vocab))
        # greedy fast path: all-greedy batches (the default) skip the
        # top-k/top-p sorts and the categorical draw entirely
        self._greedy = jax.jit(lambda lg: jnp.argmax(
            sampling.mask_vocab(lg, vocab), axis=-1).astype(jnp.int32))

    # -- sampling ------------------------------------------------------------
    def _first_token(self, req: Request, logits: jax.Array) -> int:
        """Sample the admission token from one request's (1, V) logits."""
        sp = req.sampling
        if sp.temperature <= 0.0:
            return int(self._greedy(logits)[0])
        key = sampling.step_key(
            sampling.request_key(sp.seed, req.uid), 0)[None]
        return int(self._sample(
            logits, key,
            jnp.full((1,), sp.temperature, jnp.float32),
            jnp.full((1,), sp.top_k, jnp.int32),
            jnp.full((1,), sp.top_p, jnp.float32))[0])

    # -- admission prefill ---------------------------------------------------
    def prefill_batch(self, admissions, target: Optional["EngineCore"] = None
                      ) -> List[PrefillOutcome]:
        """Prefill same-split requests as one ``(k, bucket)`` call and land
        the rows in ``target`` (default: this core).

        Every request in ``admissions`` must share a prefill split (the
        admission policy guarantees it), so their bucket prefixes stack
        into one jitted prefill — shape set bounded by
        ``(ladder U {1}) x prefill_batch``.  Ragged sub-bucket remainders
        then decode-replay per request on the sliced row cache — exact for
        every backbone — and the surviving rows land in their slots through
        one multi-row ``insert_many`` on the target core (the
        prefill→decode disaggregation handoff is exactly
        ``prefill_core.prefill_batch(adm, target=decode_core)``).

        Returns one :class:`PrefillOutcome` per admission row: either a
        first token or which device phase failed.  What to *do* about a
        failure (abort, free pages, count) is the Replica's decision.
        """
        target = target if target is not None else self
        reqs = [r for _, r in admissions]
        outcomes = [PrefillOutcome(slot=s, request=r) for s, r in admissions]
        try:
            split = prefill_split(reqs[0].prompt_len, self.ladder)
            toks = jnp.asarray([r.tokens[:split] for r in reqs], jnp.int32)
            logits, kcache = self._prefill(self.params, {"tokens": toks})
        except Exception:  # noqa: BLE001 — shared phase: all k rows fail
            # a compile error lands here too: keep its traceback
            log.exception("prefill of %d request(s) failed", len(reqs))
            for o in outcomes:
                o.error = "prefill"
            return outcomes
        row_logits = [logits[i:i + 1] for i in range(len(reqs))]
        if any(r.prompt_len > split for r in reqs):
            rows = [self.state.row(kcache, i) for i in range(len(reqs))]
            for i, r in enumerate(reqs):
                try:
                    full = jnp.asarray(r.tokens, jnp.int32)[None, :]
                    for j in range(split, r.prompt_len):
                        row_logits[i], rows[i] = self.state.decode(
                            self.params, rows[i], full[:, j:j + 1])
                except Exception:  # noqa: BLE001 — this request only
                    outcomes[i].error = "replay"
            live = [i for i in range(len(reqs)) if not outcomes[i].error]
            stacked = (self.state.stack_rows([rows[i] for i in live])
                       if live else None)
        else:
            live = list(range(len(reqs)))
            stacked = kcache
        if stacked is not None:
            target.insert_rows(
                np.asarray([outcomes[i].slot for i in live], np.int32),
                stacked)
        for i in live:
            try:
                outcomes[i].first_token = self._first_token(reqs[i],
                                                            row_logits[i])
            except Exception:  # noqa: BLE001 — per-request sampling fault
                outcomes[i].error = "sample"
        return outcomes

    # -- slot surgery --------------------------------------------------------
    def insert_rows(self, slots: np.ndarray, stacked) -> None:
        """Multi-row insert of stacked model-format rows into slots."""
        self.cache = self.state.insert_many(self.cache, slots, stacked)

    def evict(self, slot: int) -> None:
        """Clear one slot (and release its page reservation when paged —
        a no-op for dense states and for slots nothing was inserted into)."""
        self.cache = self.state.evict(self.cache, slot)

    def gather(self, slot: int):
        """Model-format row for one slot (the migration export path)."""
        return self.state.gather(self.cache, slot)

    # -- the fused decode step (device half) --------------------------------
    def decode_step(self, toks, keys, steps, temps, topk, topp,
                    all_greedy: bool) -> np.ndarray:
        """One fused decode + sample over all slots; returns the (n_slots,)
        next-token array.  Inactive rows compute garbage the caller never
        surfaces (their cache writes are dropped by the "active" mask)."""
        logits, self.cache = self.state.decode(self.params, self.cache,
                                               jnp.asarray(toks))
        if all_greedy:
            return np.asarray(self._greedy(logits))
        return np.asarray(self._sample_at(
            logits, jnp.asarray(keys), jnp.asarray(steps),
            jnp.asarray(temps), jnp.asarray(topk), jnp.asarray(topp)))

    # -- telemetry -----------------------------------------------------------
    @property
    def free_pages(self) -> int:
        """Free pages in the paged pool; -1 for dense states."""
        alloc = getattr(self.state, "alloc", None)
        return alloc.free_page_count if alloc is not None else -1


class Replica:
    """Slot ownership + retirement + containment around one EngineCore.

    Owns the :class:`Scheduler`, the admission policy, and
    :class:`EngineStats`; every device phase runs inside a per-slot
    try/except ring so one poisoned request retires alone while the batch
    keeps going.

    Roles: ``"both"`` (the default — one core prefills and decodes),
    ``"decode"`` (admission prefills delegate to ``prefill_source``'s
    core; rows land here via ``insert_many``), ``"prefill"`` (core only —
    no scheduler, no slots; it exists to serve decode-role partners).
    """

    def __init__(self, model, params, cfg: Optional[SchedulerConfig] = None,
                 rules=None, role: str = "both",
                 prefill_source: Optional["Replica"] = None, name: str = ""):
        self.cfg = cfg or SchedulerConfig()
        self.role = role
        self.name = name or role
        self.stats = EngineStats()
        self.core = EngineCore(model, params, self.cfg, rules=rules,
                               role=role)
        # optional per-step metrics hook (launch/serve.py --metrics-jsonl)
        self.on_step_metrics: Optional[Callable[[dict], None]] = None
        self.prefill_replica: Optional["Replica"] = None
        if role == "prefill":
            if prefill_source is not None:
                raise ValueError("a prefill-role replica cannot have a "
                                 "prefill_source")
            self.scheduler = None
            self.policy = None
            self.prefill_core = self.core
            return
        if prefill_source is not None:
            if role != "decode":
                raise ValueError("prefill_source requires role='decode'")
            self.prefill_replica = prefill_source
            self.prefill_core = prefill_source.core
        else:
            if role == "decode":
                raise ValueError("role='decode' requires a prefill_source")
            self.prefill_core = self.core
        self.scheduler = Scheduler(self.cfg)
        self.policy = make_policy(self.cfg)
        # fused-step staging, preallocated once and refreshed in place:
        # rebuilding six (n_slots,) arrays every decode step was measurable
        # host churn at small-model decode rates.  Stale entries in rows no
        # longer active are harmless — per-slot sampling is independent,
        # inactive cache writes are dropped, and inactive outputs are never
        # surfaced.
        n = self.cfg.n_slots
        self._toks = np.zeros((n, 1), np.int32)
        self._temps = np.zeros((n,), np.float32)
        self._topk = np.zeros((n,), np.int32)
        self._topp = np.ones((n,), np.float32)
        self._keys = np.zeros((n, 2), np.uint32)
        self._steps = np.zeros((n,), np.int32)

    # -- construction helpers ----------------------------------------------
    @classmethod
    def from_arch(cls, arch: str, use_reduced: bool = True, seed: int = 0,
                  cfg: Optional[SchedulerConfig] = None,
                  decode_backend: Optional[str] = None, **kw) -> "Replica":
        from repro.configs import get_arch, reduced as reduce_cfg
        spec = get_arch(arch)
        mcfg = reduce_cfg(spec.model) if use_reduced else spec.model
        if decode_backend:
            mcfg = mcfg.replace(decode_backend=decode_backend)
        model = model_zoo.build_model(mcfg, dtype=jnp.float32, remat="none")
        params = model_zoo.init_params(jax.random.PRNGKey(seed), mcfg)
        return cls(model, params, cfg=cfg, **kw)

    # -- compatibility surface (the pre-split InferenceEngine monolith) ----
    # Tests and callers reach into the device layer through the replica;
    # property setters keep instance-level monkeypatching working by
    # forwarding onto the core.
    @property
    def model(self):
        return self.core.model

    @property
    def params(self):
        return self.core.params

    @property
    def state(self):
        return self.core.state

    @property
    def cache(self):
        return self.core.cache

    @cache.setter
    def cache(self, value):
        self.core.cache = value

    @property
    def _prefill(self):
        return self.core._prefill

    @_prefill.setter
    def _prefill(self, fn):
        self.core._prefill = fn

    @property
    def _first_token(self):
        return self.core._first_token

    @_first_token.setter
    def _first_token(self, fn):
        self.core._first_token = fn

    # -- admission -----------------------------------------------------------
    def _admit_batch(self, admissions, on_token: Optional[OnToken]) -> None:
        """Admit same-split requests through the prefill core; activate,
        abort or retire each row per its :class:`PrefillOutcome`.
        Per-request ``prefill_s`` reports the batch wall time amortized
        over the rows that survived."""
        t0 = time.time()
        outcomes = self.prefill_core.prefill_batch(admissions,
                                                   target=self.core)
        if all(o.error == "prefill" for o in outcomes):
            # shared phase failed: all k slots abort, no timing accounted
            # (nothing was inserted; evict still releases page reservations)
            for o in outcomes:
                self.core.evict(o.slot)
                self.scheduler.abort(o.slot, o.request)
                self.stats.slot_errors += 1
            return
        dt = time.time() - t0
        n_ok = sum(1 for o in outcomes if not o.error)
        self.stats.prefill_s += dt
        self.stats.prefill_tokens += sum(o.request.prompt_len
                                         for o in outcomes if not o.error)
        self.stats.admitted += n_ok
        self.stats.generated_tokens += n_ok
        if self.prefill_replica is not None:
            # disaggregated: the prefill partner did the device work —
            # mirror the prefill accounting onto its stats too
            self.prefill_replica.stats.prefill_s += dt
            self.prefill_replica.stats.prefill_tokens += sum(
                o.request.prompt_len for o in outcomes if not o.error)
        for o in outcomes:
            if o.error:
                # the failing request retires alone; the evict clears its
                # cache row if one was inserted (sampling failed after
                # insert_many) and releases its page reservation either
                # way — the rest of the batch proceeds
                self.core.evict(o.slot)
                self.scheduler.abort(o.slot, o.request)
                self.stats.slot_errors += 1
                continue
            st = self.scheduler.activate(o.slot, o.request, o.first_token,
                                         dt / max(n_ok, 1))
            try:
                if on_token:
                    on_token(o.request.uid, o.first_token)
                reason = self.scheduler.stop_reason(st)
            except Exception:  # noqa: BLE001 — consumer callback fault
                self._retire(o.slot, "error")
                self.stats.slot_errors += 1
                continue
            if reason:
                self._retire(o.slot, reason)

    def _retire(self, slot: int, reason: str) -> GenerationResult:
        self.core.evict(slot)
        res = self.scheduler.finish(slot, reason)
        res.decode_steps = max(len(res.tokens) - 1, 0)
        return res

    def admit(self, on_token: Optional[OnToken] = None) -> bool:
        """One admission round under the configured policy; False when
        nothing was admissible."""
        adm = self.policy.select(self.scheduler, self.cfg.prefill_batch,
                                 reserve=self.core.reserve)
        if not adm:
            return False
        self._admit_batch(adm, on_token)
        return True

    # -- the fused decode step ---------------------------------------------
    def step(self, on_token: Optional[OnToken] = None) -> None:
        """One fused decode step over the active slots: refresh the staging
        buffers in place, run the device half, append/stream/retire."""
        toks, temps, topk = self._toks, self._temps, self._topk
        topp, keys, steps = self._topp, self._keys, self._steps
        active_now: List[tuple] = list(self.scheduler.active.items())
        all_greedy = True
        for slot, st in active_now:
            sp = st.request.sampling
            toks[slot, 0] = st.last_token
            temps[slot] = sp.temperature
            topk[slot] = sp.top_k
            topp[slot] = sp.top_p
            if sp.temperature > 0.0:
                all_greedy = False
                keys[slot] = st.base_key
                steps[slot] = st.n_generated
        t0 = time.time()
        nxt = self.core.decode_step(toks, keys, steps, temps, topk, topp,
                                    all_greedy)
        dt = time.time() - t0
        self.stats.step_times.append(dt)
        self.stats.decode_s += dt
        self.stats.decode_steps += 1
        self.stats.generated_tokens += len(active_now)
        for slot, st in active_now:
            try:
                tok = int(nxt[slot])
                st.result.tokens.append(tok)
                st.last_token = tok
                if on_token:
                    on_token(st.request.uid, tok)
                reason = self.scheduler.stop_reason(st)
            except Exception:  # noqa: BLE001 — retire only this slot; the
                self._retire(slot, "error")  # rest of the batch finishes
                self.stats.slot_errors += 1
                continue
            if reason:
                self._retire(slot, reason)
        if self.on_step_metrics is not None:
            self.on_step_metrics(self.metrics_row(dt))

    # -- driver --------------------------------------------------------------
    def pump(self, on_token: Optional[OnToken] = None) -> bool:
        """Admit everything admissible, then one fused step if anything is
        active.  Returns whether any progress was made (the router's
        drain-loop termination signal)."""
        progressed = False
        while self.admit(on_token):
            progressed = True
        if self.scheduler.active:
            self.step(on_token)
            progressed = True
        return progressed

    def run(self, requests: Sequence[Request],
            on_token: Optional[OnToken] = None) -> List[GenerationResult]:
        """Generate for all ``requests``; returns results in request order.

        ``on_token(uid, token)`` streams tokens as they are produced (the
        first token of a request arrives during its admission prefill).
        The replica is reusable: each call drains its own request set and
        hands back exactly those results (uids must be unique per call).
        Validation is all-or-nothing: a bad request enqueues nothing.
        """
        requests = list(requests)  # tolerate generators: iterated 3 times
        self.scheduler.validate_batch(requests)
        # feed through the bounded queue: run() owns its whole request set,
        # so nothing is shed — the backlog drains as pending slots open
        backlog = deque(requests)
        while backlog or self.scheduler.busy:
            while backlog and self.scheduler.has_room:
                self.scheduler.enqueue_validated(backlog.popleft())
            self.pump(on_token)
        done = self.take_finished()
        by_uid: Dict[int, GenerationResult] = {r.uid: r for r in done}
        return [by_uid[r.uid] for r in requests]

    def try_submit(self, request: Request) -> bool:
        """Streaming-caller admission with explicit shed on overload:
        returns False (and counts the shed) when the bounded pending queue
        is full.  Invalid requests still raise — a malformed request is a
        caller bug, not an overload signal."""
        try:
            self.scheduler.submit(request)
            return True
        except QueueFull:
            self.stats.shed += 1
            return False

    def take_finished(self) -> List[GenerationResult]:
        """Drain and return the finished-result list (router collection)."""
        done, self.scheduler.finished = self.scheduler.finished, []
        return done

    # -- migration -----------------------------------------------------------
    def migrate_slot_to(self, slot: int, other: "Replica") -> int:
        """Move one active slot — device row + host bookkeeping — onto
        ``other``; returns the destination slot.  The token stream
        continues identically on the destination (tests/test_router.py
        pins this), which is what makes live rebalancing safe."""
        from repro.distributed.collectives import migrate_row
        if slot not in self.scheduler.active:
            raise KeyError(f"slot {slot} is not active")
        if not other.scheduler.free:
            raise RuntimeError("destination replica has no free slot")
        st = self.scheduler.active[slot]
        dst_slot = other.scheduler.free[-1]
        if other.core.reserve is not None and \
                not other.core.reserve(dst_slot, st.request):
            raise RuntimeError("destination replica cannot reserve pages")
        other.scheduler.free.pop()
        self.core.cache, other.core.cache = migrate_row(
            self.core.state, self.core.cache, slot,
            other.core.state, other.core.cache, dst_slot,
            cache_len=other.cfg.cache_len)
        del self.scheduler.active[slot]
        self.scheduler.free.append(slot)
        other.scheduler.active[dst_slot] = st
        return dst_slot

    # -- telemetry -----------------------------------------------------------
    def telemetry(self) -> ReplicaTelemetry:
        """Admission telemetry snapshot for the router's routing score."""
        return ReplicaTelemetry(
            name=self.name,
            queue_depth=len(self.scheduler.pending),
            active=len(self.scheduler.active),
            free_slots=len(self.scheduler.free),
            free_pages=self.core.free_pages,
            p95_step_s=self.stats.latency_percentile(95))

    def metrics_row(self, step_s: float) -> dict:
        """One JSONL-able per-step metrics row (--metrics-jsonl)."""
        s = self.stats
        return {
            "replica": self.name,
            "decode_step": s.decode_steps,
            "step_s": step_s,
            "active": len(self.scheduler.active),
            "queue_depth": len(self.scheduler.pending),
            "free_slots": len(self.scheduler.free),
            "free_pages": self.core.free_pages,
            "generated_tokens": s.generated_tokens,
            "admitted": s.admitted,
            "slot_errors": s.slot_errors,
            "shed": s.shed,
            "p50_s": s.latency_percentile(50),
            "p95_s": s.latency_percentile(95),
        }

    def reset_stats(self) -> EngineStats:
        """Swap in a fresh stats accumulator (returns the old one)."""
        old, self.stats = self.stats, EngineStats()
        return old


class InferenceEngine(Replica):
    """Single-host continuous-batching engine: a ``role="both"`` Replica.

    Kept as the stable public name — and as the single-engine parity
    oracle the router tests compare against.  The disaggregated stack
    composes the same layers explicitly (EngineCore / Replica / Router;
    see serve/router.py)."""
