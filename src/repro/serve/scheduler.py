"""Continuous-batching scheduler: length-bucketed admission into fixed slots.

Admission control reuses the training side's TPU adaptation verbatim: the
prompt length is quantized *down* onto ``core.pacing.bucket_ladder`` (the
same ladder that bounds jit cache churn for the SLW curriculum), the bucket
prefix runs through the jitted prefill — one compiled executable per bucket
— and the sub-bucket remainder replays through the decode step, which is
exact for every backbone (no padding, no masked prefill).  The paper's
observation that sequence-length heterogeneity dominates cost applies
unchanged at serving time: ragged prompts land on a bounded shape set, and
ragged generation lengths are absorbed by per-slot eviction + backfill.

Batched prefill (``SchedulerConfig.prefill_batch``): admission can pop up
to ``k`` pending requests that share a prefill split and hand them to the
engine as one ``(k, bucket)`` prefill call — sub-bucket remainders still
decode-replay per request, so parity with sequential admission is exact.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Tuple

import numpy as np

from repro.core.pacing import bucket_ladder, quantize
from repro.serve.types import GenerationResult, Request
from repro.serve import sampling


class QueueFull(RuntimeError):
    """Bounded submit queue is at capacity — the caller must shed or retry.

    Overload is an explicit signal, not silent queue growth: at production
    rates an unbounded pending deque is memory-pressure-then-OOM, and the
    caller (router, API front-end) is the layer that knows whether to
    reject with 429, retry elsewhere, or spill."""


@dataclass(frozen=True)
class SchedulerConfig:
    """Knobs for slot/bucket composition.

    n_slots:      decode batch width (fixed; empty slots decode garbage that
                  is never surfaced)
    cache_len:    per-slot KV/state capacity; every request must satisfy
                  prompt_len + max_tokens <= cache_len
    prompt ladder (min_prompt_bucket / round_multiple / max_buckets): feeds
                  core.pacing.bucket_ladder — at most max_buckets + 1
                  distinct single-request prefill shapes ever compile (the
                  ladder plus the length-1 shape sub-bucket prompts use).
    prefill_batch: max same-bucket requests admitted as one (k, bucket)
                  prefill call (1 = sequential admission, the legacy
                  behavior; >1 amortizes weight reads across prompts and
                  multiplies the prefill shape set by at most
                  prefill_batch).
    max_pending:  bound on the pending queue (0 = unbounded, the legacy
                  behavior).  ``submit``/``submit_all`` raise
                  :class:`QueueFull` at capacity; the engine's
                  ``try_submit`` turns that into an explicit shed.
    paged:        allocate KV as a shared page pool behind a per-slot page
                  table (serve/paging.py) instead of dense
                  ``(n_slots, cache_len)`` rows.  Memory goes from
                  ``n_slots * cache_len`` to ``n_pages * page_size`` cache
                  tokens — sized to what requests actually use; admission
                  gains a page budget (a request needs
                  ``ceil((prompt_len + max_tokens) / page_size)`` pages
                  reserved or it waits in the pending queue).
    page_size:    tokens per page when paged.
    n_pages:      pool size when paged; 0 = dense-equivalent
                  (``n_slots * ceil(cache_len / page_size)`` — no memory
                  saving, same behavior; set lower to oversubscribe).
    policy:       admission policy name (serve/policies.py): "fcfs" (the
                  default — bitwise the behavior of
                  :meth:`Scheduler.next_admission`, which stays the FCFS
                  primitive), "shortest-prompt-first", or
                  "budget-packing".
    pack_budget:  token budget per admission round for
                  policy="budget-packing": the round's total worst-case
                  footprint (prompt_len + max_tokens per request) stays
                  under it.  0 resolves to cache_len * prefill_batch —
                  one full slot row per packed request, so the default
                  never binds below the FCFS batch.
    """

    n_slots: int = 8
    cache_len: int = 512
    min_prompt_bucket: int = 16
    round_multiple: int = 32
    max_buckets: int = 8
    prefill_batch: int = 1
    max_pending: int = 0
    paged: bool = False
    page_size: int = 64
    n_pages: int = 0
    policy: str = "fcfs"
    pack_budget: int = 0

    @property
    def pages_per_slot(self) -> int:
        return -(-self.cache_len // self.page_size)

    @property
    def resolved_n_pages(self) -> int:
        if not self.paged:
            return 0
        return self.n_pages or self.dense_equivalent_pages()

    def dense_equivalent_pages(self) -> int:
        return self.n_slots * self.pages_per_slot

    @property
    def resolved_pack_budget(self) -> int:
        return self.pack_budget or self.cache_len * max(self.prefill_batch, 1)

    def ladder(self) -> Tuple[int, ...]:
        return bucket_ladder(self.min_prompt_bucket, self.cache_len,
                             self.round_multiple, self.max_buckets)


def prefill_split(prompt_len: int, ladder: Tuple[int, ...]) -> int:
    """Tokens to prefill at a bucketed shape; the rest replays via decode.

    Round-*down* quantization (paper semantics, ``pacing.quantize``).
    Prompts shorter than the smallest bucket prefill a single token and
    decode-replay the rest: N distinct short lengths share the one
    length-1 prefill executable, so the compiled shape set stays
    ``ladder U {1}`` (the bounded-jit-shape guarantee above — exact-length
    prefills used to leak one executable per distinct short length).
    """
    if prompt_len < ladder[0]:
        return 1
    return quantize(prompt_len, ladder)


@dataclass
class ActiveSlot:
    """Host-side bookkeeping for one occupied slot."""

    request: Request
    result: GenerationResult
    base_key: np.ndarray  # (2,) uint32 — host copy, folded on device
    last_token: int = 0

    @property
    def n_generated(self) -> int:
        return len(self.result.tokens)


class Scheduler:
    """Admission queue + slot lifecycle.  The engine executes; the
    scheduler decides which request occupies which slot and when a slot
    retires (per-slot stopping: length budget or stop token)."""

    def __init__(self, cfg: SchedulerConfig):
        if cfg.n_slots < 1 or cfg.cache_len < 1:
            raise ValueError(f"need n_slots >= 1 and cache_len >= 1, got "
                             f"{cfg.n_slots}, {cfg.cache_len}")
        if cfg.paged:
            if cfg.page_size < 1:
                raise ValueError(f"need page_size >= 1, got {cfg.page_size}")
            if cfg.resolved_n_pages < cfg.pages_per_slot:
                # any request _validate admits (prompt + max_tokens up to
                # cache_len) must eventually get its reservation once the
                # pool drains, else admission deadlocks with work pending
                raise ValueError(
                    f"n_pages {cfg.resolved_n_pages} cannot hold one "
                    f"maximal request ({cfg.pages_per_slot} pages = "
                    f"cache_len {cfg.cache_len} / page_size "
                    f"{cfg.page_size})")
        self.cfg = cfg
        self.ladder = cfg.ladder()
        self.pending: Deque[Request] = deque()
        self.active: Dict[int, ActiveSlot] = {}
        self.free: List[int] = list(range(cfg.n_slots))[::-1]  # pop() -> 0 first
        self.finished: List[GenerationResult] = []

    # -- admission ---------------------------------------------------------
    def _validate(self, request: Request, uids: set) -> None:
        need = request.prompt_len + request.max_tokens
        if need > self.cfg.cache_len:
            raise ValueError(
                f"request {request.uid}: prompt_len + max_tokens = {need} "
                f"exceeds cache_len {self.cfg.cache_len}")
        if request.max_tokens < 1:
            raise ValueError(f"request {request.uid}: max_tokens must be >= 1")
        if request.prompt_len < 1:
            raise ValueError(f"request {request.uid}: empty prompt")
        if request.uid in uids:
            # uids key result routing and the per-request PRNG stream
            raise ValueError(f"request uid {request.uid} already in flight")
        uids.add(request.uid)

    def _in_flight_uids(self) -> set:
        return ({r.uid for r in self.pending}
                | {s.request.uid for s in self.active.values()})

    @property
    def has_room(self) -> bool:
        return (not self.cfg.max_pending
                or len(self.pending) < self.cfg.max_pending)

    def submit(self, request: Request) -> None:
        if not self.has_room:
            raise QueueFull(f"pending queue at capacity "
                            f"({self.cfg.max_pending})")
        self._validate(request, self._in_flight_uids())
        self.pending.append(request)

    def submit_all(self, requests) -> None:
        """All-or-nothing admission: a validation failure anywhere in the
        batch enqueues nothing (a half-submitted batch would leak orphan
        pending requests into the caller's next drain).  ``requests`` is
        materialized once up front — a generator used to be exhausted by
        the validation pass, silently enqueueing nothing.  Overload is
        all-or-nothing too: if the whole batch does not fit under
        ``max_pending``, :class:`QueueFull`."""
        requests = list(requests)
        if self.cfg.max_pending and \
                len(self.pending) + len(requests) > self.cfg.max_pending:
            raise QueueFull(
                f"{len(requests)} requests exceed pending capacity "
                f"{self.cfg.max_pending} ({len(self.pending)} queued)")
        uids = self._in_flight_uids()
        for r in requests:
            self._validate(r, uids)
        self.pending.extend(requests)

    def validate_batch(self, requests) -> None:
        """Validation only (uid/shape checks against in-flight + each
        other), no enqueue — the engine validates its whole request set
        up front, then feeds it through the bounded queue incrementally."""
        uids = self._in_flight_uids()
        for r in requests:
            self._validate(r, uids)

    def enqueue_validated(self, request: Request) -> None:
        """Append one already-validated request (engine backlog feed)."""
        self.pending.append(request)

    def next_admission(self, k: int = 1, reserve=None
                       ) -> List[Tuple[int, Request]]:
        """Pop up to ``k`` same-split (free slot, request) pairs; [] if no
        slot or no request is available.

        The queue head fixes the prefill split; later pending requests
        with the same split are pulled forward to fill the batch (Lau et
        al.-style batch composition: same-shape prompts amortize one
        ``(k, bucket)`` prefill), skipped requests keep their relative
        order.

        ``reserve`` is the paged-admission budget hook
        (``PagedDecodeState.try_reserve``): called as ``reserve(slot,
        request)`` before a pair is emitted.  A False for the queue *head*
        returns [] with the queue untouched — strict FCFS, the head waits
        for pages freed by retiring slots rather than being starved by
        smaller requests jumping it.  A False for a pulled-forward
        candidate just skips that candidate (it kept its queue position
        anyway).
        """
        if not self.pending or not self.free:
            return []
        if reserve is not None and not reserve(self.free[-1],
                                               self.pending[0]):
            return []
        head = self.pending.popleft()
        out = [(self.free.pop(), head)]
        if k > 1:
            split = prefill_split(head.prompt_len, self.ladder)
            skipped: List[Request] = []
            while self.pending and self.free and len(out) < k:
                r = self.pending.popleft()
                if prefill_split(r.prompt_len, self.ladder) != split:
                    skipped.append(r)
                    continue
                if reserve is not None and not reserve(self.free[-1], r):
                    skipped.append(r)
                    continue
                out.append((self.free.pop(), r))
            self.pending.extendleft(reversed(skipped))
        return out

    def activate(self, slot: int, request: Request,
                 first_token: int, prefill_s: float) -> ActiveSlot:
        st = ActiveSlot(
            request=request,
            result=GenerationResult(uid=request.uid,
                                    prompt_len=request.prompt_len,
                                    prefill_s=prefill_s),
            base_key=np.asarray(sampling.request_key(request.sampling.seed,
                                                     request.uid)),
            last_token=first_token)
        st.result.tokens.append(first_token)
        self.active[slot] = st
        return st

    # -- stopping ----------------------------------------------------------
    def stop_reason(self, st: ActiveSlot) -> str:
        sp = st.request.sampling
        if sp.stop_token is not None and st.result.tokens \
                and st.result.tokens[-1] == sp.stop_token:
            return "stop_token"
        if st.n_generated >= st.request.max_tokens:
            return "length"
        return ""

    def finish(self, slot: int, reason: str) -> GenerationResult:
        st = self.active.pop(slot)
        st.result.finish_reason = reason
        self.free.append(slot)
        self.finished.append(st.result)
        return st.result

    def abort(self, slot: int, request: Request, detail: str = ""
              ) -> GenerationResult:
        """Retire a slot whose request failed: free the slot, record an
        ``error`` result so the caller still gets an answer for the uid.

        If the request had already activated, its partial result — tokens
        generated so far, possibly already streamed via ``on_token`` — is
        preserved on the error result (a fresh empty result here used to
        silently drop that work, so the caller saw tokens stream and then
        vanish from the final answer)."""
        st = self.active.pop(slot, None)
        if st is not None:  # activated before the failure surfaced
            res = st.result
            res.finish_reason = "error"
        else:
            res = GenerationResult(uid=request.uid,
                                   prompt_len=request.prompt_len,
                                   finish_reason="error")
        self.free.append(slot)
        self.finished.append(res)
        return res

    # -- state -------------------------------------------------------------
    @property
    def busy(self) -> bool:
        return bool(self.active) or bool(self.pending)
