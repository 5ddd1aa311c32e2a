"""Continuous-batching request scheduler for the GSI serving engine.

The engine decodes a *fixed-capacity* batch (one jit compilation, stable
shapes); the scheduler keeps that batch full.  Requests wait in an arrival
queue, admission control maps them onto free slots of the
:class:`~repro.serving.slots.SlotPool` (prompt prefill into the vacated
row via the engine's masked ``admit`` commit), and every engine step the
scheduler harvests finished slots — EOS, per-request step budget, or the
paper's B.2 early-stop — frees them, and admits the next queued prompts on
the following step.  This is the serving-layer analogue of the capacity
reclamation in Speculative Rejection (Sun et al., 2024) / RSD (Liao et
al., 2025): a request that finishes at step 3 stops paying for its three
KV-cache rows immediately instead of idling until the slowest request in
its gang completes.

With a paged engine, admission also consults the radix prefix cache
(serving/pages.py + serving/radix.py): the longest cached page-aligned
prefix of each prompt is spliced into the new slot's block table, only
the unmatched tail is prefilled and reserved, and under pool pressure
LRU unreferenced cached pages are evicted before a request is ever
deferred.  ``prefix_stats()`` reports hit/evict/reuse counters.

``continuous=False`` degrades to gang scheduling (admit only into an empty
pool, run the batch to completion) — the fixed-batch ``run()`` discipline,
timed against the continuous mode in ``benchmarks/throughput.py``.

``sync=False`` turns the lock-step loop into a two-stage pipeline: the
scheduler keeps one dispatched :class:`~repro.serving.gsi_engine.StepTicket`
in flight and runs step k+1's host work — the previous step's harvest
(token slicing, response assembly, stats folding) and admission — while
step k executes on the device.  Slot release stays *deferred one step*:
a slot whose request finishes at step k is released only after step k's
ticket has been materialized to host memory, so a slot is never
reacquired before its final tokens are harvested, and admission then sees
exactly the free-slot/free-page view the synchronous scheduler would —
which is what makes async == sync tokens bit-identical (same engine
steps, same slots, same rng keys) at any temperature.

Three SLO-facing mechanisms ride on top (``docs/SERVING.md`` "Traffic
shaping & SLOs"):

* **chunked prefill** (``chunk_tokens > 0``) — prompt prefill is metered
  to at most ``chunk_tokens`` tokens per engine step, shared between new
  admissions and mid-prefill continuations.  A long prompt is admitted
  truncated (its slot stays device-done, inert under the decode masks)
  and grows by one chunk per step via the engine's ``extend`` commit, so
  in-flight requests keep decoding instead of stalling behind one giant
  prefill.  At temperature 0 the committed context — hence the decoded
  tokens — is identical chunked or not.
* **priority preemption** — requests carry ``priority`` (larger = more
  urgent) and optional ``deadline_s``.  When a higher-priority arrival
  cannot be admitted, the lowest-priority live slot is *paused*: its
  full committed pages are published into the radix cache, the slot and
  pages are released, and the request re-queues with prompt + generated
  tokens as its resume context (spliced straight back from the cache on
  re-admission).  Preempt == pause, never drop; page conservation
  ``free + referenced + cached == num_pages`` holds across every
  preempt/resume.
* **token streaming** — ``submit(..., stream=cb)`` delivers a
  :class:`StreamEvent` per harvested step (and a final event) in
  materialize order, giving per-request TTFT and inter-token latency;
  :class:`TokenStream` adapts the callback to a blocking iterator.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.serving.gsi_engine import (EngineStats, GSIServingEngine,
                                      StepResult, StepTicket)
from repro.serving.slots import PAD, SlotPool, pack_prompts
from repro.serving.spans import HostClock


@dataclass
class Request:
    """A queued prompt awaiting admission (scheduler-internal record)."""

    id: str
    prompt: np.ndarray            # 1-D int32 token array (no padding)
    max_steps: int                # per-request reasoning-step budget
    arrival_time: float = 0.0     # seconds after scheduler start
    submitted_at: float = 0.0     # wall clock (perf_counter) at submit
    priority: int = 0             # larger = more urgent (0 = default class)
    deadline_s: Optional[float] = None   # SLO: finish within s of arrival
    queued_at: float = 0.0        # scheduler clock when it (re-)entered
                                  # the queue: where its queue wait starts


@dataclass
class Response:
    """One finished request: its step tokens, finish reason and timing."""

    request_id: str
    steps: List[np.ndarray] = field(default_factory=list)
    finish_reason: str = ""       # "eos" | "low_reward" | "max_steps"
    engine_steps: int = 0         # decode steps this request consumed
    admitted_at: float = 0.0      # seconds since scheduler start
    finished_at: float = 0.0
    arrival_time: float = 0.0
    priority: int = 0
    deadline_s: Optional[float] = None
    first_token_at: Optional[float] = None  # scheduler clock, first token
    preemptions: int = 0          # times this request was paused/resumed

    @property
    def tokens(self) -> np.ndarray:
        """All committed step tokens concatenated (PAD stripped)."""
        if not self.steps:
            return np.zeros((0,), np.int32)
        return np.concatenate([np.asarray(s, np.int32) for s in self.steps])

    @property
    def num_tokens(self) -> int:
        """Total committed tokens across the response's steps."""
        return int(self.tokens.size)

    @property
    def latency(self) -> float:
        """Queueing + decode latency, seconds since the request arrived."""
        return self.finished_at - self.arrival_time

    @property
    def ttft(self) -> float:
        """Time to first committed token since arrival (NaN if none)."""
        if self.first_token_at is None:
            return float("nan")
        return self.first_token_at - self.arrival_time

    @property
    def tpot(self) -> float:
        """Mean inter-token latency after the first token (time per
        output token; NaN with fewer than two tokens)."""
        n = self.num_tokens
        if self.first_token_at is None or n < 2:
            return float("nan")
        return (self.finished_at - self.first_token_at) / (n - 1)

    @property
    def deadline_missed(self) -> bool:
        """True iff a deadline was set and the total latency blew it."""
        return self.deadline_s is not None and self.latency > self.deadline_s


@dataclass
class StreamEvent:
    """One incremental streaming update for a request.

    Per-step events carry the step's non-PAD tokens in materialize order;
    the final event (``final=True``, possibly zero tokens) carries the
    finish reason.  ``t`` is the scheduler clock (seconds since start),
    so ``t`` of the first event minus the request's arrival time is its
    observed TTFT and gaps between events are inter-token latencies.
    """

    request_id: str
    tokens: np.ndarray
    step: int                     # engine steps the request has consumed
    final: bool = False
    finish_reason: str = ""
    t: float = 0.0


class TokenStream:
    """Thread-safe stream consumer: a callback that is also an iterator.

    Pass an instance as ``submit(..., stream=...)`` and iterate it from
    any thread: iteration yields :class:`StreamEvent` objects as the
    scheduler harvests them and ends after the final event.  Useful with
    the threaded router fleet, where the callback fires on a replica
    thread while the consumer iterates on the caller's.
    """

    def __init__(self):
        """Create an empty, open stream."""
        self._events: deque = deque()
        self._cv = threading.Condition()
        self._closed = False

    def __call__(self, event: StreamEvent) -> None:
        """Producer side: enqueue one event (scheduler harvest thread)."""
        with self._cv:
            self._events.append(event)
            if event.final:
                self._closed = True
            self._cv.notify_all()

    def __iter__(self):
        """Consumer side: block for events until the final one arrives."""
        while True:
            with self._cv:
                while not self._events and not self._closed:
                    self._cv.wait()
                if not self._events:
                    return
                event = self._events.popleft()
            yield event
            if event.final:
                return


@dataclass
class _InflightStep:
    """A dispatched-but-unmaterialized engine step (async pipeline).

    ``bound`` snapshots slot -> partial :class:`Response` at dispatch
    time, so the harvest attributes the step's rows to the requests that
    actually occupied the slots — even after the slots are released and
    re-admitted to newer requests.
    """

    ticket: StepTicket
    bound: Dict[int, Response]


@dataclass
class _RetiredStep:
    """A materialized step awaiting its deferred (overlapped) harvest.

    ``res`` is host numpy (the ticket was materialized before any of its
    slots could be released), so the heavy per-slot token slicing and
    response finalization can safely run while the *next* step executes
    on device.  ``finished`` carries the finish decisions — (slot,
    response, reason, finished_at) — made at release time.
    """

    res: StepResult
    bound: Dict[int, Response]
    finished: List[Tuple[int, Response, str, float]]


@dataclass
class _Prefill:
    """A slot mid chunked-prefill: how much of the prompt is committed.

    ``committed`` counts prompt tokens the engine holds for the slot
    (including the pending one), prefix-cache match included; the next
    chunk is ``req.prompt[committed : committed + chunk]``.  The slot is
    claimed and device-done until its final chunk commits.
    """

    req: Request
    committed: int


class GSIScheduler:
    """Drives ``GSIServingEngine.step_decode`` over a slot pool.

    Parameters
    ----------
    engine:      a built :class:`GSIServingEngine` (any mode).
    capacity:    number of slots == engine batch size (jit-stable).
    continuous:  admit into freed slots mid-flight (True) or only into an
                 empty pool (False, gang/fixed-batch discipline).
    collect_stats: forward per-step reward/ratio arrays into ``stats``.
    cache_aware: admission-ordering policy — when True, arrived queued
                 requests whose prompts have a *live* radix prefix match
                 are admitted before requests that would prefill cold.
                 Admitting a hit first both skips prefill work now and
                 keeps the matched pages referenced (they cannot be
                 evicted under pool pressure while the hit is decoding).
                 Requests with equal match state keep arrival order, a
                 deferral (out of pages) still blocks the whole queue,
                 and the queue head is never bypassed more than a
                 bounded number of consecutive admissions — so even an
                 endless stream of fresher cache hits cannot starve a
                 cold request.  Off by default because it reorders
                 sampling streams (router replicas enable it).
    sync:        True (default) runs the lock-step loop: every ``step``
                 dispatches one engine step and blocks for its results.
                 False runs the two-stage pipeline: one ticket stays in
                 flight and the previous step's harvest overlaps the
                 device execution (``step`` then returns the responses
                 *finalized* this call, which lag the decode by one
                 step until the pipeline drains).  Token streams are
                 bit-identical either way.
    chunk_tokens: per-engine-step prefill token budget (0 = off: whole
                 prompts prefill in one admit).  When set, admissions and
                 mid-prefill continuations share at most ``chunk_tokens``
                 committed prompt tokens per step, interleaved with the
                 live slots' decode — a long prompt no longer stalls
                 in-flight requests.  Greedy (temperature-0) outputs are
                 identical with chunking on or off.
    """

    def __init__(self, engine: GSIServingEngine, *, capacity: int,
                 continuous: bool = True, prompt_pad_len: int = 0,
                 collect_stats: bool = False, cache_aware: bool = False,
                 sync: bool = True, chunk_tokens: int = 0):
        """Build a scheduler over ``engine`` with ``capacity`` slots."""
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if chunk_tokens < 0:
            raise ValueError("chunk_tokens must be >= 0")
        self.engine = engine
        self.capacity = capacity
        self.continuous = continuous
        self.collect_stats = collect_stats
        self.cache_aware = cache_aware
        self.sync = sync
        self.pool = SlotPool(capacity)
        self.queue: deque = deque()
        self.state = engine.fresh_state(capacity)
        self.stats = EngineStats()
        self.responses: Dict[str, Response] = {}
        self.engine_steps = 0
        self._partial: Dict[int, Response] = {}      # slot -> in-flight
        self._steps_taken = np.zeros((capacity,), np.int64)
        self._budget = np.zeros((capacity,), np.int64)
        self._pad = int(prompt_pad_len)
        self._seq = 0
        self._t0: Optional[float] = None
        # SLO machinery: chunked prefill, priority preemption, streaming
        self._chunk = int(chunk_tokens)
        self._prefill: Dict[int, _Prefill] = {}      # slot -> mid-prefill
        self._live_req: Dict[int, Request] = {}      # slot -> its request
        # decode-time page publication bookkeeping: the slot's committed
        # context tokens (admitted prompt + every harvested step) and how
        # many full pages of it are already in the radix index
        self._ctx: Dict[int, np.ndarray] = {}        # slot -> context
        self._pub_full: Dict[int, int] = {}          # slot -> pages published
        self._paused: Dict[str, Response] = {}       # preempted, unfinished
        self._streams: Dict[str, object] = {}        # id -> stream callback
        self._ids: set = set()                       # every id ever submitted
        # cache-aware ordering may prefer hits over the queue head, but
        # never more than this many consecutive admissions (bounded
        # head-of-line starvation; FIFO order bounds everyone behind it)
        self._bypass_limit = 8
        self._head_bypassed = 0
        # async pipeline state: at most one dispatched-unmaterialized
        # ticket plus one materialized-unharvested step
        self._inflight: Optional[_InflightStep] = None
        self._retired: Optional[_RetiredStep] = None
        # idle handling: woken by submit(), waits out exact arrival gaps
        self._wake = threading.Condition()
        # host/device overlap accounting (pipeline_stats), one profiler
        # span per timed region: overlap_host_s (host work under an
        # in-flight step), serial_host_s (host work with the device idle),
        # materialize_wait_s (blocked on device results), dispatch_s
        # (enqueueing steps, compiles included)
        self._clock = HostClock()

    def fresh_state(self) -> None:
        """Reset for a new serving phase (back-to-back benchmark runs).

        Rebuilds the engine state — which, for a paged engine, also
        rebuilds the page pool and radix index — and resets *all*
        scheduler bookkeeping with it: queue, slot pool, responses and
        the stats counters ``prefix_stats()`` reads.  Without the stat
        reset a second phase on the same scheduler would report the
        previous phase's hits folded into its own (stale hit-rates).
        """
        self.state = self.engine.fresh_state(self.capacity)
        self.pool = SlotPool(self.capacity)
        self.queue.clear()
        self.stats = EngineStats()
        self.responses = {}
        self.engine_steps = 0
        self._partial = {}
        self._steps_taken[:] = 0
        self._budget[:] = 0
        self._t0 = None
        self._prefill = {}
        self._live_req = {}
        self._ctx = {}
        self._pub_full = {}
        self._paused = {}
        self._streams = {}
        self._ids = set()
        self._head_bypassed = 0
        self._inflight = None
        self._retired = None
        self._clock = HostClock()

    # ------------------------------------------------------------------
    # Submission / admission control
    # ------------------------------------------------------------------
    def submit(self, prompt, *, request_id: Optional[str] = None,
               max_steps: Optional[int] = None,
               arrival_time: float = 0.0, priority: int = 0,
               deadline_s: Optional[float] = None,
               stream=None) -> str:
        """Queue a prompt; returns the request id.

        ``priority`` (larger = more urgent) orders admission across
        classes and arms preemption: a deferring higher-priority request
        pauses the lowest-priority live slot.  ``deadline_s`` is the SLO
        latency target (arrival to finish) — purely accounting, see
        ``Response.deadline_missed``.  ``stream`` is an optional callable
        (e.g. a :class:`TokenStream`) receiving one :class:`StreamEvent`
        per harvested step plus a final event.

        Request ids are unique for the scheduler's lifetime: reusing an
        id — even one whose first request already finished — raises
        (``self.responses`` is id-keyed; a silent overwrite would corrupt
        the earlier response's ledger entry).
        """
        g = self.engine.gcfg
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        budget = int(max_steps if max_steps is not None else g.max_steps)
        if budget < 1:
            raise ValueError("max_steps must be >= 1")
        need = self.engine.positions_needed(prompt.size, budget)
        if need > self.engine.max_seq:
            raise ValueError(
                f"request needs up to {need} cache positions but engine "
                f"max_seq={self.engine.max_seq}; shorten the prompt or "
                f"lower max_steps")
        if getattr(self.engine, "paged", False):
            blocks = self.engine.blocks_needed(prompt.size, budget)
            if blocks > self.engine.num_pages:
                raise ValueError(
                    f"request needs up to {blocks} pages but the pool "
                    f"only has {self.engine.num_pages}; it could never "
                    f"be admitted")
        if request_id is None:
            while f"req-{self._seq}" in self._ids:
                self._seq += 1
            request_id = f"req-{self._seq}"
        elif request_id in self._ids:
            raise ValueError(
                f"duplicate request id {request_id!r}: ids must be unique "
                f"for the scheduler's lifetime (responses are keyed by id)")
        self._ids.add(request_id)
        if stream is not None:
            self._streams[request_id] = stream
        self._seq += 1
        # a request submitted once the scheduler clock runs waits from
        # its submission at the earliest (closed-loop clients submit
        # with arrival_time 0); before that, from its arrival_time
        queued_at = float(arrival_time) if self._t0 is None \
            else max(float(arrival_time), self._now())
        self.queue.append(Request(
            id=request_id, prompt=prompt, max_steps=budget,
            arrival_time=float(arrival_time),
            submitted_at=time.perf_counter(),
            priority=int(priority), deadline_s=deadline_s,
            queued_at=queued_at))
        if len(self.queue) > 1 and \
                arrival_time < self.queue[-2].arrival_time:
            # keep the queue arrival-ordered (stable for equal arrivals) so
            # an early arrival is never head-of-line blocked behind a
            # not-yet-arrived request submitted before it
            self.queue = deque(sorted(self.queue,
                                      key=lambda r: r.arrival_time))
        with self._wake:
            self._wake.notify_all()      # run() may be idle-waiting
        return request_id

    def _now(self) -> float:
        if self._t0 is None:
            self._t0 = time.perf_counter()
        return time.perf_counter() - self._t0

    def _ready(self, now: float) -> bool:
        return bool(self.queue) and self.queue[0].arrival_time <= now

    def _pick_ready(self, now: float):
        """Pick the next request to admit.

        Returns ``(queue_index, shared_pages, hit_tokens)`` — the match
        is computed here once and reused by the admission path, so each
        candidate costs exactly one host-side trie walk.

        Selection is two-level.  First the highest *priority* among
        arrived requests wins outright — priority deliberately overrides
        both FIFO order and the bypass bound (that is what priority
        classes mean; the starvation guarantee below holds *within* a
        class).  Within the winning class: FIFO by default; with
        ``cache_aware=True`` the request with the longest live radix
        prefix match wins (a hit admitted now skips prefill and pins its
        matched pages before anything can evict them), arrival order
        breaking ties.  The class's FIFO-first request is never bypassed
        more than ``_bypass_limit`` consecutive admissions — a
        bounded-starvation guarantee that holds even against an endless
        stream of fresher cache hits.
        """
        # the arrived highest-priority class, FIFO-ordered (the queue is
        # arrival-ordered, so stop at the first future arrival)
        tier: List[int] = []
        top = None
        for i, req in enumerate(self.queue):
            if req.arrival_time > now:
                break
            if top is None or req.priority > top:
                top, tier = req.priority, [i]
            elif req.priority == top:
                tier.append(i)
        if not tier:
            tier = [0]                 # caller guarantees _ready(now)
        lead = tier[0]
        if not self.cache_aware or len(tier) == 1 \
                or self._head_bypassed >= self._bypass_limit:
            return (lead,) + self.engine.match_prefix(
                self.queue[lead].prompt)
        best = None
        for i in tier:
            shared, hit = self.engine.match_prefix(self.queue[i].prompt)
            if best is None or hit > best[2]:
                best = (i, shared, hit)
        return best

    def _admit_ready(self, now: float) -> List[str]:
        """Advance mid-prefill slots, then move arrived requests from the
        queue into free slots.

        Each admission first consults the engine's radix prefix cache: the
        longest cached page-aligned prefix of the prompt is spliced into
        the slot's block table and only the tail is prefilled.  Paged
        engines additionally gate on free pages — counting LRU-evictable
        cached pages, so admission prefers evicting cold prefix pages over
        deferring.  A request that still doesn't fit may *preempt* a
        strictly-lower-priority live slot (pause + page publication, see
        ``_preempt``); otherwise admission stops (the request stays
        queued — back-pressure, never dropped) and retries on a later
        step once finished requests have returned pages.

        With ``chunk_tokens`` set, continuations and new admissions share
        one per-step prefill token budget: a prompt whose tail exceeds
        what is left admits *truncated* (its slot inert until ``extend``
        commits the rest, one chunk per step).

        ``now`` decides which requests have arrived; the scheduler clock
        read on entry stamps the admissions (``Response.admitted_at``)
        and ends their queue wait (``stats.queue_wait_s``).
        """
        at = self._now()
        budget = self._chunk if self._chunk else None
        budget = self._advance_prefill(now, budget)
        if not self.continuous and self.pool.num_live > 0:
            return []
        batch: Dict[int, Tuple[Request, np.ndarray]] = {}
        starts = np.zeros((self.capacity,), np.int32)
        live = np.ones((self.capacity,), bool)
        committed_total = 0
        while self._ready(now):
            if budget is not None and budget <= 0:
                break                  # this step's prefill budget is spent
            free = [s for s in self.pool.free_slots() if s not in batch]
            pick, shared, hit_tok = self._pick_ready(now)
            req = self.queue[pick]
            if not free or not self.engine.admit_ok(
                    req.prompt.size, req.max_steps, shared=shared):
                # a deferring higher-priority request may pause a live
                # lower-priority slot instead of waiting behind it
                if self._try_preempt(req, now):
                    continue           # re-pick: slot/pages freed, cache grew
                break                  # true back-pressure: defer, keep order
            if pick and req.priority == self.queue[0].priority:
                self._head_bypassed += 1
            elif not pick:
                self._head_bypassed = 0
            del self.queue[pick]
            slot = free[0]
            if self._inflight is not None and \
                    slot in self._inflight.bound:
                # deferred-release invariant: a slot bound by a ticket
                # still in flight has not had its final tokens
                # materialized — admission must never reacquire it
                raise RuntimeError(
                    f"slot {slot} reacquired while its step is still in "
                    f"flight (deferred-release invariant violated)")
            self.engine.claim_slot(slot, req.prompt.size, req.max_steps,
                                   shared=shared)
            tail = req.prompt.size - hit_tok
            take = tail if budget is None else min(tail, budget)
            committed = hit_tok + take
            if committed < req.prompt.size:
                # chunked admission: only prompt[:committed] prefills now
                live[slot] = False
                self._prefill[slot] = _Prefill(req=req, committed=committed)
            if budget is not None:
                budget -= take
            committed_total += take
            batch[slot] = (req, req.prompt[:committed])
            starts[slot] = hit_tok
            self.stats.bump(
                prefix_queries=1, prefix_hits=int(bool(hit_tok)),
                prefix_hit_tokens=int(hit_tok),
                prefix_pages_reused=len(shared),
                prefill_tokens=max(tail - 1, 0))
        if not batch:
            return []
        longest = max(p.size for _, p in batch.values())
        if longest > self._pad:
            # round up so prompt-length jitter doesn't retrace _jit_admit
            self._pad = -(-longest // 8) * 8
        packed = pack_prompts({s: p for s, (_, p) in batch.items()},
                              self.capacity, self._pad)
        mask = np.zeros((self.capacity,), bool)
        for slot, (req, committed_prompt) in batch.items():
            mask[slot] = True
            self.pool.claim(slot, req.id)
            self._live_req[slot] = req
            # seed the decode-publication bookkeeping: admit() publishes
            # exactly the committed prompt's full pages below
            self._ctx[slot] = np.asarray(committed_prompt, np.int32)
            self._pub_full[slot] = max(committed_prompt.size - 1, 0) \
                // self.engine.page_size
            self._steps_taken[slot] = 0
            self._budget[slot] = req.max_steps
            resp = self._paused.pop(req.id, None)
            if resp is not None:
                self.stats.bump(resumes=1)   # resumed after a preemption
            else:
                resp = Response(
                    request_id=req.id, admitted_at=at,
                    arrival_time=req.arrival_time,
                    priority=req.priority, deadline_s=req.deadline_s)
            self._partial[slot] = resp
        self.stats.bump(admissions=len(batch), queue_wait_s=sum(
            at - req.queued_at for req, _ in batch.values()),
            prefill_batched=len(batch) * self.engine.batched_prefill)
        self.state = self.engine.admit(self.state, mask, packed, starts,
                                       live=live)
        self.stats.prefill_commit_max = max(
            self.stats.prefill_commit_max, committed_total)
        pager = getattr(self.engine, "pager", None)
        if pager is not None:
            self.stats.pages_evicted = pager.evicted
        return [req.id for req, _ in batch.values()]

    def _advance_prefill(self, now: float,
                         budget: Optional[int]) -> Optional[int]:
        """Commit the next chunk of every mid-prefill slot, spending from
        this step's prefill token ``budget``; returns what is left for
        new admissions.

        Slots advance in slot order.  A slot whose final chunk commits
        comes up live (it decodes from the next engine step — exactly
        the state a one-shot admit would have left it in) and its
        prompt's full pages are published to the radix index.
        """
        if not self._prefill:
            return budget
        mask = np.zeros((self.capacity,), bool)
        live = np.zeros((self.capacity,), bool)
        chunks: Dict[int, np.ndarray] = {}
        total = 0
        for slot in sorted(self._prefill):
            if budget is not None and budget <= 0:
                break
            pf = self._prefill[slot]
            remaining = pf.req.prompt.size - pf.committed
            take = remaining if budget is None else min(remaining, budget)
            chunks[slot] = pf.req.prompt[pf.committed:pf.committed + take]
            mask[slot] = True
            pf.committed += take
            self._ctx[slot] = pf.req.prompt[:pf.committed].astype(np.int32)
            total += take
            if budget is not None:
                budget -= take
            if pf.committed == pf.req.prompt.size:
                live[slot] = True
        if not chunks:
            return budget
        width = max(c.size for c in chunks.values())
        if self._chunk:
            # fixed width (the chunk budget, rounded up) keeps
            # _jit_extend from retracing on chunk-length jitter
            width = max(width, self._chunk)
        width = -(-width // 8) * 8
        packed = np.full((self.capacity, width), PAD, np.int32)
        for slot, c in chunks.items():
            packed[slot, :c.size] = c
        self.state = self.engine.extend(self.state, mask, packed, live)
        if self.engine.batched_prefill:
            self.stats.bump(prefill_batched=len(chunks))
        self.stats.prefill_commit_max = max(
            self.stats.prefill_commit_max, total)
        for slot in np.nonzero(live)[0]:
            pf = self._prefill.pop(int(slot))
            self.engine.publish_prefix(int(slot), pf.req.prompt)
            self._pub_full[int(slot)] = \
                max(pf.req.prompt.size - 1, 0) // self.engine.page_size
        return budget

    # ------------------------------------------------------------------
    # Decode-time page publication
    # ------------------------------------------------------------------
    def _publish_decode(self, slot: int, toks: np.ndarray) -> None:
        """Fold one harvested step's tokens into the slot's committed
        context and publish every newly *filled* page to the radix
        index — the decode-time extension of the admission publish.

        Runs strictly after the step's commit was ordered on the device
        stream (the step is materialized before any harvest) and before
        the slot could be released, so a published page's content is
        complete and its refcount is still held — the same ordering
        contract ``admit`` obeys.  Per the engine invariant the
        context's last token is pending, so exactly
        ``(len - 1) // page_size`` pages are full.  No-op unless the
        engine has ``decode_publish`` (and a live prefix cache).
        """
        ctx = self._ctx.get(slot)
        if ctx is None:
            return
        if toks.size:
            ctx = np.concatenate([ctx, np.asarray(toks, np.int32)])
            self._ctx[slot] = ctx
        eng = self.engine
        if not getattr(eng, "decode_publish", False):
            return
        full = max(ctx.size - 1, 0) // eng.page_size
        if full <= self._pub_full.get(slot, 0):
            return                        # no page filled this step
        published = eng.publish_prefix(slot, ctx)
        self._pub_full[slot] = full
        if published:
            self.stats.bump(decode_pages_published=published)

    def _drop_ctx(self, slot: int) -> None:
        """Forget a released/preempted slot's publication bookkeeping."""
        self._ctx.pop(slot, None)
        self._pub_full.pop(slot, None)

    # ------------------------------------------------------------------
    # Priority preemption
    # ------------------------------------------------------------------
    def _try_preempt(self, req: Request, now: float) -> bool:
        """Pause the lowest-priority live slot strictly below
        ``req.priority`` so ``req`` can admit; False if no such victim.

        Victim order: lowest priority first, then fewest decode steps
        taken (least progress to replay on engines without a prefix
        cache), then lowest slot.  Mid-prefill slots are not preemptible:
        their request has produced nothing and holds no published pages —
        pausing one would only reshuffle the prefill budget.
        """
        victim = None
        for slot in self.pool.live_slots():
            if slot in self._prefill:
                continue
            vreq = self._live_req[slot]
            if vreq.priority >= req.priority:
                continue
            key = (vreq.priority, int(self._steps_taken[slot]), slot)
            if victim is None or key < victim:
                victim = key
        if victim is None:
            return False
        self._preempt(victim[2], now)
        return True

    def _preempt(self, slot: int, now: float) -> None:
        """Pause the live request in ``slot``: publish its committed
        pages, release the slot and its pages, requeue it for resume.

        The request's committed context (prompt + every harvested step)
        becomes the resume prompt; with a radix cache its full pages were
        just published, so re-admission splices them straight back and
        re-prefills at most one page worth of tail.  The partial
        :class:`Response` parks in ``_paused`` and keeps accumulating on
        resume — preempt is a pause, never a drop, and page conservation
        (``free + referenced + cached == num_pages``) holds throughout.
        """
        req = self._live_req.pop(slot)
        resp = self._partial.pop(slot)
        # async: the victim's latest step may still await its deferred
        # harvest — fold those tokens in before building the context
        if self._retired is not None and slot in self._retired.bound:
            res = self._retired.res
            self._retired.bound.pop(slot)
            if not res.done_prev[slot]:
                toks = res.chosen[slot]
                self._emit_step(resp, toks[toks != PAD], now)
        context = np.concatenate(
            [req.prompt.astype(np.int32), resp.tokens])
        mask = np.zeros((self.capacity,), bool)
        mask[slot] = True
        self.state = self.engine.force_done(self.state, mask)
        self.engine.preempt_slot(slot, context)
        self.pool.release(slot)
        self._drop_ctx(slot)
        remaining = int(self._budget[slot] - self._steps_taken[slot])
        resp.preemptions += 1
        self.stats.bump(preemptions=1)
        self._paused[req.id] = resp
        resumed = Request(
            id=req.id, prompt=context, max_steps=remaining,
            arrival_time=req.arrival_time, submitted_at=req.submitted_at,
            priority=req.priority, deadline_s=req.deadline_s,
            queued_at=self._now())
        self.queue.appendleft(resumed)
        if len(self.queue) > 1 and \
                self.queue[1].arrival_time < resumed.arrival_time:
            self.queue = deque(sorted(self.queue,
                                      key=lambda r: r.arrival_time))

    def preempt(self, request_id: str) -> bool:
        """Manually pause a live request (the mechanism priority
        admission uses).  Returns False when the request is not in a
        preemptible state: unknown / queued / mid-prefill / finished.

        Drains the async pipeline first so the request's final harvested
        state is known — the drain may even *finish* it (EOS already in
        flight), in which case there is nothing left to preempt.
        """
        if self._inflight is not None or self._retired is not None:
            self.flush()
        slot = self.pool.slot_of(request_id)
        if slot is None or slot in self._prefill:
            return False
        self._preempt(slot, self._now())
        return True

    def prefix_stats(self) -> Dict[str, float]:
        """Prefix-cache admission counters.

        ``queries`` and ``prefill_tokens`` count every admission on any
        engine (they are the baseline the sharing runs are compared
        against); ``hits``/``hit_tokens``/``pages_*`` stay zero for dense
        engines or when sharing is off/unsupported.
        """
        s = self.stats
        pager = getattr(self.engine, "pager", None)
        return {
            "queries": s.prefix_queries,
            "hits": s.prefix_hits,
            "hit_rate": s.prefix_hit_rate,
            "hit_tokens": s.prefix_hit_tokens,
            "pages_reused": s.prefix_pages_reused,
            "prefill_tokens": s.prefill_tokens,
            "pages_evicted": s.pages_evicted,
            "pages_published_decode": s.decode_pages_published,
            "pages_cached": 0 if pager is None else pager.num_cached,
        }

    # ------------------------------------------------------------------
    # Stepping
    # ------------------------------------------------------------------
    def step(self, rng, rng_target=None) -> List[Response]:
        """Admit ready requests, run one engine decode step, harvest and
        free finished slots.

        ``sync=True``: dispatches and materializes one step, returning
        the responses finished *this* step.  ``sync=False``: pumps the
        pipeline (harvest + admission for the in-flight step) and
        dispatches the next step without waiting for it — returned
        responses are the ones finalized this call, which lag the decode
        by one step until the pipeline drains (``flush``).
        """
        if self.sync:
            return self._step_sync(rng, rng_target)
        now = self._now()
        finished = self._pump(now)
        if self.pool.num_live:
            self._dispatch(rng, rng_target)
        else:
            finished += self.flush()
        return finished

    def _step_sync(self, rng, rng_target=None) -> List[Response]:
        """The lock-step path: one dispatched + materialized step."""
        now = self._now()
        self._admit_ready(now)
        if self.pool.num_live == 0:
            return []
        self.state, res = self.engine.step_decode(
            self.state, rng, rng_target, stats=self.stats,
            collect_stats=self.collect_stats)
        self.engine_steps += 1
        finished: List[Response] = []
        force_done = np.zeros((self.capacity,), bool)
        for slot in self.pool.live_slots():
            if res.done_prev[slot]:
                continue               # mid-prefill rows are device-inert
            resp = self._partial[slot]
            toks = res.chosen[slot]
            kept = toks[toks != PAD]
            self._emit_step(resp, kept, self._now())
            # publish the pages this step filled *before* any release
            # below could drop the slot's page references
            self._publish_decode(slot, kept)
            self._steps_taken[slot] += 1
            reason = ""
            if res.eos[slot]:
                reason = "eos"
            elif res.failed[slot]:
                reason = "low_reward"
            elif self._steps_taken[slot] >= self._budget[slot]:
                reason = "max_steps"
                force_done[slot] = True
            if reason:
                self.pool.release(slot)
                self.engine.release_slot(slot)
                del self._partial[slot]
                self._live_req.pop(slot, None)
                self._drop_ctx(slot)
                self._finalize(resp, reason, self._now())
                finished.append(resp)
        self.state = self.engine.force_done(self.state, force_done)
        return finished

    def _emit_step(self, resp: Response, toks: np.ndarray,
                   now: float) -> None:
        """Append one harvested step's tokens to ``resp`` and fire its
        stream callback (streams observe materialize order)."""
        resp.steps.append(toks)
        resp.engine_steps += 1
        if toks.size and resp.first_token_at is None:
            resp.first_token_at = now
        cb = self._streams.get(resp.request_id)
        if cb is not None and toks.size:
            cb(StreamEvent(request_id=resp.request_id, tokens=toks,
                           step=resp.engine_steps, t=now))

    def _finalize(self, resp: Response, reason: str, at: float) -> None:
        """Stamp a finished response, account its SLO and close its
        stream (one final event carrying the finish reason)."""
        resp.finish_reason = reason
        resp.finished_at = at
        self.responses[resp.request_id] = resp
        self.stats.bump(requests_finished=1)
        if resp.deadline_missed:
            self.stats.bump(deadline_misses=1)
        cb = self._streams.pop(resp.request_id, None)
        if cb is not None:
            cb(StreamEvent(request_id=resp.request_id,
                           tokens=np.zeros((0,), np.int32),
                           step=resp.engine_steps, final=True,
                           finish_reason=reason, t=at))

    # ------------------------------------------------------------------
    # Async pipeline (sync=False)
    # ------------------------------------------------------------------
    @property
    def has_pending(self) -> bool:
        """True while the pipeline holds an unharvested step."""
        return self._inflight is not None or self._retired is not None

    def _pump(self, now: float) -> List[Response]:
        """Advance the pipeline up to (not including) the next dispatch.

        Order matters for both overlap and identity:

        1. heavy-harvest the step materialized last call — token
           slicing, response finalization, stats folding — *while the
           in-flight step executes on device* (this is the overlapped
           host work the pipeline exists for);
        2. materialize the in-flight ticket (one batched ``device_get``;
           the only point the host blocks on the device);
        3. retire it: decide finish reasons, release finished slots —
           release is thereby deferred exactly one step, and the
           final tokens are already in host memory when the slot frees;
        4. admit — seeing the same freed slots and pages the
           synchronous scheduler would see before this engine step.
        """
        finished: List[Response] = []
        clock = self._clock
        if self._retired is not None:
            retired, self._retired = self._retired, None
            key = "serial_host_s" if self._inflight is None \
                else "overlap_host_s"
            with clock.span("sched.harvest", key):
                finished = self._harvest(retired)
        if self._inflight is not None:
            pend, self._inflight = self._inflight, None
            with clock.span("sched.materialize", "materialize_wait_s"):
                res = self.engine.materialize(pend.ticket)
            with clock.span("sched.retire", "serial_host_s"):
                self._retire(pend, res)
        with clock.span("sched.admit", "serial_host_s"):
            self._admit_ready(now)
        return finished

    def _retire(self, pend: _InflightStep, res: StepResult) -> None:
        """Decide finishes for a just-materialized step and free slots.

        The cheap, order-critical part of the harvest: budget counting,
        finish reasons, slot + page release and the budget force-done —
        everything admission parity with the synchronous scheduler
        depends on.  The heavy per-slot work is deferred to ``_harvest``
        via ``self._retired``.
        """
        now = self._now()
        force_done = np.zeros((self.capacity,), bool)
        finished: List[Tuple[int, Response, str, float]] = []
        for slot, resp in pend.bound.items():
            if res.done_prev[slot]:
                continue
            toks = res.chosen[slot]
            # res is already host numpy (the ticket was materialized just
            # above), so publication here has the same commit-then-publish
            # ordering as the synchronous path — and precedes the release
            self._publish_decode(slot, toks[toks != PAD])
            self._steps_taken[slot] += 1
            reason = ""
            if res.eos[slot]:
                reason = "eos"
            elif res.failed[slot]:
                reason = "low_reward"
            elif self._steps_taken[slot] >= self._budget[slot]:
                reason = "max_steps"
                force_done[slot] = True
            if reason:
                self.pool.release(slot)
                self.engine.release_slot(slot)
                del self._partial[slot]
                self._live_req.pop(slot, None)
                self._drop_ctx(slot)
                finished.append((slot, resp, reason, now))
        self.state = self.engine.force_done(self.state, force_done)
        self._retired = _RetiredStep(res=res, bound=pend.bound,
                                     finished=finished)

    def _harvest(self, retired: _RetiredStep) -> List[Response]:
        """Heavy harvest of a retired step (runs under the next step).

        Appends every bound slot's step tokens to its partial response,
        finalizes the responses whose finish reason fired, and folds the
        step into ``stats`` — all pure host numpy on data materialized
        before any of these slots could have been reused.
        """
        res = retired.res
        now = self._now()
        for slot, resp in retired.bound.items():
            if res.done_prev[slot]:
                continue
            toks = res.chosen[slot]
            self._emit_step(resp, toks[toks != PAD], now)
        done_now: List[Response] = []
        for slot, resp, reason, at in retired.finished:
            # finalize at harvest time, not retire time: the finish is
            # client-visible only once its tokens are (keeps
            # finished_at >= first_token_at, so TPOT is never negative)
            self._finalize(resp, reason, now)
            done_now.append(resp)
        self.engine.fold_step_stats(res, self.stats, self.collect_stats)
        return done_now

    def _dispatch(self, rng, rng_target=None) -> None:
        """Dispatch the next engine step and leave its ticket in flight."""
        with self._clock.span("sched.dispatch", "dispatch_s"):
            self.state, ticket = self.engine.dispatch_decode(
                self.state, rng, rng_target)
            self.engine_steps += 1
            self._inflight = _InflightStep(ticket=ticket,
                                           bound=dict(self._partial))

    def flush(self) -> List[Response]:
        """Drain the pipeline without dispatching: materialize the
        in-flight ticket (if any) and harvest everything retired.
        Returns the responses finalized by the drain."""
        finished: List[Response] = []
        clock = self._clock
        if self._inflight is not None:
            pend, self._inflight = self._inflight, None
            with clock.span("sched.materialize", "materialize_wait_s"):
                res = self.engine.materialize(pend.ticket)
            with clock.span("sched.retire", "serial_host_s"):
                self._retire(pend, res)
        if self._retired is not None:
            retired, self._retired = self._retired, None
            with clock.span("sched.harvest", "serial_host_s"):
                finished = self._harvest(retired)
        return finished

    def pipeline_stats(self) -> Dict[str, float]:
        """Host/device overlap accounting for the async pipeline.

        ``overlap_fraction`` is the share of host *bookkeeping* time
        (harvest + admission; dispatch enqueueing and one-off jit
        compiles are reported separately as ``dispatch_s``) that ran
        while an engine step was executing on the device — 0.0 for a
        purely synchronous scheduler.  ``materialize_wait_s`` is the
        time the host spent blocked on device results.
        """
        sec = self._clock.seconds
        overlap, serial = sec["overlap_host_s"], sec["serial_host_s"]
        total = overlap + serial
        return {
            "sync": self.sync,
            "overlap_host_s": overlap,
            "serial_host_s": serial,
            "materialize_wait_s": sec["materialize_wait_s"],
            "dispatch_s": sec["dispatch_s"],
            "overlap_fraction": overlap / total if total > 0 else 0.0,
        }

    # ------------------------------------------------------------------
    # Run loops
    # ------------------------------------------------------------------
    def _wait_next_arrival(self) -> None:
        """Idle until the head queued request arrives (or a new submit
        wakes us) — an exact condition-variable wait, not a capped
        ``time.sleep`` poll, so sub-50ms arrival gaps cost exactly the
        gap."""
        wait = self.queue[0].arrival_time - self._now()
        if wait > 0:
            with self._wake:
                self._wake.wait(timeout=wait)

    def run(self, rng) -> Dict[str, Response]:
        """Drain the queue and all live slots; returns id -> Response."""
        self._t0 = time.perf_counter()
        for req in self.queue:
            # the clock restarts here: arrival times count from now on
            req.queued_at = req.arrival_time
        if not self.sync:
            return self._run_async(rng)
        while self.queue or self.pool.num_live:
            if self.pool.num_live == 0 and not self._ready(self._now()):
                self._wait_next_arrival()     # idle until the next arrival
                continue
            rng, k1, k2 = jax.random.split(rng, 3)
            self._step_sync(k1, k2)
        return dict(self.responses)

    def _run_async(self, rng) -> Dict[str, Response]:
        """Pipelined drain: rng is split once per *dispatched* engine
        step (never on drain-only iterations), keeping the per-step key
        sequence identical to the synchronous loop's."""
        while (self.queue or self.pool.num_live or self.has_pending):
            now = self._now()
            if (self.pool.num_live == 0 and not self.has_pending
                    and not self._ready(now)):
                self._wait_next_arrival()
                continue
            self._pump(now)
            if self.pool.num_live:
                rng, k1, k2 = jax.random.split(rng, 3)
                self._dispatch(k1, k2)
            else:
                self.flush()
        return dict(self.responses)
