"""Driving the program: clients that send the traffic, and observers that
record what each engine call did.

:class:`Clients` sends a mix's requests through the frontend's ``submit``
and hears every committed step through the frontend's token stream, on
the host clock: the times the end-to-end metrics are read from.

:class:`Observer` wraps one engine's ``admit``, ``dispatch_decode`` and
``materialize`` (on the instance; the program is not edited).  It puts a
host span (``TraceAnnotation``) around each call and keeps, per request,
the rows the engine produced for it: the committed tokens, the accept
decision and the candidates' tilted rewards.
``stops`` counts the requests the engine ended early (paper B.2).
The check compares those rows with the reference; the work counts read
the admissions and the steps.  ``dispatches`` counts the engine steps.
"""
from __future__ import annotations

import time
from collections import defaultdict, deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

PAD = 0


def clock() -> float:
    return time.perf_counter()


@dataclass
class StepRow:
    """What one engine step produced for one live request."""
    tokens: np.ndarray        # committed tokens (pad dropped)
    accept: bool
    tilted: np.ndarray        # (n,) tilted rewards of the draft candidates
    pad_inside: bool          # a sampled pad id before the step's end
    pad_end: bool             # a pad id sampled as the step's last token
    dispatch: int             # index of the engine step among dispatches


@dataclass
class Admission:
    prompt_len: int
    hit: int                  # prompt tokens served from the prefix cache


class Observer:
    """Records one engine's admissions and steps (see module doc)."""

    def __init__(self, engine, scheduler):
        self.engine, self.scheduler = engine, scheduler
        self.rows: Dict[str, List[StepRow]] = defaultdict(list)
        self.admissions: List[Admission] = []
        self.dispatches = 0
        self.stops = 0
        self._owners: deque = deque()
        admit, dispatch = engine.admit, engine.dispatch_decode
        materialize = engine.materialize
        ends = (engine.gcfg.sep_token_id, engine.gcfg.eos_token_id)

        def admit_w(state, admit_mask, prompts, starts=None, live=None):
            mask = np.asarray(admit_mask, bool)
            lens = (np.asarray(prompts) != PAD).sum(axis=1)
            st = np.zeros(mask.shape, np.int64) if starts is None \
                else np.asarray(starts)
            for slot in np.nonzero(mask)[0]:
                self.admissions.append(Admission(int(lens[slot]),
                                                 int(st[slot])))
            with TraceAnnotation("admit"):
                return admit(state, admit_mask, prompts, starts, live)

        def dispatch_w(state, rng, rng_target=None):
            pool = self.scheduler.pool
            owners = {s: pool.request_of(s) for s in pool.live_slots()}
            with TraceAnnotation("dispatch_decode"):
                out = dispatch(state, rng, rng_target)
            self._owners.append((self.dispatches, owners))
            self.dispatches += 1
            return out

        def materialize_w(ticket):
            with TraceAnnotation("materialize"):
                res = materialize(ticket)
            index, owners = self._owners.popleft()
            for slot, rid in owners.items():
                if res.done_prev[slot] or rid is None:
                    continue
                toks = np.asarray(res.chosen[slot])
                real = toks[toks != PAD]
                inside = real.size > 0 and \
                    not np.all(toks[:real.size] != PAD)
                # a step ends early only at a separator or end of
                # sequence, or where its last token sampled was a pad
                end = real.size == toks.size - 1 and not inside and \
                    int(real[-1]) not in ends
                self.rows[rid].append(StepRow(
                    real.copy(), bool(res.accept[slot]),
                    _row(res.tilted, slot), bool(inside), bool(end), index))
                self.stops += bool(res.failed[slot])
            return res

        engine.admit = admit_w
        engine.dispatch_decode = dispatch_w
        engine.materialize = materialize_w


def _row(arr, slot) -> np.ndarray:
    if arr is None:
        return np.zeros((0,), np.float32)
    return np.asarray(arr[slot], np.float32).copy()


@dataclass
class Record:
    """One request as its client saw it."""
    client: int
    prompt: np.ndarray
    budget: int
    due: float                                   # host time it was due
    events: List[tuple] = field(default_factory=list)   # (t, tokens)
    finished: Optional[float] = None
    tokens: List[np.ndarray] = field(default_factory=list)


class Clients:
    """Sends a :class:`~bench.traffic.Traffic` mix through ``frontend``.

    Closed loop: each client has one request out at a time and sends its
    next one the moment the last one's final stream event arrives.  Open
    loop: requests are sent as their due times pass, the due time being
    what latency counts from.
    """

    def __init__(self, frontend, traffic):
        self.frontend, self.traffic = frontend, traffic
        self.records: Dict[str, Record] = {}
        self._ready: deque = deque()          # (client, time it got free)
        self._schedule: deque = deque()       # open loop: due times
        self.refused = 0

    def on_event(self, event) -> None:
        """Stream callback: one committed step, or the final event."""
        t = clock()
        rec = self.records[event.request_id]
        if event.final:
            rec.finished = t
            if self.traffic.kind == "closed":
                self._ready.append((rec.client, t))
            return
        rec.events.append((t, int(len(event.tokens))))
        rec.tokens.append(np.asarray(event.tokens, np.int32).copy())

    def send(self, req, due: float, request_id: Optional[str] = None):
        with TraceAnnotation("submit"):
            try:
                rid = self.frontend.submit(req.prompt, max_steps=req.budget,
                                           request_id=request_id,
                                           stream=self.on_event)
            except ValueError:
                self.refused += 1
                return None
        self.records[rid] = Record(req.client, req.prompt, req.budget, due)
        return rid

    def start(self, now: float, horizon_s: float) -> None:
        """Closed loop: every client's first request, at staggered ages.
        Open loop: the due times of the next ``horizon_s`` seconds."""
        if self.traffic.kind == "closed":
            for c in range(self.traffic.clients):
                self.send(self.traffic.first_request(c), now)
            return
        spec = self.traffic.spec
        count = int(float(spec["rate"]) * horizon_s * 1.5) \
            + int(spec.get("burst", 1))
        self._schedule = deque(now + o for o in
                               self.traffic.schedule(count))

    def refill(self, now: float) -> None:
        """Send every request that is due by ``now``."""
        while self._ready:
            client, t = self._ready.popleft()
            self.send(self.traffic.next_request(client), t)
        i = len(self.records)
        while self._schedule and self._schedule[0] <= now:
            due = self._schedule.popleft()
            self.send(self.traffic.next_request(i), due)
            i += 1
