"""The one traffic generator: every mix is a data file under
``bench/traffic/<mix>.json`` read by :class:`Traffic`.

Keys of a mix file:

``kind``             ``closed`` (each client sends its next request when
                     its previous one finishes), ``poisson`` or ``bursty``
                     (open loop: requests are due on a schedule).
``clients``          closed loop: the number of clients.
``rate``, ``burst``  open loop: requests per second, requests per burst.
``max_step_tokens``  tokens per reasoning step (the engine's step length).
``sizes``            how many (prompt, budget) pairs make the fixed set of
                     sizes, sent in an order fixed by ``order_seed``.  Sizes
                     and order are the same for every seed: ``--seed``
                     draws the tokens only, so that it changes what is
                     computed but not how much.
``prompt``           length of each request's own part of the prompt:
                     ``{"dist": "uniform" | "lognormal", "min", "max"
                     [, "median", "sigma"]}``.
``budget``           reasoning steps per request, a ``uniform`` dist.
``prefix``           ``{"groups": g, "length": n}``: a client belongs to
                     group ``client % g`` and every prompt of that group
                     starts with the group's own ``n``-token system prompt.

Token ids are drawn from ``[FIRST_TOKEN, vocab)``: ids 0, 1 and 2 are the
engine's pad, step separator and end of sequence.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List

import numpy as np

FIRST_TOKEN = 3


@dataclass
class Request:
    """One generated request: who sends it, its prompt and step budget."""
    client: int
    prompt: np.ndarray
    budget: int


def _quantiles(dist: dict, count: int) -> np.ndarray:
    """``count`` evenly spaced quantiles of a length distribution, as
    whole numbers inside ``[min, max]``: the same set for every seed."""
    lo, hi = int(dist["min"]), int(dist["max"])
    q = (np.arange(count) + 0.5) / count
    if dist["dist"] == "uniform":
        vals = lo + q * (hi + 1 - lo)
    elif dist["dist"] == "lognormal":
        from statistics import NormalDist
        z = np.asarray([NormalDist().inv_cdf(float(p)) for p in q])
        vals = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.floor(vals), lo, hi).astype(np.int64)


def arrivals(count: int, *, process: str, rate: float, burst: int,
             rng: np.random.Generator) -> np.ndarray:
    """Arrival offsets (seconds, sorted) for ``count`` requests.

    ``poisson``: iid exponential gaps at ``rate`` req/s.  ``bursty``:
    groups of ``burst`` simultaneous arrivals, groups spaced at the same
    mean inter-group rate.  Copied from ``benchmarks/loadgen.py``.
    """
    if process == "poisson":
        return np.cumsum(rng.exponential(1.0 / rate, size=count))
    if process == "bursty":
        groups = -(-count // burst)
        starts = np.cumsum(rng.exponential(burst / rate, size=groups))
        return np.repeat(starts, burst)[:count]
    raise ValueError(f"unknown arrival process {process!r}")


class Traffic:
    """Requests of one mix for one seed.

    The (own-prompt length, budget) pairs and their order do not depend on
    the seed; the seed draws every token.  ``next_request(client)`` hands a client its next request;
    ``first_request(client)`` shortens the first request of each client
    to a share of its budget so that a closed loop starts with its
    requests at staggered ages, as in steady state.
    """

    def __init__(self, spec: dict, *, vocab: int, seed: int):
        self.spec = spec
        self.kind = spec["kind"]
        self.vocab = int(vocab)
        self.max_step_tokens = int(spec["max_step_tokens"])
        self.clients = int(spec.get("clients", 0))
        count = int(spec["sizes"])
        self.rng = np.random.default_rng(seed)
        own = _quantiles(spec["prompt"], count)
        budget = _quantiles(spec["budget"], count)
        order = np.random.default_rng(int(spec["order_seed"])).permutation(
            count)
        self._own, self._budget = own[order], budget[order]
        pre = spec.get("prefix") or {}
        self.groups = int(pre.get("groups", 0))
        self.prefix_len = int(pre.get("length", 0)) if self.groups else 0
        self._prefixes = [self._tokens(self.prefix_len)
                          for _ in range(self.groups)]
        self._next = 0

    # -- sizes ---------------------------------------------------------
    @property
    def max_prompt(self) -> int:
        return self.prefix_len + int(self.spec["prompt"]["max"])

    @property
    def max_budget(self) -> int:
        return int(self.spec["budget"]["max"])

    @property
    def max_tokens(self) -> int:
        """The longest sequence a request can reach: prompt and answer."""
        return self.max_prompt + self.max_budget * self.max_step_tokens

    # -- requests ------------------------------------------------------
    def _tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(FIRST_TOKEN, self.vocab, size=n,
                                 dtype=np.int64).astype(np.int32)

    def _make(self, client: int, own: int, budget: int) -> Request:
        parts = []
        if self.groups:
            parts.append(self._prefixes[client % self.groups])
        parts.append(self._tokens(own))
        return Request(client, np.concatenate(parts), int(budget))

    def next_request(self, client: int) -> Request:
        i = self._next % len(self._own)
        self._next += 1
        return self._make(client, int(self._own[i]), int(self._budget[i]))

    def first_request(self, client: int) -> Request:
        req = self.next_request(client)
        share = (client + 0.5) / max(self.clients, 1)
        req.budget = max(1, math.ceil(share * req.budget))
        return req

    def longest(self, client: int = 0) -> Request:
        """A request at the mix's largest prompt length and a one-step
        budget: it warms the admission program at its widest shape."""
        return self._make(client, int(self.spec["prompt"]["max"]), 1)

    def schedule(self, count: int) -> List[float]:
        """Open loop: due offsets (seconds from the window's start)."""
        return list(arrivals(count, process=self.kind,
                             rate=float(self.spec["rate"]),
                             burst=int(self.spec.get("burst", 1)),
                             rng=self.rng))
