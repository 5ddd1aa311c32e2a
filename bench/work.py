"""The work the GSI algorithm needs, whatever implements it.

Counted from what the harness records: each admission's prompt length
and prefix-cache hit, and each committed step of a live request with its
context, its length and its accept decision.  For one live request at a
step, with context ``c`` (tokens committed before the step; the last of
them, the pending token, is not in the cache yet), a step of ``L``
tokens and ``n`` candidates:

* draft: the pending token once, then each candidate's first ``L - 1``
  tokens (the ``L``-th is sampled, not fed);
* target scoring: the same tokens through the target;
* PRM: the pending token once and each candidate's ``L`` tokens, for the
  reward at its last token;
* a rejected request only: its ``n`` target candidates (``L - 1`` fed
  tokens each) and their PRM scoring (``L`` each).

An admission feeds the prompt's unmatched tail, bar its last (pending)
token, once through each model.

FLOPs are 2 x the matmul parameters a token meets (of an expert layer,
the experts it is routed through), plus attention at its real context:
4 x heads x head_dim x keys per layer, plus a recurrent layer's state
update, whose state is read and written once per fed token.  The LM head
counts only where logits are used: for draft and target tokens of a
step, not for prefill and never for the PRM.  Attention bytes are the
K/V a phase has to read once per layer: the request's shared context
once for all its candidates, plus each candidate's own new keys.

Not counted, being waste of an implementation: the commit's re-scoring
of the chosen step, the fallback phase of requests that accepted, the
padding of admission to a common width, and blocks a kernel walks past
a request's context.  Candidates of a step are counted at the committed
step's length.
"""
from __future__ import annotations

from dataclasses import dataclass

from bench import arch

BF16 = 2


@dataclass(frozen=True)
class Cost:
    """Per-token costs of one model, from its configuration keys, counted
    by its architecture file (``bench/arch/<arch>.py`` ``cost``)."""
    layers: int
    body: int          # matmul parameters of the layers (experts: the
                       # share a token is routed through)
    head: int          # LM head parameters (real vocabulary)
    attn: int          # 4 * heads * head_dim * layers: FLOPs per key
    kv: int            # K/V bytes per key, all layers
    state_flops: int = 0   # a recurrent state's update, all layers
    state_bytes: int = 0   # recurrent state read and written, all layers

    @classmethod
    def of(cls, m: dict) -> "Cost":
        return arch.module(m).cost(m)


@dataclass
class Work:
    flops: float = 0.0         # all of it, state updates included
    attn_flops: float = 0.0
    attn_bytes: float = 0.0
    state_flops: float = 0.0
    state_bytes: float = 0.0

    def add(self, other: "Work") -> None:
        self.flops += other.flops
        self.attn_flops += other.attn_flops
        self.attn_bytes += other.attn_bytes
        self.state_flops += other.state_flops
        self.state_bytes += other.state_bytes


def feed(cost: Cost, positions, *, head: bool, shared_keys: int,
         own_keys: int) -> Work:
    """Tokens fed at ``positions`` (each attends to position + 1 keys),
    reading ``shared_keys + own_keys`` keys of K/V once."""
    keys = sum(p + 1 for p in positions)
    mat = 2 * (cost.body + (cost.head if head else 0)) * len(positions)
    state = cost.state_flops * len(positions)
    return Work(mat + cost.attn * keys + state, cost.attn * keys,
                cost.kv * (shared_keys + own_keys), state,
                cost.state_bytes * len(positions))


def step(costs: dict, *, n: int, context: int, length: int,
         accepted: bool) -> Work:
    """One committed step of one live request (see module doc)."""
    c, L = context, length
    pend = [c - 1]
    cand = [p for p in range(c, c + L - 1)]      # fed candidate tokens
    prm = [p for p in range(c, c + L)]
    w = Work()
    for role, toks, head in (("draft", cand, True), ("target", cand, True),
                             ("prm", prm, False)):
        cost = costs[role]
        w.add(feed(cost, pend, head=head, shared_keys=c, own_keys=0))
        w.add(feed(cost, toks * n, head=head, shared_keys=0,
                   own_keys=n * len(toks)))
    if not accepted:
        w.add(feed(costs["target"], cand * n, head=True, shared_keys=c,
                   own_keys=n * len(cand)))
        w.add(feed(costs["prm"], prm * n, head=False, shared_keys=c,
                   own_keys=n * len(prm)))
    return w


def prefill(costs: dict, *, prompt_len: int, hit: int) -> Work:
    """An admission: the unmatched tail bar the pending token."""
    w = Work()
    tail = list(range(hit, prompt_len - 1))
    for cost in costs.values():
        w.add(feed(cost, tail, head=False, shared_keys=0,
                   own_keys=prompt_len - 1 if tail else 0))
    return w
