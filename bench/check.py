"""Whether what the timed path served is correct: a comparison with the
plain reference (``bench/reference.py``).

The cells serve sampled steps (temperature 0.7), so no served token can
be compared with a best one.  What the program reports of each step can:
the tilted reward ``r + (log pi_target - log pi_draft) / beta`` of each
of its n draft candidates, the number the accept decision compares with
``u``.  It holds the PRM's reward at the step's last token and both
models' log-probabilities of the step's tokens, so it covers the three
models' admission (prefill), their paged decode and scoring, and the
commit: every committed step, a rejected (target) step included, feeds
the contexts of the steps after it.  For a sample of the requests that
finished in the run, the reference runs once over each prompt with its
served tokens, through the draft, the target and the PRM, and scores
every accepted step the client received.  The step is one of the
program's n candidates, so one number is read:

``tilted_err``  the distance from the reference's tilted reward of the
                step to the nearest of the program's n tilted rewards,
                the worst over the sample.

The program does not report which candidate it selected: selection is
soft best-of-n, sampled from ``softmax(beta * tilted)``.  Reward and
log-ratio read apart at the nearest candidate confuse candidates whose
tilted rewards lie close, so they are not compared.

The program's pad id, 0, is also a token the models can sample.  The
engine drops a sampled 0 from what it commits, yet counts it in the
draft's log-probability.  Where it was the step's last token (the step is
one token short and ends at neither a separator nor the end of
sequence), the reference counts it too.  Where tokens follow it, the
engine takes a wrong pending token for the next step: the request no
longer follows its own served tokens, and requests with such a step are
not sampled (``pad_excluded``).

The control runs the same reading with the reference in float8 in the
program's place: float8's own tilted reward of each accepted step against
the float32 reference's.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np

from bench import reference

NUMBERS = ("tilted_err",)


@dataclass
class Sample:
    """One finished request: its prompt, the steps its client received,
    and the engine's row for each of those steps."""
    request_id: str
    prompt: np.ndarray
    steps: List[np.ndarray]
    rows: list


def choose(finished: Dict[str, Sample], rng: np.random.Generator,
           count: int) -> List[Sample]:
    """The longest finished request and others drawn from ``rng``, among
    those the pad id did not derail (see module doc)."""
    ids = sorted(i for i, s in finished.items()
                 if not any(r.pad_inside for r in s.rows))
    if not ids:
        return []
    served = [sum(s.size for s in finished[i].steps) for i in ids]
    first = ids[int(np.argmax(served))]
    rest = [i for i in ids if i != first]
    rng.shuffle(rest)
    return [finished[i] for i in [first] + rest[:count - 1]]


def _readings(models, weights, seq, length, lowp=False):
    return {role: reference.forward(models[role], weights[role], seq, length,
                                    lowp=lowp)
            for role in ("draft", "target", "prm")}


def _tilted(readings, pos, last, beta, pad_end=False):
    """The tilted reward of the step fed at ``pos``, whose last token
    sits at ``last``; ``pad_end``: the draft sampled a pad after it."""
    lp = {m: float(np.sum(readings[m]["next"][pos] - readings[m]["lse"][pos]))
          for m in ("draft", "target")}
    if pad_end:
        d = readings["draft"]
        lp["draft"] += float(d["pad"][last] - d["lse"][last])
    r = float(readings["prm"]["reward"][last])
    return r + (lp["target"] - lp["draft"]) / beta


def compare(samples: List[Sample], models: dict, weights: dict, *,
            beta: float, width: int, control: bool = False) -> dict:
    """The numbers compared, over ``samples`` (see module doc).

    With ``control`` the readings are those of the float8 reference in
    the program's place instead of the program's own."""
    worst = {k: 0.0 for k in NUMBERS}
    counts = {"requests": 0, "steps": 0, "accepted_steps": 0, "tokens": 0}
    problems: List[str] = []
    for s in samples:
        if len(s.steps) != len(s.rows) or not all(
                np.array_equal(a, r.tokens) for a, r in zip(s.steps, s.rows)):
            problems.append(f"{s.request_id}: the steps its client got "
                            f"differ from the engine's")
            continue
        seq = np.concatenate([s.prompt] + list(s.steps)).astype(np.int32)
        length = int(seq.size)
        if length > width:
            problems.append(f"{s.request_id}: {length} tokens > {width}")
            continue
        padded = np.zeros((width,), np.int32)
        padded[:length] = seq
        ref = _readings(models, weights, padded, length)
        low = _readings(models, weights, padded, length, lowp=True) \
            if control else None
        counts["requests"] += 1
        a = s.prompt.size
        for toks, row in zip(s.steps, s.rows):
            b = a + toks.size
            pos = np.arange(a - 1, b - 1)
            a = b
            counts["steps"] += 1
            if not row.accept:
                continue
            counts["accepted_steps"] += 1
            counts["tokens"] += pos.size
            want = _tilted(ref, pos, b - 1, beta, row.pad_end)
            if control:
                err = abs(_tilted(low, pos, b - 1, beta, row.pad_end) - want)
            elif row.tilted.size and np.isfinite(row.tilted).all():
                err = float(np.min(np.abs(row.tilted - want)))
            else:
                problems.append(f"{s.request_id}: non-finite or missing "
                                f"tilted rewards")
                continue
            worst["tilted_err"] = max(worst["tilted_err"], err)
    return {"numbers": worst, "counts": counts, "problems": problems}


def judge(result: dict, limits: dict) -> dict:
    """Each number beside its limit, and whether all are within."""
    checks = {k: {"value": result["numbers"][k],
                  "limit": float(limits.get(k, "nan"))} for k in NUMBERS}
    ok = not result["problems"] and result["counts"]["accepted_steps"] > 0 \
        and all(c["value"] <= c["limit"] for c in checks.values())
    return {"correct": bool(ok), "checks": checks}
