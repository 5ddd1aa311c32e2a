"""bench/traffic.py: sizes fixed by the mix, tokens by the seed.

    python3 -m pytest bench/tests/test_traffic.py
"""
import json
from pathlib import Path

import numpy as np

from bench.traffic import FIRST_TOKEN, Traffic

MIX = json.loads((Path(__file__).resolve().parents[1] / "traffic"
                  / "reasoning.json").read_text())


def requests(seed, count=20):
    t = Traffic(MIX, vocab=151936, seed=seed)
    return [t.next_request(i % t.clients) for i in range(count)]


def test_seed_draws_tokens_not_sizes():
    a, b = requests(1), requests(2 ** 33 + 7)
    assert [(r.prompt.size, r.budget) for r in a] == \
        [(r.prompt.size, r.budget) for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert all(np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, requests(1)))


def test_sizes_stay_in_the_mix_and_avoid_special_ids():
    for r in requests(3, 64):
        assert MIX["prompt"]["min"] <= r.prompt.size <= MIX["prompt"]["max"]
        assert MIX["budget"]["min"] <= r.budget <= MIX["budget"]["max"]
        assert r.prompt.min() >= FIRST_TOKEN


def test_first_requests_start_at_staggered_ages():
    t = Traffic(MIX, vocab=151936, seed=5)
    budgets = [t.first_request(c).budget for c in range(t.clients)]
    assert budgets[0] < budgets[-1] and min(budgets) >= 1


def test_shared_prefix_groups_and_open_loop_schedule():
    mix = dict(MIX, kind="poisson", rate=4.0, prefix={"groups": 2,
                                                      "length": 32})
    t = Traffic(mix, vocab=1000, seed=9)
    a, b, c = (t.next_request(i) for i in range(3))
    assert np.array_equal(a.prompt[:32], c.prompt[:32])
    assert not np.array_equal(a.prompt[:32], b.prompt[:32])
    due = t.schedule(400)
    assert np.all(np.diff(due) >= 0)
    assert 80 < due[-1] < 120            # 400 arrivals at 4 per second
