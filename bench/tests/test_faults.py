"""The check catches a broken timed path: a whole run of the toy cell on
the CPU (the harness's look for a chip skipped), with a fault planted in
the engines underneath, must come out ``correct: false``; the same run
without a fault must come out true.

    python3 -m pytest bench/tests/test_faults.py

The faults a serving cell can have: a step that returns its state
unchanged, half of the batch left out (its rows given the other half's
tokens), and a token altered where it is produced.  No cell exchanges
anything between chips (the four-chip cell is replicas behind a router),
so that fault has no place here.
"""
import jax
import numpy as np
import pytest

from bench.run import run_cell
from bench.tests.common import tiny_cell


def stale_state(engines):
    for e in engines:
        def dispatch(state, rng, rng_target=None, orig=e.dispatch_decode):
            _, ticket = orig(state, rng, rng_target)
            return state, ticket
        e.dispatch_decode = dispatch


def _rewrite(engines, change):
    for e in engines:
        def materialize(ticket, orig=e.materialize):
            res = orig(ticket)
            chosen = np.array(res.chosen)
            change(chosen, np.asarray(res.done_prev))
            return res._replace(chosen=chosen)
        e.materialize = materialize


def half_batch(engines):
    def change(chosen, done):
        half = chosen.shape[0] // 2
        chosen[half:] = chosen[:chosen.shape[0] - half]
    _rewrite(engines, change)


def altered_token(engines):
    def change(chosen, done):
        live = np.nonzero(~done & (chosen[:, 0] != 0))[0]
        if live.size:
            s = live[0]
            chosen[s, 0] = 3 + (chosen[s, 0] + 101) % 400
    _rewrite(engines, change)


def run(hook):
    out = run_cell(tiny_cell(), seed=2 ** 31 + 17, seconds=3.0,
                   trace=False, devices=jax.devices()[:1], engine_hook=hook)
    return out


def test_sound_run_is_correct():
    out = run(None)
    assert out["correct"], out["checks"]
    assert out["extra"]["check"]["counts"]["accepted_steps"] > 0


@pytest.mark.parametrize("fault", [stale_state, half_batch, altered_token],
                         ids=lambda f: f.__name__)
def test_fault_makes_the_run_incorrect(fault):
    out = run(fault)
    assert not out["correct"], out["checks"]
