"""The control at a size a test run holds: the toy cell's reference
computed in float8 in the program's place fails the limits that the
program's own bfloat16 run passes.

    python3 -m pytest bench/tests/test_control.py

On the chip the same comparison runs at each cell's own size:
``python3 -m bench.control`` (readings in PERF.md).
"""
import jax
import numpy as np

from bench import check
from bench import weights as wts
from bench.run import run_cell
from bench.tests.common import LIMITS, THRESHOLD, tiny_cell
from bench.traffic import Traffic


def test_float8_control_fails_where_the_program_passes():
    cell = tiny_cell(THRESHOLD)
    out = run_cell(cell, seed=91, seconds=3.0, trace=False,
                   devices=jax.devices()[:1])
    assert out["correct"], out["checks"]
    ex = out["extra"]
    models = cell.config["models"]
    params = wts.make_triple(models, cell.config["weights_seed"],
                             jax.devices()[0])
    weights = dict(zip(("draft", "target", "prm"), params))
    finished = {rid: check.Sample(rid, r.prompt, r.tokens, ex["rows"][rid])
                for rid, r in ex["records"].items() if r.finished}
    samples = check.choose(finished, np.random.default_rng(0), 8)
    width = -(-Traffic(cell.traffic, vocab=500, seed=0).max_tokens
              // 128) * 128
    ctl = check.compare(samples, models, weights, beta=20.0, width=width,
                        control=True)
    assert not check.judge(ctl, LIMITS)["correct"], ctl["numbers"]
