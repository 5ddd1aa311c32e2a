"""Architectures by file (``bench/arch/``).

    python3 -m pytest bench/tests/test_arch.py

The golden tests hold the dense decoder, moved into ``bench/arch/dense.py``,
to what the harness read before the move, to the bit: each model's leaves,
shapes and scales, its ``Cost`` and the program's ``ModelConfig`` for
``tri17`` and ``math7b-stage``, the toy triple's weights and the reference's
readings over one fixed sequence (``record_golden.py`` wrote them).  The
plug-in tests add an architecture as one new file in a directory of its
own and run the toy cell's weights, layout check, reference check and work
counts through it.
"""
import dataclasses
import json

import jax
import numpy as np
import pytest

from bench import arch, check, reference, work
from bench import weights as wts
from bench.drive import StepRow
from bench.errors import Refused
from bench.run import model_config
from bench.tests import record_golden as rec

GOLDEN = json.loads((rec.HERE / "data" / "golden.json").read_text())
MODELS = sorted(GOLDEN["models"])


def model(key):
    name, role = key.split("/")
    cfg = rec.config(name)
    return cfg["models"][role], cfg["dtype"]


@pytest.mark.parametrize("key", MODELS)
def test_shapes_as_before_the_move(key):
    assert rec.model_record(*model(key))["shapes"] == \
        GOLDEN["models"][key]["shapes"]


@pytest.mark.parametrize("key", MODELS)
def test_cost_as_before_the_move(key):
    got = dataclasses.asdict(work.Cost.of(model(key)[0]))
    want = GOLDEN["models"][key]["cost"]
    assert {k: got.pop(k) for k in want} == want
    assert got == {"state_flops": 0, "state_bytes": 0}


@pytest.mark.parametrize("key", MODELS)
def test_program_config_as_before_the_move(key):
    assert rec.model_record(*model(key))["model_config"] == \
        GOLDEN["models"][key]["model_config"]


def test_tiny_weights_as_before_the_move():
    assert rec.weight_digests(rec.tiny()) == GOLDEN["tiny_weights"]


def test_reference_readings_as_before_the_move():
    want = np.load(rec.HERE / "data" / "golden_forward.npz")
    got = rec.forward_readings(rec.tiny())
    assert sorted(got) == sorted(want.files)
    for k, v in got.items():
        assert v.dtype == want[k].dtype and v.shape == want[k].shape, k
        assert np.array_equal(v.view(np.uint32), want[k].view(np.uint32)), k


# ---------------------------------------------------------------------------
# An architecture added by one new file
# ---------------------------------------------------------------------------

DENSE = arch.HERE / "dense.py"
# a copy of dense whose tokens also update a recurrent state
STATE = """

import dataclasses as _dc

_dense_cost = cost


def cost(m):
    return _dc.replace(_dense_cost(m), state_flops=7, state_bytes=11)
"""


@pytest.fixture
def plugged(tmp_path, monkeypatch):
    """A tiny configuration whose target names a new architecture file,
    ``<name>.py`` in a temporary directory, and the unchanged one."""
    def make(name, source):
        (tmp_path / f"{name}.py").write_text(source)
        monkeypatch.setattr(arch, "SEARCH", (arch.HERE, tmp_path))
        cfg = rec.tiny()
        cfg["models"]["target"]["arch"] = name
        return cfg, rec.tiny()
    return make


def samples():
    rng = np.random.default_rng(5)
    steps = [rng.integers(3, 500, 4).astype(np.int32) for _ in range(3)]
    rows = [StepRow(s, True, np.zeros((2,), np.float32), False, False, i)
            for i, s in enumerate(steps)]
    prompt = rng.integers(3, 500, 20).astype(np.int32)
    return [check.Sample("r0", prompt, steps, rows)]


def test_a_copy_of_dense_under_another_name_reads_as_dense(plugged):
    new, old = plugged("dense_copy", DENSE.read_text())
    dev = jax.devices("cpu")[0]
    params = {}
    for tag, cfg in (("new", new), ("old", old)):
        params[tag] = wts.make_triple(cfg["models"], cfg["weights_seed"], dev)
    from repro.models import build_model
    key = jax.random.key(0, impl="rbg")
    wts.check_layout(params["new"], tuple(
        jax.eval_shape(build_model(model_config(r, new["models"][r],
                                                new["dtype"])).init, key)
        for r in rec.ROLES))
    for a, b in zip(jax.tree.leaves(params["new"]),
                    jax.tree.leaves(params["old"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for control in (False, True):
        got = [check.compare(samples(), cfg["models"],
                             dict(zip(rec.ROLES, params[tag])), beta=20.0,
                             width=128, control=control)
               for tag, cfg in (("new", new), ("old", old))]
        assert got[0] == got[1]
        assert got[0]["counts"]["accepted_steps"] == 3
        assert got[0]["numbers"]["tilted_err"] > 0
    costs = [{r: work.Cost.of(cfg["models"][r]) for r in rec.ROLES}
             for cfg in (new, old)]
    assert costs[0] == costs[1]
    for fn in (lambda c: work.step(c, n=2, context=30, length=4,
                                   accepted=False),
               lambda c: work.prefill(c, prompt_len=30, hit=16)):
        assert fn(costs[0]) == fn(costs[1])


def test_state_work_reaches_work_and_leaves_attention_alone(plugged):
    new, old = plugged("dense_state", DENSE.read_text() + STATE)
    state = work.Cost.of(new["models"]["target"])
    dense = work.Cost.of(old["models"]["target"])
    assert (state.state_flops, state.state_bytes) == (7, 11)
    assert dataclasses.replace(state, state_flops=0, state_bytes=0) == dense
    w = work.feed(state, [3, 4, 5], head=True, shared_keys=3, own_keys=2)
    d = work.feed(dense, [3, 4, 5], head=True, shared_keys=3, own_keys=2)
    assert (w.state_flops, w.state_bytes) == (21, 33)
    assert w.flops == d.flops + 21
    costs = [{r: work.Cost.of(cfg["models"][r]) for r in rec.ROLES}
             for cfg in (new, old)]
    total = [work.Work(), work.Work()]
    for t, c in zip(total, costs):
        t.add(work.step(c, n=2, context=30, length=4, accepted=False))
        t.add(work.prefill(c, prompt_len=30, hit=16))
    # the target fed 1 + 2 x 3 tokens to score, 2 x 3 as its own
    # candidates, and 13 of the prompt
    assert total[0].state_flops == 7 * (1 + 6 + 6 + 13)
    assert total[0].state_bytes == 11 * (1 + 6 + 6 + 13)
    assert total[1].state_flops == total[1].state_bytes == 0
    assert total[0].flops - total[1].flops == total[0].state_flops
    # what paged_attn_roofline reads is the same
    assert (total[0].attn_flops, total[0].attn_bytes) == \
        (total[1].attn_flops, total[1].attn_bytes)


@pytest.mark.parametrize("name", [None, "", "no_such_arch"])
def test_a_model_without_a_known_arch_is_refused(name):
    m = dict(rec.tiny()["models"]["target"])
    if name is None:
        del m["arch"]
    else:
        m["arch"] = name
    for fn in (wts.shapes, work.Cost.of,
               lambda m: model_config("target", m, "bfloat16"),
               lambda m: reference.forward(m, {}, np.zeros(8), 8)):
        with pytest.raises(Refused):
            fn(m)
