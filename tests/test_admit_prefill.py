"""Admission prefill as one multi-token pass per model.

  * Equivalence: ``_admit`` / ``_extend`` through ``Model.extend`` against
    the token-serial ``_commit`` scan on the same input state — every
    written K/V row to float32 tolerance, ``pending`` / ``pos`` / ``done``
    equal, the next decode step's logits close, and every row the pass
    does not write (unadmitted slots, untouched pages) bit-identical to
    the input.  Cases: PAD-padded tails (one with a PAD inside), a prefix
    hit (``starts > 0`` over spliced pages), a sliding-window layer whose
    dense ring is shorter than the tail, and a chunked ``extend`` whose
    final chunk comes up live; paged and dense layouts.  A
    mixture-of-experts triple keeps the scan, and its admission matches
    the scan the same way.
  * Engagement: ``EngineStats.prefill_batched`` counts every admission of
    an attention-only triple, and none where a recurrent layer, a
    quantized pool or an MoE FFN keeps the scan.
  * The admission program computes nothing over the vocabulary.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import GSIConfig, ModelConfig
from repro.models import build_model
from repro.serving import GSIScheduler, GSIServingEngine

PAD = 0
PS = 4


def _triple(pattern=("full",), window=0, vocab=64, **ffn):
    draft = ModelConfig(
        name=f"t-ap-{'-'.join(pattern)}-{window}-{vocab}-{len(ffn)}",
        family="hybrid" if "recurrent" in pattern else "dense",
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
        vocab_size=vocab, head_dim=16, dtype="float32",
        param_dtype="float32", layer_pattern=pattern,
        window_size=window or 4096, **ffn)
    target = dataclasses.replace(draft, name=draft.name + "-t", num_layers=3)
    prm = dataclasses.replace(target, name=draft.name + "-p",
                              reward_head=True)
    params = tuple(build_model(c).init(jax.random.PRNGKey(i))
                   for i, c in enumerate((draft, target, prm)))
    return (draft, target, prm), params


@pytest.fixture(scope="module")
def gcfg():
    return GSIConfig(n=2, max_step_tokens=5, max_steps=3, beta=4.0,
                     min_step_reward=-1.0)


# the MoE triple is sized so that one multi-token dispatch would drop
# live tokens: a slot's ~16 repeated tokens fill its two experts' capacity
# of 16, which the scan's 3 tokens a step never reach
STACKS = {"full": {}, "local": {"pattern": ("full", "local"), "window": 3},
          "moe": {"num_experts": 8, "experts_per_token": 2, "moe_d_ff": 16},
          "hybrid": {"pattern": ("recurrent", "full")}}


def _engine(gcfg, stack, **kw):
    cfgs, params = _triple(**STACKS[stack])
    return GSIServingEngine(*cfgs, *params, gcfg, max_seq=64,
                            page_size=PS, **kw)


def _capture(eng, name):
    """Record the state and arguments of each call to a jitted program."""
    seen = []
    orig = getattr(eng, name)

    def call(state, *args):
        seen.append((state, args))
        return orig(state, *args)
    setattr(eng, name, call)
    return seen


def _pad_rows(rows, width):
    out = np.full((len(rows), width), PAD, np.int32)
    for b, r in enumerate(rows):
        out[b, :len(r)] = r
    return out


PROMPT_A = [5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15]


def _decoding_state(eng):
    """Slot 0 holds a live request; slots 1-2 are free."""
    state = eng.fresh_state(3)
    prompts = _pad_rows([PROMPT_A, [], []], 16)
    return eng.admit(state, np.array([True, False, False]), prompts)


def _case_pad_tail(eng):
    state = _decoding_state(eng)
    seen = _capture(eng, "_jit_admit")
    prompts = _pad_rows([[], [21, 22, 23, 24, 25, 26, 27, 28, 29],
                         [31, 32, PAD, 33, 34]], 16)
    eng.admit(state, np.array([False, True, True]), prompts)
    return "_admit", seen[-1]


def _case_prefix(eng):
    state = _decoding_state(eng)
    prompt = PROMPT_A[:8] + [40, 41, 42, 43, 44, 45]
    shared, hit = eng.match_prefix(np.asarray(prompt))
    assert hit == 8
    eng.claim_slot(1, len(prompt), eng.gcfg.max_steps, shared=shared)
    seen = _capture(eng, "_jit_admit")
    starts = np.array([0, hit, 0], np.int32)
    eng.admit(state, np.array([False, True, False]),
              _pad_rows([[], prompt, []], 16), starts)
    assert int(seen[-1][1][2][1]) == hit
    return "_admit", seen[-1]


def _case_extend(eng):
    state = _decoding_state(eng)
    prompt = list(range(20, 33))
    state = eng.admit(state, np.array([False, True, False]),
                      _pad_rows([[], prompt[:5], []], 16),
                      live=np.array([False, False, False]))
    seen = _capture(eng, "_jit_extend")
    eng.extend(state, np.array([False, True, False]),
               _pad_rows([[], prompt[5:], []], 16),
               np.array([False, True, False]))
    return "_extend", seen[-1]


CASES = {"pad_tail": _case_pad_tail, "prefix": _case_prefix,
         "extend": _case_extend}


def _leaves(cache):
    """Every K/V leaf by path: (paged?, array with a leading layer axis)."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        keys = [getattr(p, "key", None) for p in path]
        arr = np.asarray(leaf)
        if "blocks" not in keys:
            arr = arr[None]
        out[jax.tree_util.keystr(path)] = (keys[-1] in ("kp", "vp"), arr)
    return out


def _check_kv(before, batched, scan, pt, pos_in, pos_out, admitted,
              pass_ran=True):
    """Rows the pass writes match the scan's; every other row is the
    input's, bit for bit (on the pool, where the pass ran: the scan also
    writes the pending token and dead rows' trash pages)."""
    b_in, b_new, b_scan = _leaves(before), _leaves(batched), _leaves(scan)
    for name, (paged, old) in b_in.items():
        new, ref = b_new[name][1], b_scan[name][1]
        if paged:
            R, P, ps = old.shape[:3]
            old, new, ref = (a.reshape((R, P * ps) + a.shape[3:])
                             for a in (old, new, ref))
            rows = [pt[b, p // ps] * ps + p % ps for b in admitted
                    for p in range(pos_in[b], pos_out[b])]
            assert rows
            np.testing.assert_allclose(new[:, rows], ref[:, rows],
                                       atol=1e-5, rtol=1e-5, err_msg=name)
            if not pass_ran:
                continue
            keep = np.ones((P * ps,), bool)
            keep[rows] = False
            np.testing.assert_array_equal(new[:, keep], old[:, keep], name)
            continue
        size = old.shape[2]
        for b in range(old.shape[1]):
            if b not in admitted:
                np.testing.assert_array_equal(new[:, b], old[:, b], name)
                continue
            # the scan also writes the pending token at pos_out (ring
            # slot pos_out % size), which the next decode step rewrites
            lo = max(pos_in[b], pos_out[b] - size + 1)
            slots = [p % size for p in range(lo, pos_out[b])]
            assert slots
            np.testing.assert_allclose(new[:, b, slots], ref[:, b, slots],
                                       atol=1e-5, rtol=1e-5, err_msg=name)
            rest = np.ones((size,), bool)
            rest[slots] = False
            rest[pos_out[b] % size] = False
            np.testing.assert_array_equal(new[:, b, rest], ref[:, b, rest],
                                          name)


@pytest.mark.parametrize("case,stack,paged", [
    (case, stack, paged) for case in sorted(CASES)
    for stack in ("full", "local", "moe") for paged in (True, False)
    if paged or case != "prefix"])         # prefix hits need pages
def test_batched_admission_matches_token_serial_scan(gcfg, monkeypatch,
                                                     case, stack, paged):
    eng = _engine(gcfg, stack, paged=paged)
    program, (state, args) = CASES[case](eng)
    assert eng.batched_prefill == (stack != "moe")

    def run():
        # a fresh function each time: jit's cache would hand the second
        # call the first call's trace
        return jax.jit(lambda *a: getattr(eng, program)(*a))(
            eng.params, state, *args)
    batched = run()
    monkeypatch.setattr(eng, "batched_prefill", False)
    scan = run()

    for key in ("pending", "pos", "done"):
        np.testing.assert_array_equal(np.asarray(batched[key]),
                                      np.asarray(scan[key]), key)
    mask = np.asarray(args[0])
    admitted = [int(b) for b in np.nonzero(mask)[0]]
    pos_in = np.asarray(args[2] if program == "_admit" else state["pos"])
    pos_out = np.asarray(batched["pos"])
    assert (pos_out[admitted] > pos_in[admitted]).all()
    for b in np.nonzero(~mask)[0]:
        for key in ("pending", "pos", "done"):
            assert batched[key][b] == state[key][b], key
    pt = np.asarray(state["pt"]) if paged else None
    for m in ("S", "B", "P"):
        _check_kv(state["caches"][m], batched["caches"][m],
                  scan["caches"][m], pt, pos_in, pos_out, admitted,
                  pass_ran=stack != "moe")

    # both states decode the admitted rows to the same next-token logits
    model = build_model(eng.draft.cfg)
    logits = [model.decode_step(eng.params[0], s["caches"]["S"],
                                s["pending"][:, None], s["pos"],
                                pt=s.get("pt"))[0]
              for s in (batched, scan)]
    np.testing.assert_allclose(np.asarray(logits[0])[admitted],
                               np.asarray(logits[1])[admitted],
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("stack,kv_dtype,chunk,batched", [
    ("full", None, 0, True),
    ("local", None, 6, True),
    ("hybrid", None, 0, False),
    ("full", "int8", 0, False),
    ("moe", None, 0, False),
])
def test_prefill_batched_counts_the_pass(gcfg, stack, kv_dtype, chunk,
                                         batched):
    eng = _engine(gcfg, stack, paged=True, kv_dtype=kv_dtype)
    extended = []
    extend = eng.extend

    def counted(state, mask, *args):
        extended.append(int(np.sum(mask)))
        return extend(state, mask, *args)
    eng.extend = counted
    sched = GSIScheduler(eng, capacity=2, chunk_tokens=chunk)
    for p in ([5, 6, 7, 8, 9, 10, 11, 12, 4], [7, 3, 4], [9, 9, 9, 4],
              [11, 12, 13, 14, 15, 16, 17, 18, 19, 20, 4]):
        sched.submit(np.asarray(p, np.int32))
    sched.run(jax.random.PRNGKey(7))
    stats = sched.stats
    assert stats.admissions == 4
    assert bool(extended) == bool(chunk)
    assert stats.prefill_batched == ((4 + sum(extended)) if batched else 0)


def _vocab_ops(jaxpr, dims):
    """Equations anywhere in ``jaxpr`` with an output axis in ``dims``."""
    found = []
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if set(getattr(v.aval, "shape", ())) & dims:
                found.append(str(eqn.primitive))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _vocab_ops(inner, dims)
    return found


def test_admission_program_has_no_vocabulary_sized_op(gcfg, monkeypatch):
    cfgs, params = _triple(vocab=3001)       # padded to 3072
    eng = GSIServingEngine(*cfgs, *params, gcfg, max_seq=64, paged=True,
                           page_size=PS)
    state = eng.fresh_state(2)
    args = (np.ones((2,), bool), np.full((2, 16), 7, np.int32),
            np.zeros((2,), np.int32), np.ones((2,), bool))
    dims = {3001, 3072}

    def ops():
        return _vocab_ops(jax.make_jaxpr(lambda *a: eng._admit(*a))(
            eng.params, state, *map(jnp.asarray, args)).jaxpr, dims)
    assert ops() == []
    # the checker sees the scan's per-token logits
    monkeypatch.setattr(eng, "batched_prefill", False)
    assert "dot_general" in ops()
