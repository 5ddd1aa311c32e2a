"""The program's own measurement: named scopes in the engine's device
programs, and the host spans and counters the scheduler and the engine
keep (fallback steps, queue wait, admissions, pipeline seconds).

  * Every phase scope reaches the ``op_name`` metadata of the lowered and
    of the compiled ``_decode_core``, ``_admit`` and ``_extend``;
    ``gsi.fallback`` sits under the ``lax.cond``, and nothing of the
    decode step but the cond and its predicate is left unscoped.
  * ``fallback_steps`` counts the steps whose ``StepResult`` shows a
    rejection anywhere in the batch (the cond's predicate).
  * ``queue_wait_s`` sums ``admitted_at - arrival_time`` over a run's
    responses, sync and async; once the scheduler clock runs, a request
    waits from its submission.
  * ``merge_engine_stats`` sums the new counters.
  * The scheduler's spans land in a profiler trace, and the counters
    behind them keep ``pipeline_stats()``'s keys.
"""
import glob
import re
import time

import jax
import numpy as np
import pytest

from repro.config import GSIConfig
from repro.models import build_model
from repro.serving import (EngineStats, GSIScheduler, GSIServingEngine,
                           merge_engine_stats)
from repro.serving.spans import HostClock

PHASES = ("gsi.draft", "gsi.verify/prm", "gsi.verify/target", "gsi.select",
          "gsi.commit/draft", "gsi.commit/target", "gsi.commit/prm")


@pytest.fixture(scope="module")
def triple(tiny_triple):
    params = tuple(build_model(c).init(jax.random.PRNGKey(i))
                   for i, c in enumerate(tiny_triple))
    return tiny_triple, params


@pytest.fixture(scope="module")
def gcfg():
    # u = 0.3 rejects some steps and accepts others on this toy triple,
    # so runs hold steps with and without the fallback
    return GSIConfig(n=2, max_step_tokens=5, max_steps=3, beta=4.0,
                     threshold_u=0.3, min_step_reward=-1.0)


def _engine(triple, g, **kw):
    cfgs, params = triple
    return GSIServingEngine(*cfgs, *params, g, max_seq=96, paged=True,
                            page_size=8, **kw)


@pytest.fixture(scope="module")
def engine(triple, gcfg):
    return _engine(triple, gcfg)


def _lowered(engine, program):
    state = engine.fresh_state(2)
    key = jax.random.PRNGKey(0)
    mask, live = np.ones((2,), bool), np.ones((2,), bool)
    tokens = np.ones((2, 8), np.int32)
    args = {"_decode_core": (key, key),
            "_admit": (mask, tokens, np.zeros((2,), np.int32), live),
            "_extend": (mask, tokens, live)}[program]
    return jax.jit(getattr(engine, program)).lower(engine.params, state,
                                                   *args)


def _op_names(engine, program, stage):
    low = _lowered(engine, program)
    if stage == "lowered":
        return set(re.findall(r'loc\("([^"]*)"', low.as_text(
            debug_info=True)))
    return set(re.findall(r'op_name="([^"]*)"', low.compile().as_text()))


def _under(names, scope):
    return [n for n in names if f"/{scope}/" in f"/{n}/"]


@pytest.mark.parametrize("stage", ["lowered", "compiled"])
def test_decode_program_carries_every_phase_scope(engine, stage):
    names = _op_names(engine, "_decode_core", stage)
    for scope in PHASES:
        assert _under(names, scope), scope
    fallback = _under(names, "gsi.fallback")
    assert fallback
    assert all(re.search(r"/cond/branch_\d+_fun/gsi\.fallback(/|$)", n)
               for n in fallback)
    # the target's token-serial sampling loop runs under the fallback
    assert any("/while/body/" in n for n in fallback)


def test_decode_program_leaves_only_the_cond_unscoped(engine):
    names = _op_names(engine, "_decode_core", "compiled")
    program = [n for n in names if n.startswith("jit(_decode_core)/")]
    unscoped = {n for n in program if "/gsi." not in n}
    assert unscoped <= {"jit(_decode_core)/cond",
                        "jit(_decode_core)/convert_element_type"}, unscoped


@pytest.mark.parametrize("stage", ["lowered", "compiled"])
@pytest.mark.parametrize("program,outer", [("_admit", "gsi.admit"),
                                           ("_extend", "gsi.extend")])
def test_admission_programs_reach_the_commit_children(engine, program,
                                                      outer, stage):
    names = _op_names(engine, program, stage)
    for model in ("draft", "target", "prm"):
        assert _under(names, f"{outer}/gsi.commit/{model}"), model
    inside = [n for n in names if n.startswith(f"jit({program})/")]
    assert all(f"/{outer}" in n for n in inside), \
        [n for n in inside if f"/{outer}" not in n]


def _tally(engine, fallbacks):
    """Wrap ``engine.materialize`` to record each step's predicate."""
    orig = engine.materialize

    def materialize(ticket):
        res = orig(ticket)
        fallbacks.append((not bool(np.all(res.accept)), res.fallback))
        return res
    engine.materialize = materialize


def test_fallback_steps_count_steps_with_a_rejection(engine):
    prompts = np.asarray([[5, 6, 7, 8], [9, 10, 11, 0]], np.int32)
    state = engine.init_state(prompts)
    stats = EngineStats()
    seen = []
    rng = jax.random.PRNGKey(3)
    for _ in range(6):
        rng, k1, k2 = jax.random.split(rng, 3)
        state, res = engine.step_decode(state, k1, k2, stats=stats)
        seen.append(not bool(np.all(res.accept)))
        assert res.fallback == seen[-1]
    assert stats.fallback_steps == sum(seen)
    assert stats.steps == len(seen)


@pytest.mark.parametrize("sync", [True, False])
def test_scheduler_counts_fallback_steps(triple, gcfg, sync):
    eng = _engine(triple, gcfg)
    seen = []
    _tally(eng, seen)
    sched = GSIScheduler(eng, capacity=2, sync=sync)
    for i in range(5):
        sched.submit(np.asarray([5 + i, 6, 7, 8], np.int32), max_steps=3)
    sched.run(jax.random.PRNGKey(7))
    assert all(pred == ran for pred, ran in seen)
    assert sched.stats.fallback_steps == sum(pred for pred, _ in seen)
    # the toy threshold gives both kinds of step
    assert 0 < sched.stats.fallback_steps < sched.stats.steps


@pytest.mark.parametrize("sync", [True, False])
def test_queue_wait_sums_admitted_minus_arrival(triple, gcfg, sync):
    sched = GSIScheduler(_engine(triple, gcfg), capacity=2, sync=sync)
    arrivals = [0.0, 0.0, 0.0, 0.0, 0.05, 0.05]
    for i, t in enumerate(arrivals):
        sched.submit(np.asarray([5 + i, 6, 7, 8], np.int32),
                     request_id=f"r{i}", max_steps=2, arrival_time=t)
    out = sched.run(jax.random.PRNGKey(11))
    assert sched.stats.admissions == len(out) == len(arrivals)
    waits = [r.admitted_at - r.arrival_time for r in out.values()]
    assert min(waits) >= 0
    assert sched.stats.queue_wait_s == pytest.approx(sum(waits))
    # capacity 2: the later requests queued behind running ones
    assert max(waits) > 0


def test_queue_wait_counts_from_submission_once_the_clock_runs(triple,
                                                               gcfg):
    sched = GSIScheduler(_engine(triple, gcfg), capacity=2, sync=False)
    sched.submit(np.asarray([5, 6, 7, 8], np.int32), max_steps=1)
    rng = jax.random.PRNGKey(5)
    sched.step(rng)                   # starts the clock, admits the first
    first = sched.stats.queue_wait_s
    time.sleep(0.2)
    submitted = sched._now()
    sched.submit(np.asarray([9, 6, 7, 8], np.int32), request_id="late",
                 max_steps=1)          # arrival_time 0, long past
    while "late" not in sched.responses:
        rng, k = jax.random.split(rng)
        sched.step(k)
    late = sched.responses["late"]
    assert sched.stats.admissions == 2
    # the late request waits from its submission, not from time 0
    assert sched.stats.queue_wait_s - first == pytest.approx(
        late.admitted_at - submitted, abs=0.01)
    assert late.admitted_at - submitted < late.admitted_at - 0.2


def test_merge_engine_stats_sums_the_new_counters():
    a, b = EngineStats(), EngineStats()
    a.bump(fallback_steps=3, admissions=2, queue_wait_s=0.5)
    b.bump(fallback_steps=4, admissions=5, queue_wait_s=1.25)
    m = merge_engine_stats([a, b])
    assert (m.fallback_steps, m.admissions) == (7, 7)
    assert m.queue_wait_s == pytest.approx(1.75)


def test_host_clock_span_times_its_region_into_its_key():
    clock = HostClock()
    with clock.span("sched.admit", "serial_host_s"):
        time.sleep(0.01)
    with clock.span("engine.assign_pages"):
        pass
    with pytest.raises(ValueError):
        with clock.span("sched.retire", "serial_host_s"):
            raise ValueError("boom")
    assert clock.seconds["serial_host_s"] >= 0.01
    assert set(clock.seconds) == {"serial_host_s", "engine.assign_pages"}


def test_scheduler_spans_reach_the_profiler_and_keep_pipeline_keys(
        triple, gcfg, tmp_path):
    eng = _engine(triple, gcfg)
    sched = GSIScheduler(eng, capacity=2, sync=False)
    for i in range(3):
        sched.submit(np.asarray([5 + i, 6, 7, 8], np.int32), max_steps=2)
    sched.run(jax.random.PRNGKey(1))          # compile outside the trace
    for i in range(3):
        sched.submit(np.asarray([15 + i, 6, 7, 8], np.int32), max_steps=2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        sched.run(jax.random.PRNGKey(2))
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert {"sched.harvest", "sched.materialize", "sched.retire",
            "sched.admit", "sched.dispatch",
            "engine.assign_pages"} <= names
    pipe = sched.pipeline_stats()
    assert set(pipe) == {"sync", "overlap_host_s", "serial_host_s",
                         "materialize_wait_s", "dispatch_s",
                         "overlap_fraction"}
    assert pipe["overlap_host_s"] > 0 and pipe["serial_host_s"] > 0
    assert pipe["dispatch_s"] >= eng.clock.seconds["engine.assign_pages"]
    total = pipe["overlap_host_s"] + pipe["serial_host_s"]
    assert pipe["overlap_fraction"] == pytest.approx(
        pipe["overlap_host_s"] / total)
