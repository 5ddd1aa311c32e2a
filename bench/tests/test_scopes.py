"""Device time by named scope (``bench/scopes.py``), on a trace recorded
on one TPU v5e.

    python3 -m pytest bench/tests/test_scopes.py

``data/scopes.xplane.pb`` was recorded by ``python3 -m
bench.tests.record_scopes`` (its docstring describes the program).  The
numbers below were read off the trace's events by hand (device
timeline, picoseconds): the window runs from 43,859,016 ns for
13,861,108 ns; the first of the four executions starts before it and is
not counted; of the three counted, the second ran the fallback branch.
Per counted execution the operations' ``tf_op`` paths and durations are

    gsi.draft/dot_general                  3,456,250  3,447,422  3,656,250
    gsi.verify/prm/dot_general             3,516,172  3,545,078  3,329,922
    cond/branch_1_fun/gsi.fallback/...             -  6,203,750          -
    gsi.commit/draft/dot_general           3,798,828  3,654,844  3,778,672
    gsi.commit/draft/reduce_sum              333,750    332,578    332,500
    (none: copy-start, copy-done, copy)    2,105,078     17,578  2,211,328

and the ``%cond.3.clone`` wrapper (2,094,922, 6,218,750, 2,201,172) is
left out, as control flow.  Every reader the benchmark has returns what
it returned before the trace held scopes, on both recorded traces.
"""
import json
from pathlib import Path

import pytest

from bench import scopes, trace, work
from bench.metrics import Context, reader

DATA = Path(__file__).resolve().parent / "data"
PS = 1e-12
PROGRAM = "jit__decode_core"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
COUNTERS = {"accepted": 30.0, "decisions": 60.0, "serial_host_s": 0.5,
            "engine_steps": 10.0}
#: (scope, ps over the counted executions)
SCOPES = [("gsi.draft", 3_456_250 + 3_447_422 + 3_656_250),
          ("gsi.verify", 3_516_172 + 3_545_078 + 3_329_922),
          ("gsi.verify/prm", 3_516_172 + 3_545_078 + 3_329_922),
          ("gsi.fallback", 6_203_750),
          ("gsi.commit", 3_798_828 + 3_654_844 + 3_778_672
           + 333_750 + 332_578 + 332_500),
          ("gsi.commit/draft", 3_798_828 + 3_654_844 + 3_778_672
           + 333_750 + 332_578 + 332_500),
          ("gsi.select", 0), ("gsi.admit", 0), ("prm/gsi.verify", 0)]
UNSCOPED = 2_105_078 + 17_578 + 2_211_328
#: each reader's value at the parent commit, on each trace
BEFORE = {
    "small": {"host_serial_ms_per_step": 50.0, "accept_rate": 50.0,
              "decode_step_ms": None, "prefill_ms_per_request": None,
              "paged_attn_roofline": None,
              "idle_share": 99.80399802679698,
              "step_mfu": 0.022232662379099356},
    "scopes": {"host_serial_ms_per_step": 50.0, "accept_rate": 50.0,
               "decode_step_ms": 0.015955333333333335,
               "prefill_ms_per_request": None, "paged_attn_roofline": None,
               "idle_share": 99.68446245422805,
               "step_mfu": 0.03662147450246903},
}


@pytest.fixture(scope="module")
def found():
    return scopes.read(str(DATA / "scopes.xplane.pb"))


def _ctx(summary, counters=COUNTERS):
    return Context(counters, 1, PEAKS, summary, work.Work(1e9, 1e6, 1e6), 2)


def test_scope_paths_by_program(found):
    assert list(found.paths) == [PROGRAM]
    paths = found.paths[PROGRAM]
    assert paths["jit(_decode_core)/cond/branch_1_fun/gsi.fallback/"
                 "dot_general"] == [pytest.approx(6_203_750 * PS), 1]
    assert paths["jit(_decode_core)/gsi.draft/dot_general"][1] == 3
    # copies carry no tf_op; the cond wrapper is left out
    assert paths[""] == [pytest.approx(UNSCOPED * PS), 8]
    assert sum(n for _, n in paths.values()) == 8 + 3 * 4 + 1


@pytest.mark.parametrize("scope,ps", SCOPES)
def test_scope_time(found, scope, ps):
    assert found.time(PROGRAM, scope) == pytest.approx(ps * PS)


def test_scope_time_sums_every_operation_but_control_flow(found):
    summary = trace.reduce(str(DATA / "scopes.xplane.pb"))
    ops = sum(t for name, (t, _) in summary.ops.items()
              if not name.startswith("cond"))
    assert found.total(PROGRAM) == pytest.approx(ops, rel=1e-3)


def test_under(found):
    assert found.under(PROGRAM) == pytest.approx(
        found.total(PROGRAM) - UNSCOPED * PS)
    assert found.under(PROGRAM, "sched.") == 0
    assert found.under("jit__admit") == 0


def test_window_must_be_in_the_trace():
    with pytest.raises(ValueError, match="no 'nowhere' span"):
        scopes.read(str(DATA / "scopes.xplane.pb"), window="nowhere")


def test_report_per_execution():
    row = scopes.report(str(DATA / "scopes.xplane.pb"))[PROGRAM]
    assert row["executions"] == 3
    assert row["program_ms"] == pytest.approx(
        BEFORE["scopes"]["decode_step_ms"])
    for scope, ps in SCOPES:
        if scope in scopes.SCOPES:
            assert row["scopes_ms"].get(scope, 0.0) == pytest.approx(
                ps * PS * 1e3 / 3)
    assert row["covered"] == pytest.approx(
        100 * (1 - UNSCOPED * PS * 1e3 / 3 / row["ops_ms"]))


def test_report_without_scopes():
    # a program built without the engine's scopes
    row = scopes.report(str(DATA / "small.xplane.pb"))["jit__lambda"]
    assert row["covered"] == 0.0 and row["scopes_ms"] == {}


def test_command(capsys):
    path = str(DATA / "scopes.xplane.pb")
    assert scopes.main([path]) == 0
    assert json.loads(capsys.readouterr().out) == json.loads(
        json.dumps(scopes.report(path)))


def test_breakdown_reads_as_before():
    b = trace.breakdown(trace.reduce(str(DATA / "scopes.xplane.pb")))
    assert [n for n, _ in b["device_ops"]] == [
        "convolution_tanh_fusion", "convert_reduce_fusion", "cond.3.clone",
        "convolution_reduce_fusion", "copy", "reduce", "copy-start",
        "copy-done"]
    assert b["idle_gaps"][0] == ["none (device 0)",
                                 pytest.approx(0.0039671030000000005)]


@pytest.mark.parametrize("data", sorted(BEFORE))
def test_existing_readers_read_as_before(data):
    summary = trace.reduce(str(DATA / f"{data}.xplane.pb"))
    names = [m["name"] for m in json.loads(
        (DATA.parents[2] / "BENCHMARK.json").read_text())["per_layer"]]
    assert sorted(names) == sorted(BEFORE[data])
    for name, before in BEFORE[data].items():
        got = reader(name)(_ctx(summary))
        assert got == (None if before is None else pytest.approx(before,
                                                                  rel=1e-12))
