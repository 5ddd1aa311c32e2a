"""Every per-layer reader named in BENCHMARK.json loads and reads a
context built from the small recorded trace; with nothing to read it
returns None, never 0.

    python3 -m pytest bench/tests/test_metrics.py
"""
import json
from pathlib import Path

import pytest

from bench import trace, work
from bench.metrics import Context, reader

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
COUNTERS = {"accepted": 30.0, "decisions": 60.0, "serial_host_s": 0.5,
            "engine_steps": 10.0}


def names():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [m["name"] for m in bench["per_layer"]]


@pytest.mark.parametrize("name", names())
def test_reader_reads_or_returns_none(name):
    summary = trace.reduce(str(DATA))
    ctx = Context(COUNTERS, 1, PEAKS, summary, work.Work(1e9, 1e6, 1e6), 2)
    value = reader(name)(ctx)
    assert value is None or value > 0
    empty = Context({k: 0.0 for k in COUNTERS}, 1, PEAKS, None, None, 0)
    assert reader(name)(empty) is None


def test_counter_readers():
    ctx = Context(COUNTERS, 1, PEAKS, None, None, 0)
    assert reader("accept_rate")(ctx) == pytest.approx(50.0)
    assert reader("host_serial_ms_per_step")(ctx) == pytest.approx(50.0)


def test_trace_readers():
    summary = trace.reduce(str(DATA))
    ctx = Context(COUNTERS, 1, PEAKS, summary, work.Work(1e9, 1e6, 1e6), 2)
    idle = 100 * (1 - summary.busy_mean_s / summary.window_s)
    assert reader("idle_share")(ctx) == pytest.approx(idle)
    mfu = 100 * 1e9 / (summary.window_s * 197e12)
    assert reader("step_mfu")(ctx) == pytest.approx(mfu)
    # the small trace holds no engine program and no paged kernel
    assert reader("decode_step_ms")(ctx) is None
    assert reader("paged_attn_roofline")(ctx) is None
