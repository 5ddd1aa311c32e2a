"""Per-layer metrics: one reader per metric, ``bench/metrics/<name>.py``,
found by the metric's name in ``BENCHMARK.json``.

A reader defines ``read(ctx: Context) -> float | None``.  It returns None
when the run holds nothing for it to read, and the metric is then left
out of the result line; a share of a roofline or of a peak is never
reported as 0 in place of a missing reading.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

from bench.trace import Summary
from bench.work import Work

HERE = Path(__file__).resolve().parent


@dataclass
class Context:
    """What a run hands its readers."""
    counters: dict              # program counters, window deltas
    chips: int
    peaks: dict                 # the device's row of bench/peaks.json
    trace: Optional[Summary]    # the traced window, when --trace 1
    traced_work: Optional[Work]  # required work dispatched in it
    traced_admissions: int      # admissions dispatched in it


def reader(name: str):
    path = HERE / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
