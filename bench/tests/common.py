"""A toy cell for running the harness on the CPU (no chip)."""
from __future__ import annotations

import json
from pathlib import Path

from bench.run import Cell

HERE = Path(__file__).resolve().parent
#: the toy cell's calibrated threshold (``bench.calibrate``'s rule on the
#: CPU: 43% of steps accepted at it)
THRESHOLD = 0.535
#: the limit of the toy cell, from its readings on the CPU over 12 seeds
#: with accepted steps (3-second windows, u = THRESHOLD): the program at
#: most tilted_err 0.00107, the float8 control at least 0.00124 (a sample
#: may hold one accepted step at this size, and float8 then reads as
#: little as bfloat16 by chance); the chip cells' readings are in PERF.md.
LIMITS = {"tilted_err": 0.003}


def tiny_cell(threshold_u: float = THRESHOLD, limits=None) -> Cell:
    config = json.loads((HERE / "tiny.json").read_text())
    traffic = json.loads((HERE / "tiny_traffic.json").read_text())
    units = {"tokens_per_s": "tokens/s", "ttft_p90_s": "s",
             "step_gap_p90_s": "s", "setup_s": "s"}
    return Cell("tiny", 1, config, traffic,
                {"threshold_u": threshold_u, "limits": limits or LIMITS},
                list(units), [], units)
