"""Calibrate a cell's acceptance threshold on the chip.

    python3 -m bench.calibrate --workload <name> --seed <n> --seconds <s>

Runs the cell once at ``--threshold`` (default +inf: every draft step
rejected) and finds the threshold ``u`` at which about half of the steps
would accept: the median of the selected candidate's tilted reward.  The
engine selects candidate i of a step with probability
``softmax(beta * tilted)_i`` (soft best-of-n), so the share a threshold
accepts is the mean over the recorded steps of that probability summed
over the candidates at or above it.  Which steps are accepted changes the
contexts, so run again at the threshold found and check the share
(``accepted``); write the threshold as ``threshold_u`` into
``bench/cells/<name>.json``.  The weights are fixed by the
configuration's ``weights_seed``, so the threshold holds for every seed.
Also prints the run's metrics and the numbers the check compares.
"""
from __future__ import annotations

import argparse
import json
import math
import sys

import numpy as np

from bench.run import ROOT, Refused, load_cell, log, require_chips, run_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--threshold", type=float, default=math.inf)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        cell = load_cell(args.workload)
        out = run_cell(cell, seed=args.seed, seconds=args.seconds,
                       trace=False, devices=require_chips(cell.chips),
                       threshold_u=args.threshold)
    except Refused as e:
        log(f"refused: {e}")
        return 2
    extra = out.pop("extra")
    tilted = np.stack([r.tilted for rs in extra["rows"].values()
                       for r in rs])
    beta = float(cell.config["engine"]["beta"])
    c = extra["counters"]
    print(json.dumps({"workload": cell.name, "steps": len(tilted),
                      "threshold": args.threshold,
                      "accepted": c["accepted"] / max(c["decisions"], 1),
                      "stops": extra["stops"],
                      "threshold_u": median_selected(tilted, beta),
                      "run": out}), flush=True)
    return 0


def accept_share(tilted: np.ndarray, beta: float, u: float) -> float:
    """The expected share of steps accepted at ``u``; ``tilted`` is
    (steps, n)."""
    z = beta * (tilted - tilted.max(axis=1, keepdims=True))
    p = np.exp(z) / np.exp(z).sum(axis=1, keepdims=True)
    return float(np.mean(np.sum(p * (tilted >= u), axis=1)))


def median_selected(tilted: np.ndarray, beta: float) -> float:
    """The largest recorded tilted reward at which half or more of the
    steps would accept."""
    vals = np.unique(tilted)
    lo, hi = 0, len(vals) - 1          # share(vals[lo]) >= 0.5 holds
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if accept_share(tilted, beta, vals[mid]) >= 0.5:
            lo = mid
        else:
            hi = mid - 1
    return float(vals[lo])


if __name__ == "__main__":
    sys.exit(main())
