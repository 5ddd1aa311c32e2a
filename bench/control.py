"""The readings a cell's limits are set from, on the chip.

    python3 -m bench.control --workload <name> --seed <n> --seconds 15

One run of the cell as it is timed, with a shorter window: its check
numbers are the program's readings.  Then, on the same sampled requests,
the numbers of the control: the reference computed in float8 in the
program's place, judged against the cell's limits as the program's are
(``control_correct`` has to come out false).  Prints one JSON line; run
it once per seed.
"""
from __future__ import annotations

import argparse
import json
import sys

from bench.check import judge
from bench.run import ROOT, Refused, load_cell, log, require_chips, run_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        cell = load_cell(args.workload)
        out = run_cell(cell, seed=args.seed, seconds=args.seconds,
                       trace=False, devices=require_chips(cell.chips),
                       control=True)
    except Refused as e:
        log(f"refused: {e}")
        return 2
    ex = out.pop("extra")
    print(json.dumps({"workload": cell.name, "seed": args.seed,
                      "program": ex["check"]["numbers"],
                      "control": ex["control"]["numbers"],
                      "program_correct": out["correct"],
                      "control_correct": judge(
                          ex["control"], cell.calib.get("limits", {}))[
                              "correct"],
                      "counts": ex["check"]["counts"],
                      "problems": ex["check"]["problems"][:3],
                      "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
