"""Record the golden readings that ``test_arch.py`` holds the harness to.

    JAX_PLATFORMS=cpu python3 -m bench.tests.record_golden

Writes ``data/golden.json`` and ``data/golden_forward.npz``, on the CPU,
through the harness's four entry points that depend on a model's
architecture (``run.model_config``, ``weights.shapes``, ``work.Cost.of``,
``reference.forward``):

* every model of ``tri17`` and ``math7b-stage``: its sorted ``(leaf,
  shape, std)`` list, its ``Cost`` fields and the program's
  ``ModelConfig`` (pure Python, no weights made);
* the toy triple of ``tiny.json``: a SHA-256 of each weight leaf that
  ``make_triple`` makes, and the reference's readings, float32 and float8
  control, over one fixed sequence.

Recorded once, before the architecture-specific code moved into
``bench/arch/``; the tests hold the moved code to these values to the bit.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

CONFIGS = ("tri17", "math7b-stage")
ROLES = ("draft", "target", "prm")
WIDTH, LENGTH = 128, 40         # the fixed sequence: 40 tokens padded to 128


def config(name: str) -> dict:
    path = ROOT / "bench" / "configs" / f"{name}.json"
    return json.loads(path.read_text())


def tiny() -> dict:
    return json.loads((HERE / "tiny.json").read_text())


def sequence() -> np.ndarray:
    seq = np.zeros((WIDTH,), np.int32)
    seq[:LENGTH] = np.random.default_rng(20251019).integers(3, 500, LENGTH)
    return seq


def model_record(m: dict, dtype: str) -> dict:
    from bench import weights, work
    from bench.run import model_config
    return {
        "shapes": [[k, list(s), std]
                   for k, (s, std) in sorted(weights.shapes(m).items())],
        "cost": dataclasses.asdict(work.Cost.of(m)),
        "model_config": json.loads(json.dumps(dataclasses.asdict(
            model_config("role", m, dtype)))),
    }


def weight_digests(cfg: dict) -> dict:
    import jax
    from bench import weights
    params = weights.make_triple(cfg["models"], cfg["weights_seed"],
                                 jax.devices("cpu")[0])
    out = {}
    for role, tree in zip(ROLES, params):
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            key = f"{role}{jax.tree_util.keystr(path)}"
            out[key] = hashlib.sha256(np.asarray(leaf).tobytes()).hexdigest()
    return out


def forward_readings(cfg: dict) -> dict:
    import jax
    from bench import reference, weights
    params = weights.make_triple(cfg["models"], cfg["weights_seed"],
                                 jax.devices("cpu")[0])
    out = {}
    for role, w in zip(ROLES, params):
        for lowp in (False, True):
            got = reference.forward(cfg["models"][role], w, sequence(),
                                    LENGTH, lowp=lowp)
            for k, v in got.items():
                out[f"{role}/{'lowp' if lowp else 'f32'}/{k}"] = v
    return out


def main() -> int:
    golden = {"models": {f"{name}/{role}": model_record(
        config(name)["models"][role], config(name)["dtype"])
        for name in CONFIGS for role in ROLES},
        "tiny_weights": weight_digests(tiny())}
    (HERE / "data" / "golden.json").write_text(
        json.dumps(golden, indent=1, sort_keys=True) + "\n")
    np.savez(HERE / "data" / "golden_forward.npz", **forward_readings(tiny()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
