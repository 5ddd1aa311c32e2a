"""Model architectures: one file per architecture, ``bench/arch/<arch>.py``,
found by the ``arch`` key of each model entry of a configuration file and
loaded by path, as ``bench/metrics`` loads a metric reader.  A model entry
without the key stops the run: no architecture is guessed.

An architecture file defines four functions of a model entry ``m`` (the
configuration's keys for one of the draft, target and PRM):

``program(role, m, dtype)``  the program's ``repro.config.ModelConfig``
                             for the model; the only function that may
                             import the program.
``shapes(m)``                weight leaf name -> (shape, std), std 0 for
                             zeros, in the layout the program's model
                             takes (``bench/weights.py`` makes them).
``forward(m, w, seq, length, *, lowp)``
                             the plain reference over one padded
                             sequence: per position ``lse``, ``next``,
                             ``pad`` and, for a PRM, ``reward``
                             (``bench/reference.py`` states the rules).
``cost(m)``                  the ``bench.work.Cost`` of one fed token.
"""
from __future__ import annotations

import functools
import importlib.util
import sys
from pathlib import Path

from bench.errors import Refused

HERE = Path(__file__).resolve().parent
#: directories searched for ``<arch>.py``, in order
SEARCH = (HERE,)


def module(m: dict):
    """The architecture file that model entry ``m`` names."""
    name = m.get("arch")
    if not name:
        raise Refused(f"model {m.get('hf', '?')!r} names no arch")
    for d in SEARCH:
        path = Path(d) / f"{name}.py"
        if path.is_file():
            return _load(path.resolve())
    raise Refused(f"no architecture file {name}.py in "
                  f"{', '.join(str(d) for d in SEARCH)}")


@functools.cache
def _load(path: Path):
    name = f"bench.arch.{path.stem}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod
