"""Seeded random weights, made by the benchmark and handed to the program.

A model is described by its configuration file's keys; its architecture
file (``bench/arch/<arch>.py``) turns them into the leaves, shapes and
scales of :func:`shapes`.  :func:`make_triple` builds the draft, target
and PRM trees for one device in ONE jitted call from the configuration's
``weights_seed``, in the dtype they are served in, each leaf from its own
key: the index of its name among the model's sorted leaf names.  The
trees follow the layout the program's models take; :func:`check_layout`
compares them with the program's own parameter shapes at set-up, so a
change of layout stops the run instead of feeding the wrong weights.

Scales: the embedding, the untied LM head and the reward head N(0,
0.02^2), the published ``initializer_range`` of the Qwen configurations;
a projection inside a layer N(0, 1/fan_in) with the fan-in counted as
the program's initializer counts it; RMS-norm gains are stored as
``gamma`` in a ``(1 + gamma)`` form and start at 0; biases at 0.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import arch

VOCAB_PAD = 512        # the program pads every vocabulary to this multiple
INIT_RANGE = 0.02      # the published initializer_range


def padded_vocab(m: dict) -> int:
    return -(-int(m["vocab_size"]) // VOCAB_PAD) * VOCAB_PAD


def shapes(m: dict) -> dict:
    """Leaf name -> (shape, std) of one model; std 0 means zeros."""
    return arch.module(m).shapes(m)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for name, leaf in flat.items():
        node = tree
        *path, last = name.split("/")
        for p in path:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def _model(m: dict, key, dtype) -> dict:
    flat = {}
    for i, (name, (shape, std)) in enumerate(sorted(shapes(m).items())):
        if std == 0.0:
            flat[name] = jnp.zeros(shape, dtype)
        else:
            k = jax.random.fold_in(key, i)
            flat[name] = (jax.random.normal(k, shape, jnp.float32)
                          * std).astype(dtype)
    return _nest(flat)


def make_triple(models: dict, seed: int, device, dtype=jnp.bfloat16):
    """(draft, target, prm) weight trees on ``device``, one jitted call.

    The key is XLA's RBG generator: it makes a published-width model in
    about a second where threefry takes most of a minute to compile."""
    roles = ("draft", "target", "prm")

    def make(key):
        return tuple(_model(models[r], jax.random.fold_in(key, i), dtype)
                     for i, r in enumerate(roles))

    key = jax.random.key(int(seed), impl="rbg")
    sharding = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(make, out_shardings=sharding)(key)


def check_layout(params, program_params) -> None:
    """Raise unless two trees have the same structure, shapes and dtypes
    (``program_params`` may hold ``jax.ShapeDtypeStruct`` leaves)."""
    a = jax.tree_util.tree_flatten_with_path(params)[0]
    b = jax.tree_util.tree_flatten_with_path(program_params)[0]
    got = {jax.tree_util.keystr(p): (x.shape, x.dtype) for p, x in a}
    want = {jax.tree_util.keystr(p): (x.shape, x.dtype) for p, x in b}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise ValueError(f"weight layout differs from the program's: "
                         f"{diff[:6]}")


def param_bytes(tree) -> int:
    return sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))
