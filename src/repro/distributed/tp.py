"""Trace-time tensor-parallel context for the serving mesh mode.

The model code (``repro.models``) is written single-device: plain
einsums over whole weight tensors.  Under the engine's ``mesh=`` mode
the *target* model's attention/FFN/vocab weights arrive inside a
``shard_map`` body as **local shards** (global dim / tp).  Rather than
fork the model code, the engine traces the shard_map body inside a
:func:`tensor_parallel` context; the (few) model-side hooks call
:func:`axis` and, when it is set AND the tensor they hold is smaller
than the config says it should be, insert the collective that makes
the computation bitwise-identical to the unsharded one:

* row-parallel matmuls (attention ``wo``, FFN ``wo``) ``all_gather``
  both the sharded activation and the sharded weight and run the full
  matmul replicated — exact concatenation followed by the identical
  op on identical operands, so the result is bit-equal to unsharded
  (a psum-of-partials would reorder float additions and is not);
* the vocab-sharded embedding lookup masks out-of-shard token ids and
  ``psum``s (x + 0 == x, exact);
* the vocab-sharded unembed computes local logits and ``all_gather``s
  the vocab dim.

Replicated params (draft, PRM, and any target leaf the plan leaves
whole) match their config sizes, so every hook no-ops for them —
one shard_map body serves sharded and replicated models alike.

This module must stay import-light (jax only): it is imported by
``repro.models.common``/``attention`` and must not create a cycle
back into the models or serving packages.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax

# Name of the mesh axis the current trace is sharded over (None =
# unsharded trace — every hook no-ops).  Per thread: the fleet loop
# traces each replica's phases on that replica's own thread, and one
# replica leaving its context must not end another's.
_STATE = threading.local()


def axis() -> Optional[str]:
    """The active tensor-parallel mesh axis name, or None."""
    return getattr(_STATE, "axis", None)


def axis_size() -> int:
    """Size of the active tp axis (1 when no context is active)."""
    ax = axis()
    if ax is None:
        return 1
    return jax.lax.psum(1, ax)


@contextlib.contextmanager
def tensor_parallel(axis_name: str = "model"):
    """Mark the enclosed trace (on this thread) as running inside a
    shard_map over ``axis_name``; model hooks become collective-aware
    for its scope."""
    prev = axis()
    _STATE.axis = axis_name
    try:
        yield
    finally:
        _STATE.axis = prev


def tp_plan(cfg, tp: int) -> dict:
    """Which weight groups of ``cfg`` can shard ``tp``-ways.

    Returns ``{"attn": bool, "mlp": bool, "vocab": bool}``.  Attention
    shards only when *both* the query heads and the kv heads divide
    ``tp`` (GQA grouping must stay aligned across q and kv shards);
    the MLP needs ``d_ff % tp == 0``; the embedding needs the *padded*
    vocab (multiple of 512) to divide.  Anything that doesn't divide
    stays replicated — sharding is always an optimisation, never a
    requirement.
    """
    if tp <= 1:
        return {"attn": False, "mlp": False, "vocab": False}
    from repro.models.common import padded_vocab
    heads_ok = (cfg.num_heads % tp == 0) and (cfg.num_kv_heads % tp == 0)
    return {
        "attn": heads_ok,
        "mlp": cfg.d_ff % tp == 0,
        "vocab": padded_vocab(cfg) % tp == 0,
    }
