"""The plain reference: each model's forward pass over a whole sequence,
in float32 at the highest matmul precision, with no kernel, cache or
batching.  It imports nothing of the program; it reads the weights the
benchmark made (``bench/weights.py``) by their names.

The pass itself is the model's architecture's (``bench/arch/<arch>.py``
``forward``); this module dispatches to it and holds what every
architecture shares: float32 matmuls at ``HIGHEST``, RMS norm, rotary
positions, and the rounding of the control.

``lowp=True`` gives the control: the same pass computed in float8 (e4m3),
the precision below the configuration's bfloat16.  Every matmul operand
is rounded to float8 (one scale per row of activations, per output column
of weights), and so is every activation at the points where a bfloat16
program rounds its own: the residual stream, norm outputs, projections,
attention probabilities and output, the feed-forward's hidden and the
logits.  An architecture's ``forward`` rounds at the same points of its
own layers (``_mm`` and ``_r8``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _fake_fp8(a, axes):
    """Round ``a`` through float8 e4m3 with one scale per slice that
    reduces over ``axes``."""
    amax = jnp.max(jnp.abs(a), axis=axes, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / F8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _r8(a, lowp, axes=-1):
    return _fake_fp8(a, axes) if lowp else a


def _mm(spec, x, w, x_axes, w_axes, lowp):
    if lowp:
        x, w = _fake_fp8(x, x_axes), _fake_fp8(w, w_axes)
    return jnp.einsum(spec, x, w, precision=HI)


def _rms(x, gamma, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gamma)


def _rope(x, theta):
    """x: (S, H, hd) at positions 0..S-1, rotate-half convention."""
    S, _, hd = x.shape
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(S, dtype=np.float64)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(m: dict, w: dict, seq: np.ndarray, length: int, *,
            lowp: bool = False) -> dict:
    """Per-position readings of one model over one sequence.

    ``seq`` is padded to a fixed width so that every sequence of a cell
    runs one compiled program; positions at or past ``length`` are
    padding.  At position p: ``lse`` (log-sum-exp of the logits),
    ``next`` (the logit of ``seq[p+1]``), ``pad`` (the logit of token 0)
    and, for a PRM, ``reward``.
    """
    return arch.module(m).forward(m, w, seq, length, lowp=lowp)
