"""Fused log-softmax + label-gather Pallas kernel — the GSI scoring op.

Computing log pi_B(y_i | x) for n draft steps is one forward pass plus, per
token, ``log_softmax(h @ W)[label]``.  Naively XLA materializes the full
(T, V) logits in HBM (V up to 262k for gemma3 — the logits tensor dwarfs the
activations).  This kernel streams W in vocab tiles through VMEM, keeping an
online logsumexp accumulator and the gathered label logit per token, so the
logits tensor never exists in HBM:

    per (token-tile i, vocab-tile j):   logits_ij = h_i @ W_j  (MXU)
    m, s   <- online max / sum-exp update     (VPU)
    picked <- sum(one_hot(label - j0) * logits_ij)

Output: picked - (m + log s).  Grid is (T/Tt, V/Vt) with the vocab dim
innermost; accumulators live in VMEM scratch across the j sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
LANES = 128


def _kernel(h_ref, w_ref, lab_ref, o_ref, m_ref, s_ref, p_ref, *,
            vt: int, vocab_size: int, num_vt: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        s_ref[...] = jnp.zeros_like(s_ref)
        p_ref[...] = jnp.full_like(p_ref, NEG)

    # operands stay in their storage dtype; the MXU accumulates in f32
    logits = jnp.dot(h_ref[...], w_ref[...],
                     preferred_element_type=jnp.float32)  # (Tt, Vt)

    v0 = j * vt
    vidx = v0 + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    valid = vidx < vocab_size
    logits = jnp.where(valid, logits, NEG)

    # online logsumexp (scratch rows broadcast over 128 lanes)
    m_old = m_ref[:, :1]                          # (Tt, 1)
    m_new = jnp.maximum(m_old, jnp.max(logits, axis=-1, keepdims=True))
    scale = jnp.exp(m_old - m_new)
    s_new = s_ref[:, :1] * scale + jnp.sum(jnp.exp(logits - m_new),
                                           axis=-1, keepdims=True)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    s_ref[...] = jnp.broadcast_to(s_new, s_ref.shape)

    # gather the label logit if it falls in this vocab tile
    hit = vidx == lab_ref[...]                    # (Tt, Vt) vs (Tt, 1)
    picked = jnp.max(jnp.where(hit, logits, NEG), axis=-1, keepdims=True)
    p_ref[...] = jnp.maximum(p_ref[...], picked)

    @pl.when(j == num_vt - 1)
    def _finish():
        o_ref[...] = p_ref[...] - (m_ref[...] + jnp.log(s_ref[...]))


@functools.partial(jax.jit,
                   static_argnames=("vocab_size", "tt", "vt", "interpret"))
def logprob_gather_pallas(h, w, labels, vocab_size: int, *, tt: int = 256,
                          vt: int = 512, interpret: bool = False):
    """h: (B,S,d); w: (d,V); labels: (B,S) -> (B,S) fp32.

    Default tiles keep scoped VMEM well under 16 MiB at d = 2048 in
    bf16: double-buffered h (2 x 1 MiB) and w (2 x 2 MiB) blocks plus
    the (Tt, Vt) f32 logits and their exp.
    """
    B, S, d = h.shape
    V = w.shape[1]
    T = B * S
    hf = h.reshape(T, d)
    lab = labels.reshape(T, 1).astype(jnp.int32)
    tt = min(tt, -(-T // 8) * 8)
    vt = min(vt, V)
    # pad T to a multiple of tt
    Tp = (T + tt - 1) // tt * tt
    if Tp != T:
        hf = jnp.pad(hf, ((0, Tp - T), (0, 0)))
        lab = jnp.pad(lab, ((0, Tp - T), (0, 0)))
    num_vt = (V + vt - 1) // vt

    out = pl.pallas_call(
        functools.partial(_kernel, vt=vt, vocab_size=vocab_size,
                          num_vt=num_vt),
        grid=(Tp // tt, num_vt),
        in_specs=[
            pl.BlockSpec((tt, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, vt), lambda i, j: (0, j)),
            pl.BlockSpec((tt, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tt, LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Tp, LANES), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((tt, LANES), jnp.float32),   # running max
            pltpu.VMEM((tt, LANES), jnp.float32),   # running sum-exp
            pltpu.VMEM((tt, LANES), jnp.float32),   # picked label logit
        ],
        interpret=interpret,
    )(hf, w, lab)
    return out[:T, 0].reshape(B, S)
