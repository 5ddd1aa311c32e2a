"""Blockwise (flash) causal/sliding-window GQA attention Pallas kernel.

Used for train/prefill (the decode path is a single-row matvec XLA already
handles well).  Grid: (B, H, Sq/Qt, Sk/Kt), k innermost; online-softmax
accumulators (m, l, acc) live in VMEM scratch across the k sweep.  GQA is
expressed in the BlockSpec index maps: query head h reads kv head h // G, so
no repeated KV materialization.  Heads are folded into the lane dim
(``(B, S, H*hd)``), so each block is a 2-D ``(tile, hd)`` slab; a head
dim that is not a multiple of 128 lanes is zero-padded up to one.  Sliding
windows additionally mask ``kpos <= qpos - window``; fully-masked tiles
are skipped by zero-ing their
contribution (on TPU the grid is traversed regardless; the masked-out tiles
cost one matmul — acceptable at our block sizes and noted in EXPERIMENTS
§Perf).

Block sizes default to (128, 128): MXU-aligned, and VMEM footprint
(q + k + v + acc tiles) ~ 4 * 128 * hd * 4B ≈ 0.5 MB for hd=256.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
LANES = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, qt: int, kt: int,
            num_kt: int, sq: int, sk: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)               # (Qt, hd)
    k = k_ref[0].astype(jnp.float32)               # (Kt, hd)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qpos = qi * qt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = kj * kt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = (qpos < sq) & (kpos < sk)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG)

    m_old = m_ref[:, :1]                           # (Qt, 1)
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_old - m_new)
    l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kj == num_kt - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "scale", "qt", "kt",
                              "interpret"))
def flash_attention_pallas(q, k, v, *, causal=True, window=0, scale=None,
                           qt: int = 128, kt: int = 128,
                           interpret: bool = False):
    """q: (B,Sq,H,hd); k/v: (B,Sk,KV,hd) -> (B,Sq,H,hd)."""
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    if scale is None:
        scale = hd ** -0.5
    qt = min(qt, max(8, Sq))
    kt = min(kt, max(8, Sk))
    Sqp = (Sq + qt - 1) // qt * qt
    Skp = (Sk + kt - 1) // kt * kt
    # a head dim off the 128-lane tile is zero-padded up to it: the
    # padded lanes add nothing to q.k and come out of p.v as zeros
    hdp = -(-hd // LANES) * LANES
    if (Sqp, hdp) != (Sq, hd):
        q = jnp.pad(q, ((0, 0), (0, Sqp - Sq), (0, 0), (0, hdp - hd)))
    if (Skp, hdp) != (Sk, hd):
        k = jnp.pad(k, ((0, 0), (0, Skp - Sk), (0, 0), (0, hdp - hd)))
        v = jnp.pad(v, ((0, 0), (0, Skp - Sk), (0, 0), (0, hdp - hd)))
    num_kt = Skp // kt
    # heads folded into the lane dim (free reshapes): head h is lane
    # block h of width hdp, so blocks are (tile, hdp) 2-D slabs
    q2 = q.reshape(B, Sqp, H * hdp)
    k2 = k.reshape(B, Skp, KV * hdp)
    v2 = v.reshape(B, Skp, KV * hdp)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          qt=qt, kt=kt, num_kt=num_kt, sq=Sq, sk=Sk),
        grid=(B, H, Sqp // qt, num_kt),
        in_specs=[
            pl.BlockSpec((1, qt, hdp), lambda b, h, i, j: (b, i, h)),
            pl.BlockSpec((1, kt, hdp),
                         lambda b, h, i, j, g=G: (b, j, h // g)),
            pl.BlockSpec((1, kt, hdp),
                         lambda b, h, i, j, g=G: (b, j, h // g)),
        ],
        out_specs=pl.BlockSpec((1, qt, hdp), lambda b, h, i, j: (b, i, h)),
        out_shape=jax.ShapeDtypeStruct((B, Sqp, H * hdp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qt, LANES), jnp.float32),   # m (lane-broadcast)
            pltpu.VMEM((qt, LANES), jnp.float32),   # l
            pltpu.VMEM((qt, hdp), jnp.float32),     # acc
        ],
        interpret=interpret,
    )(q2, k2, v2)
    return out.reshape(B, Sqp, H, hdp)[:, :Sq, :, :hd]
