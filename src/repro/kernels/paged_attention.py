"""Paged decode-attention Pallas kernel: gather K/V through the block table.

One query token per request against a paged KV cache.  The page pools stay
in HBM-resident arrays shaped ``(P, page_size, KV, hd)``; the kernel never
materializes the gathered ``(B, S, KV, hd)`` copy that the jnp oracle
builds.  Instead the block table is a *scalar-prefetch* operand
(``pltpu.PrefetchScalarGridSpec``): the K/V BlockSpec index maps read
``pt[b, i]`` to DMA exactly the physical page for logical block ``i`` of
request ``b`` — the gather happens in the grid indexing, not in compute.

Grid: ``(B, nblk)`` with the block sweep innermost.  One cell DMAs one
whole physical page (all kv heads) and folds it into online-softmax
accumulators (m, l, acc) that live in VMEM scratch across the sweep, as
in ``flash_attention.py``.  GQA: the G query heads of kv head g are one
``(G, hd)`` matmul operand (padded to 8 sublanes).  Validity is the
absolute-layout decode mask: position ``kpos = i * ps + lane`` is live iff
``kpos <= pos[b]`` (and ``kpos > pos[b] - window`` for sliding-window
layers) — stale rows of partially-filled or recycled pages are masked, so
pages never need zeroing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
LANES = 128


def _grouped_query(q, KV: int):
    """(B,1,H,hd) -> (B,KV,Gp,hd) float32, the G = H/KV query heads of a
    kv head padded with zero rows to a multiple of 8 sublanes."""
    B, _, H, hd = q.shape
    G = H // KV
    Gp = -(-G // 8) * 8
    qg = q[:, 0].astype(jnp.float32).reshape(B, KV, G, hd)
    return jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))


def _online_update(q, k, v, kpos, pos, m_ref, l_ref, acc_ref, g, *,
                   scale: float, window: int):
    """Fold one page of one kv head into the (m, l, acc) accumulators.

    q: (Gp, hd) f32; k/v: (ps, hd) f32; kpos: (1, ps) absolute
    positions of the page's rows.  m/l scratch rows are broadcast over
    their 128 lanes; lane 0 is read back.
    """
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = kpos <= pos                               # (1, ps)
    if window:
        mask &= kpos > pos - window
    s = jnp.where(mask, s, NEG)                      # (Gp, ps)
    m_old = m_ref[g][:, :1]                          # (Gp, 1)
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_old - m_new)
    l_new = l_ref[g][:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[g] = acc_ref[g] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])
    l_ref[g] = jnp.broadcast_to(l_new, l_ref.shape[1:])


def _kernel(pt_ref, pos_ref, *refs, scale: float, window: int, ps: int,
            nblk: int, kv: int, hd: int, quantized: bool):
    """One (request, logical block) grid cell: the whole physical page,
    every kv head.  ``quantized`` adds the per-page per-kv-head scale
    operands (scalar prefetch) and dequantizes K/V in registers — the
    fp copy of a quantized page is never written anywhere."""
    if quantized:
        ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, \
            acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    i = pl.program_id(1)
    pos = pos_ref[b]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # pages wholly past pos contribute exp(NEG - m) == 0 exactly; skip
    # their compute (their DMA is skipped by the clamped index map)
    @pl.when(i * ps <= pos)
    def _page():
        page = pt_ref[b, i]
        kpos = i * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        for g in range(kv):
            k = k_ref[0, :, g * hd:(g + 1) * hd].astype(jnp.float32)
            v = v_ref[0, :, g * hd:(g + 1) * hd].astype(jnp.float32)
            if quantized:
                k = k * ks_ref[page, g]
                v = v * vs_ref[page, g]
            _online_update(q_ref[0, g], k, v, kpos, pos, m_ref, l_ref,
                           acc_ref, g, scale=scale, window=window)

    @pl.when(i == nblk - 1)
    def _finish():
        for g in range(kv):
            l = jnp.maximum(l_ref[g][:, :1], 1e-30)
            o_ref[0, g] = acc_ref[g] / l


def _paged_call(q, kp, vp, scales, pt, pos, *, window, scale, interpret):
    """Shared pallas_call for the fp and quantized kernels.

    Grid ``(B, nblk)`` with the block sweep innermost.  K/V pools are
    viewed as ``(P, ps, KV*hd)`` (a free reshape) and DMA'd one whole
    physical page per cell, so every block's trailing dims equal the
    array's and any page size / head count tiles.  The index map clamps
    the logical block to the request's last live one, so pages past
    ``pos`` are never fetched.
    """
    B, _, H, hd = q.shape
    P, ps, KV, _ = kp.shape
    G = H // KV
    nblk = pt.shape[1]
    if scale is None:
        scale = hd ** -0.5
    qg = _grouped_query(q, KV)
    Gp = qg.shape[2]
    kp2 = kp.reshape(P, ps, KV * hd)
    vp2 = vp.reshape(P, ps, KV * hd)
    quantized = scales is not None
    n_pref = 4 if quantized else 2

    def live_block(b, i, pt_, pos_):
        return pt_[b, jnp.minimum(i, jnp.maximum(pos_[b], 0) // ps)]

    def qmap(b, i, *pref):
        return (b, 0, 0, 0)

    def kvmap(b, i, pt_, pos_, *rest):
        return (live_block(b, i, pt_, pos_), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pref,
        grid=(B, nblk),
        in_specs=[
            pl.BlockSpec((1, KV, Gp, hd), qmap),
            pl.BlockSpec((1, ps, KV * hd), kvmap),
            pl.BlockSpec((1, ps, KV * hd), kvmap),
        ],
        out_specs=pl.BlockSpec((1, KV, Gp, hd), qmap),
        scratch_shapes=[
            pltpu.VMEM((KV, Gp, LANES), jnp.float32),     # m
            pltpu.VMEM((KV, Gp, LANES), jnp.float32),     # l
            pltpu.VMEM((KV, Gp, hd), jnp.float32),        # acc
        ],
    )
    prefetch = (pt.astype(jnp.int32), pos.astype(jnp.int32))
    if quantized:
        prefetch += tuple(s.astype(jnp.float32) for s in scales)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, ps=ps,
                          nblk=nblk, kv=KV, hd=hd, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, Gp, hd), jnp.float32),
        interpret=interpret,
    )(*prefetch, qg, kp2, vp2)
    out = out[:, :, :G].reshape(B, 1, H, hd)
    return out.astype(q.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "scale", "interpret"))
def paged_attention_quant_pallas(q, kp, vp, ks, vs, pt, pos, *, window=0,
                                 scale=None, interpret: bool = False):
    """q: (B,1,H,hd); kp/vp: (P,ps,KV,hd) codes; ks/vs: (P,KV) float32
    scales; pt: (B,nblk); pos: (B,).

    Same grid/BlockSpec structure as ``paged_attention_pallas`` with two
    extra scalar-prefetch operands (the scale tensors): the lookup
    ``ks[pt[b, i], g]`` is an SMEM read, not an HBM gather.
    """
    return _paged_call(q, kp, vp, (ks, vs), pt, pos, window=window,
                       scale=scale, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("window", "scale", "interpret"))
def paged_attention_pallas(q, kp, vp, pt, pos, *, window=0, scale=None,
                           interpret: bool = False):
    """q: (B,1,H,hd); kp/vp: (P,ps,KV,hd); pt: (B,nblk); pos: (B,)."""
    return _paged_call(q, kp, vp, None, pt, pos, window=window,
                       scale=scale, interpret=interpret)
