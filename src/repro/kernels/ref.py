"""Pure-jnp oracles for every Pallas kernel (the correctness reference)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def logprob_gather_ref(h, w, labels, vocab_size: int):
    """log softmax(h @ w)[labels].

    h: (B,S,d); w: (d,V); labels: (B,S) int -> (B,S) float32.
    """
    logits = (h.astype(jnp.float32) @ w.astype(jnp.float32))
    v = logits.shape[-1]
    if vocab_size < v:
        mask = jnp.arange(v) < vocab_size
        logits = jnp.where(mask, logits, -1e30)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return picked - logz


def flash_attention_ref(q, k, v, *, causal=True, window=0, scale=None):
    """q: (B,Sq,H,hd); k/v: (B,Sk,KV,hd) -> (B,Sq,H,hd)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    if scale is None:
        scale = hd ** -0.5
    qg = q.reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", p, v)
    return out.reshape(B, Sq, H, hd)


def paged_attention_ref(q, kp, vp, pt, pos, *, window=0, scale=None):
    """Paged decode attention: gather K/V through the block table.

    q: (B,1,H,hd) single-token queries; kp/vp: (P,ps,KV,hd) page pools;
    pt: (B,nblk) int32 block table (logical block j of row b lives in
    page pt[b,j]); pos: (B,) per-request positions -> (B,1,H,hd).

    Logical layout is *absolute*: cache row j holds position j, so the
    validity mask is ``j <= pos`` (and ``j > pos - window`` for
    sliding-window layers).  For full-attention layers this is exactly the
    dense decode layout, so outputs are bit-identical to the dense path:
    masked rows contribute exp(-1e30 - m) == 0.0 to the softmax and
    0.0 * v to the weighted sum regardless of stale page content.
    """
    B, _, H, hd = q.shape
    P, ps, KV, _ = kp.shape
    nblk = pt.shape[1]
    S = nblk * ps
    if scale is None:
        scale = hd ** -0.5
    rows = (pt[:, :, None] * ps
            + jnp.arange(ps)[None, None, :]).reshape(B, S)   # (B, S)
    k = jnp.take(kp.reshape(P * ps, KV, hd), rows, axis=0)   # (B,S,KV,hd)
    v = jnp.take(vp.reshape(P * ps, KV, hd), rows, axis=0)
    slots = jnp.arange(S)[None, :]                           # (1, S)
    mask = slots <= pos[:, None]
    if window:
        mask &= slots > pos[:, None] - window
    # identical math/order to models.attention.gqa_attention, including
    # its REPRO_ATTN_SCORES_BF16 score-buffer knob (_score_dtype) — the
    # bit-identity with the dense path must survive the env switch
    import os
    sdt = jnp.bfloat16 if os.environ.get("REPRO_ATTN_SCORES_BF16") == "1" \
        else jnp.float32
    G = H // KV
    qg = q.reshape(B, 1, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                   preferred_element_type=sdt) * scale
    s = s.astype(jnp.float32) \
        + jnp.where(mask[:, None, None, None, :], 0.0, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", p, v)
    return out.reshape(B, 1, H, hd)


def paged_attention_quant_ref(q, kp, vp, ks, vs, pt, pos, *, window=0,
                              scale=None):
    """Quantized paged decode attention: dequantize while gathering.

    q: (B,1,H,hd) fp queries; kp/vp: (P,ps,KV,hd) int8 (or fp8) code
    pools; ks/vs: (P,KV) float32 per-page per-kv-head scales with
    ``fp ~= code * scale``; pt: (B,nblk) block table; pos: (B,) ->
    (B,1,H,hd).

    The CPU reference path of ``kernels.ops``: same gather /
    mask / softmax structure as ``paged_attention_ref`` with the
    dequantization folded into the gather (codes -> f32 times the
    per-row page scale).  It matches the fused Pallas kernel to f32
    round-off (a single softmax vs the kernel's online rescaling); the
    bit-exact mirror of the kernel is
    :func:`paged_attention_quant_cell_ref`.
    """
    B, _, H, hd = q.shape
    P, ps, KV, _ = kp.shape
    nblk = pt.shape[1]
    S = nblk * ps
    if scale is None:
        scale = hd ** -0.5
    ptc = pt.astype(jnp.int32)
    rows = (ptc[:, :, None] * ps
            + jnp.arange(ps)[None, None, :]).reshape(B, S)   # (B, S)
    # per-row scales: every row of logical block j carries block j's
    # page scale -> (B, S, KV)
    sk = jnp.repeat(jnp.take(ks, ptc, axis=0), ps, axis=1)
    sv = jnp.repeat(jnp.take(vs, ptc, axis=0), ps, axis=1)
    k = jnp.take(kp.reshape(P * ps, KV, hd), rows,
                 axis=0).astype(jnp.float32) * sk[..., None]
    v = jnp.take(vp.reshape(P * ps, KV, hd), rows,
                 axis=0).astype(jnp.float32) * sv[..., None]
    slots = jnp.arange(S)[None, :]                           # (1, S)
    mask = slots <= pos[:, None]
    if window:
        mask &= slots > pos[:, None] - window
    G = H // KV
    qg = q.reshape(B, 1, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = s + jnp.where(mask[:, None, None, None, :], 0.0, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", p, v)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def paged_attention_quant_cell_ref(q, kp, vp, ks, vs, pt, pos, *, window=0,
                                   scale=None):
    """Bit-exact oracle for the fused-dequant Pallas kernel.

    Same signature as :func:`paged_attention_quant_ref`, but mirrors
    ``paged_attention._kernel`` *exactly*, cell by cell: per request an
    online-softmax sweep over logical blocks, and per block one
    ``(Gp, hd) x (ps, hd)^T`` score matmul per kv head over its G query
    heads (zero-padded to Gp = 8k rows), same op structure and f32
    intermediate order.  The per-cell structure is load-bearing for the
    bit-identity test in tests/test_quant.py: XLA's CPU backend picks
    reduction strategies by operand *shape*, so any batched (vmapped /
    einsum) formulation of the same math accumulates in a different
    order than the kernel's per-cell dots and drifts by a few ulps.  The
    unrolled graph compiles slowly (seconds to tens of seconds) — test
    oracle only, never dispatched by ``kernels.ops``.
    """
    B, _, H, hd = q.shape
    P, ps, KV, _ = kp.shape
    nblk = pt.shape[1]
    G = H // KV
    Gp = -(-G // 8) * 8
    if scale is None:
        scale = hd ** -0.5
    ptc = pt.astype(jnp.int32)
    posc = pos.astype(jnp.int32)
    lanes = jnp.arange(ps, dtype=jnp.int32)[None, :]      # (1, ps)
    qg = jnp.pad(q[:, 0].astype(jnp.float32).reshape(B, KV, G, hd),
                 ((0, 0), (0, 0), (0, Gp - G), (0, 0)))

    def request(b):
        m = [jnp.full((Gp, 1), -1e30, jnp.float32)] * KV
        l = [jnp.zeros((Gp, 1), jnp.float32)] * KV
        acc = [jnp.zeros((Gp, hd), jnp.float32)] * KV
        for i in range(nblk):
            page = ptc[b, i]
            kpos = i * ps + lanes
            mask = kpos <= posc[b]
            if window:
                mask &= kpos > posc[b] - window
            for g in range(KV):
                k = kp[page, :, g, :].astype(jnp.float32) * ks[page, g]
                v = vp[page, :, g, :].astype(jnp.float32) * vs[page, g]
                s = jax.lax.dot_general(
                    qg[b, g], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(mask, s, -1e30)
                m_new = jnp.maximum(m[g], jnp.max(s, axis=-1,
                                                  keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m[g] - m_new)
                l[g] = l[g] * alpha + jnp.sum(p, axis=-1, keepdims=True)
                acc[g] = acc[g] * alpha + jnp.dot(
                    p, v, preferred_element_type=jnp.float32)
                m[g] = m_new
        return jnp.stack([acc[g] / jnp.maximum(l[g], 1e-30)
                          for g in range(KV)])            # (KV, Gp, hd)

    out = jnp.stack([request(b) for b in range(B)])[:, :, :G]
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def rwkv6_scan_ref(r, k, v, w, u, state):
    """Sequential WKV6 recurrence.

    r,k,v,w: (B,T,H,hd); u: (H,hd); state: (B,H,hd,hd) fp32.
    Returns (out (B,T,H,hd) fp32, final state).
    """
    rf, kf, vf, wf = (a.astype(jnp.float32) for a in (r, k, v, w))
    uf = u.astype(jnp.float32)

    def step(S, inp):
        rt, kt, vt, wt = inp
        kv = kt[..., :, None] * vt[..., None, :]
        out = jnp.einsum("bhk,bhkn->bhn", rt, S + uf[None, :, :, None] * kv)
        S = wt[..., :, None] * S + kv
        return S, out

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (rf, kf, vf, wf))
    S, outs = jax.lax.scan(step, state.astype(jnp.float32), xs)
    return jnp.moveaxis(outs, 0, 1), S
