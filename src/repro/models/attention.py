"""GQA attention: full / sliding-window (ring-buffer cache) / cross.

Four execution modes per block:
  * ``train``   — full-sequence causal attention, no cache.
  * ``prefill`` — same math, additionally returns the populated KV cache.
  * ``decode``  — single-token query against the cache (per-request positions).
  * ``extend``  — a (B, W) block of tokens at per-token positions appended
    to an existing cache in one pass (serving's prompt prefill).

Local (sliding-window) layers keep a **ring buffer** cache of ``window``
entries, so long_500k decode stores O(window), not O(seq), per local layer.
Keys are cached rope-applied (absolute positions), the standard TPU idiom.

On TPU the train/prefill path dispatches to the Pallas flash-attention kernel
(``repro.kernels.ops.flash_attention``, see its platform dispatch); the
pure-jnp path here doubles as its oracle and as the CPU/dry-run
implementation.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed import tp as _tp
from repro.kernels import quant
from repro.models.common import ParamSpec, adtype, apply_rope, spec

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def attn_specs(cfg, *, cross: bool = False):
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s = {
        "wq": spec((d, H, hd), ("embed", "heads", "head")),
        "wk": spec((d, KV, hd), ("embed", "kv", "head")),
        "wv": spec((d, KV, hd), ("embed", "kv", "head")),
        "wo": spec((H, hd, d), ("heads", "head", "embed")),
    }
    return s


# ---------------------------------------------------------------------------
# Core attention math (pure jnp; GQA grouped einsum)
# ---------------------------------------------------------------------------

def _score_dtype():
    # §Perf H1 iter-2 knob: bf16 score buffers halve the S^2 HBM traffic of
    # the non-flash (XLA) attention path; fp32 stays the default.
    return jnp.bfloat16 if os.environ.get("REPRO_ATTN_SCORES_BF16") == "1" \
        else jnp.float32


def gqa_attention(q, k, v, mask, scale):
    """q: (B,Sq,H,hd) k/v: (B,Sk,KV,hd) mask: (B or 1, Sq, Sk) boolean."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                        preferred_element_type=_score_dtype()) * scale
    scores = scores.astype(jnp.float32) \
        + jnp.where(mask[:, None, None], 0.0, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(B, Sq, H, hd)


def causal_mask(sq: int, sk: int, q_offset=0, window: int = 0):
    """(1, sq, sk) boolean mask. window>0 = sliding window."""
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    m = kpos <= qpos
    if window:
        m &= kpos > qpos - window
    return m[None]


def _full_seq_attention(q, k, v, scale, window: int, causal: bool = True):
    from repro.kernels import ops
    if ops.use_kernels() and causal:
        return ops.flash_attention(q, k, v, causal=True, window=window,
                                   scale=scale)
    if causal:
        mask = causal_mask(q.shape[1], k.shape[1], window=window)
    else:
        mask = jnp.ones((1, q.shape[1], k.shape[1]), bool)
    return gqa_attention(q, k, v, mask, scale)


# ---------------------------------------------------------------------------
# Self-attention block
# ---------------------------------------------------------------------------

def init_self_cache(cfg, kind: str, batch: int, max_seq: int):
    """Zeroed cache pytree for one attention layer."""
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    size = _cache_len(cfg, kind, max_seq)
    z = jnp.zeros((batch, size, KV, hd), adtype(cfg))
    return {"k": z, "v": z}


def init_paged_self_cache(cfg, total_pages: int, page_size: int,
                          kv_dtype=None):
    """Paged cache for one attention layer: K/V page pools, no batch dim.

    Positions are stored *absolutely* (page of position p = block table
    entry ``p // page_size``) for every layer kind; sliding-window layers
    trade the dense ring buffer's O(window) rows for page-table sharing
    and get their locality back through the decode mask instead.

    ``kv_dtype`` selects the pool storage format (see
    :mod:`repro.kernels.quant`): ``None`` keeps the activation dtype,
    ``"bf16"`` is a plain half-width cast, and ``"int8"`` / ``"fp8"``
    store codes plus per-page per-kv-head float32 scale tensors
    (``ks``/``vs``, shaped ``(P, KV)``) that ride next to the pools in
    the cache pytree and through COW branching with them.
    """
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    dt = quant.pool_dtype(kv_dtype, adtype(cfg))
    z = jnp.zeros((total_pages, page_size, KV, hd), dt)
    out = {"kp": z, "vp": z}
    if quant.is_quantized(kv_dtype):
        sc = jnp.zeros((total_pages, KV), jnp.float32)
        out["ks"], out["vs"] = sc, sc
    return out


def _cache_len(cfg, kind: str, max_seq: int) -> int:
    if kind == "local" or (cfg.serve_window_override and kind in ("full", "cross")):
        w = cfg.window_size if kind == "local" else cfg.serve_window_override
        return min(w, max_seq)
    return max_seq


def self_attention(cfg, p, x, *, kind: str, mode: str,
                   positions, cache=None, window_override: int = 0,
                   max_seq: int = 0, causal: bool = True, pt=None,
                   live=None, ctx: int = 0):
    """Returns (out, new_cache).

    positions: (S,) for train/prefill (shared across batch); (B,) for
    decode; (B, W) for extend, where ``live`` (B, W) marks the tokens to
    append and ``ctx`` bounds the positions the block can reach.
    ``pt`` (B, nblk) selects the paged path when ``cache`` holds page
    pools ({'kp','vp'}) instead of per-slot dense rows ({'k','v'}).
    """
    B = x.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    scale = hd ** -0.5
    window = cfg.window_size if kind == "local" else 0
    if window_override:
        window = window_override if window == 0 else min(window, window_override)

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))

    if mode in ("train", "prefill"):
        pos = positions[None, :]  # (1,S)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
        out = _full_seq_attention(q, k, v, scale, window, causal=causal)
        new_cache = None
        if mode == "prefill":
            new_cache = _fill_cache(cfg, kind, k, v, positions,
                                    max_seq or k.shape[1])
    elif mode == "extend":  # x is (B,W,d), positions (B,W)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        out, new_cache = _extend_attention(cache, q, k, v, positions, live,
                                           pt, ctx, window, scale)
    else:  # decode: x is (B,1,d), positions (B,)
        pos_b = positions[:, None]  # (B,1)
        q = apply_rope(q, pos_b, cfg.rope_theta)
        k = apply_rope(k, pos_b, cfg.rope_theta)
        if pt is not None and "kp" in cache:
            from repro.kernels import ops
            if "ks" in cache:
                new_cache = _write_cache_paged_quant(cache, k, v,
                                                     positions, pt)
                out = ops.paged_attention_quant(
                    q, new_cache["kp"], new_cache["vp"], new_cache["ks"],
                    new_cache["vs"], pt, positions, window=window,
                    scale=scale)
            else:
                new_cache = _write_cache_paged(cache, k, v, pos_b, pt)
                out = ops.paged_attention(q, new_cache["kp"],
                                          new_cache["vp"], pt, positions,
                                          window=window, scale=scale)
        else:
            new_cache = _write_cache(cache, k, v, positions)
            mask = _decode_mask(new_cache["k"].shape[1], positions,
                                ring=(window > 0))  # (B,1,Sk)
            out = gqa_attention(q, new_cache["k"], new_cache["v"], mask,
                                scale)

    # Tensor-parallel output projection: when this trace holds a head
    # shard (wq gave us H/tp query heads), all-gather BOTH the per-head
    # attention outputs and wo's head dim, then run the full einsum —
    # exact concatenation followed by the identical contraction, so the
    # result is bitwise-equal to unsharded (a psum over partial wo
    # products would reorder float additions and is not).  Everything
    # above is per-head math on exact head shards: q/k/v projections
    # contract over the replicated d_model dim, rope / softmax / paged
    # gathers are head-independent, and the KV cache leaves are sharded
    # along the same kv-head axis the shard computes.
    wo = p["wo"]
    ax = _tp.axis()
    if ax is not None and out.shape[2] != H:
        out = jax.lax.all_gather(out, ax, axis=2, tiled=True)
        wo = jax.lax.all_gather(wo, ax, axis=0, tiled=True)
    y = jnp.einsum("bshk,hkd->bsd", out, wo.astype(x.dtype))
    return y, new_cache


def _fill_cache(cfg, kind, k, v, positions, max_seq):
    """Build the capacity-sized cache from prefill keys/values (rope'd)."""
    S = k.shape[1]
    size = _cache_len(cfg, kind, max_seq=max_seq)
    if size > S:  # pad to capacity; decode continues writing at pos >= S
        pad = [(0, 0), (0, size - S), (0, 0), (0, 0)]
        return {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
    if size == S:
        return {"k": k, "v": v}
    # ring buffer: slot j holds the latest position p with p % size == j;
    # prefill positions are arange(S) so cache index == position index.
    start = S - size
    idx = start + (jnp.arange(size) - start) % size
    return {"k": jnp.take(k, idx, axis=1), "v": jnp.take(v, idx, axis=1)}


def _write_cache(cache, k, v, positions):
    """Write the new (B,1,KV,hd) kv at per-request positions (ring aware)."""
    size = cache["k"].shape[1]
    slots = positions % size

    def upd(c, new, s):
        return jax.lax.dynamic_update_slice(c, new, (s, 0, 0))

    k_new = jax.vmap(upd)(cache["k"], k, slots)
    v_new = jax.vmap(upd)(cache["v"], v, slots)
    return {"k": k_new, "v": v_new}


def _write_cache_paged(cache, k, v, positions, pt, live=None):
    """Write the new (B,W,KV,hd) kv at positions (B,W) through the block
    table; decode writes W=1.

    Physical row of position p for request b is
    ``pt[b, p // ps] * ps + p % ps``.  Rows that are done (or never
    admitted) resolve to scratch/trash pages the host allocator set up, so
    the unconditional write stays harmless exactly as in the dense path.
    ``live`` (B,W), if given, marks the tokens to write; the others are
    dropped.
    """
    kp, vp = cache["kp"], cache["vp"]
    P, ps = kp.shape[0], kp.shape[1]
    blk = jnp.minimum(positions // ps, pt.shape[1] - 1)
    rows = jnp.take_along_axis(pt, blk, axis=1) * ps + positions % ps
    if live is not None:
        rows = jnp.where(live, rows, P * ps)               # out of range
    rows = rows.reshape(-1)                                # (B*W,)

    def upd(pool, new):
        flat = pool.reshape((P * ps,) + pool.shape[2:])
        new = new.reshape((-1,) + pool.shape[2:]).astype(pool.dtype)
        return flat.at[rows].set(new, mode="drop").reshape(pool.shape)

    out = dict(cache)
    out["kp"], out["vp"] = upd(kp, k), upd(vp, v)
    return out


def _write_cache_paged_quant(cache, k, v, positions, pt):
    """Quantized paged write: re-quantize the touched page whole.

    Each request's new (KV,hd) key/value lands in page
    ``pt[b, pos // ps]`` at row ``pos % ps``.  The page is read back,
    dequantized with its current scale, the new token's row inserted,
    rows *beyond* the write row zeroed (they are stale garbage from a
    previous occupant of the physical page and must not inflate the
    amax), and the page re-quantized against a fresh per-kv-head scale
    ``amax / QMAX``.  Re-quantization is exact for already-written rows
    whenever the scale is unchanged (``round(code) == code``), and the
    scale of a page only grows as rows fill in, so accumulated
    round-trip error stays bounded by one quantization step.

    The page-granularity scatter is race-free for the same reason the
    dense row scatter is: a slot's tail page is exclusively owned
    (published prefix pages are read-only by construction — writes only
    ever target positions past the matched prefix), branch writes land
    in per-branch scratch pages, and duplicate page indices only occur
    for the shared trash page whose content is garbage by design.
    """
    ps = cache["kp"].shape[1]
    dt = cache["kp"].dtype
    qmax = quant.QMAX["int8"] if dt == jnp.int8 else quant.QMAX["fp8"]
    blk = jnp.minimum(positions // ps, pt.shape[1] - 1)
    page = jnp.take_along_axis(pt, blk[:, None], axis=1)[:, 0]  # (B,)
    row = positions % ps                                        # (B,)
    lane = jnp.arange(ps)[None, :]                              # (1, ps)
    at_row = (lane == row[:, None])[:, :, None, None]
    valid = (lane <= row[:, None])[:, :, None, None]

    def upd(pool, sc, new):
        fp = pool[page].astype(jnp.float32) * sc[page][:, None, :, None]
        tok = new[:, 0].astype(jnp.float32)[:, None]            # (B,1,KV,hd)
        fp = jnp.where(at_row, tok, fp)
        fp = jnp.where(valid, fp, 0.0)
        amax = jnp.max(jnp.abs(fp), axis=(1, 3))                # (B, KV)
        nsc = jnp.maximum(amax, quant.EPS) / qmax
        codes = quant.quantize_codes(fp / nsc[:, None, :, None], dt)
        return pool.at[page].set(codes), sc.at[page].set(nsc)

    out = dict(cache)
    out["kp"], out["ks"] = upd(cache["kp"], cache["ks"], k)
    out["vp"], out["vs"] = upd(cache["vp"], cache["vs"], v)
    return out


def _extend_attention(cache, q, k, v, positions, live, pt, ctx, window,
                      scale):
    """Append a block of tokens to the cache and attend causally, in one
    pass over the whole block.

    q/k/v: (B, W, ., hd) rope'd; positions (B, W) absolute, strictly
    increasing over each row's ``live`` tokens, which are the only ones
    written.  Each row's context is laid out by absolute position over
    ``[0, ctx)``: what the cache held below the row's first position
    (pages ``pt[b, :ctx // ps]``, or the dense row, a ring for window
    layers) with the block's own keys put in at their positions, in the
    pool's storage dtype, so every query sees the rows a token-serial
    append would have written before it.  A ring row holds the last
    ``size <= window`` positions, so the window mask alone hides the
    positions it no longer holds.  Returns (out, new_cache).
    """
    B, W = positions.shape
    bidx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, W))
    if pt is not None and "kp" in cache:
        nb = ctx // cache["kp"].shape[1]

        def view(pool):
            return pool[pt[:, :nb]].reshape((B, ctx) + pool.shape[2:])
        keys = ("kp", "vp")
        out = _write_cache_paged(cache, k, v, positions, pt, live)
    else:
        size = cache["k"].shape[1]
        # a ring row (window layers) holds the last ``size`` positions;
        # of the block only those survive, so no two writes share a slot
        end = positions[:, :1] + jnp.sum(live, axis=1, keepdims=True)
        slots = jnp.where(live & (positions >= end - size),
                          positions % size, size)

        def view(row):
            return jnp.take(row, jnp.arange(ctx) % size, axis=1)
        def write(row, new):
            return row.at[bidx, slots].set(new.astype(row.dtype),
                                           mode="drop")
        keys = ("k", "v")
        out = dict(cache, k=write(cache["k"], k), v=write(cache["v"], v))
    at = jnp.where(live, positions, ctx)
    kv = []
    for key, new in zip(keys, (k, v)):
        ctx_rows = view(cache[key])
        kv.append(ctx_rows.at[bidx, at].set(new.astype(ctx_rows.dtype),
                                            mode="drop"))
    kpos = jnp.arange(ctx)[None, None, :]
    qpos = positions[:, :, None]
    mask = kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    return gqa_attention(q, kv[0], kv[1], mask, scale), out


def _decode_mask(sk: int, positions, *, ring: bool):
    """(B,1,Sk) validity mask for decode against a (ring) cache."""
    slots = jnp.arange(sk)[None]           # (1,Sk)
    pos = positions[:, None]               # (B,1)
    if not ring:
        return (slots <= pos)[:, None]
    # ring: slot j valid iff some p in (pos-size, pos] has p%size==j and p>=0
    filled = (slots <= pos) | (pos >= sk)
    return filled[:, None]


# ---------------------------------------------------------------------------
# Cross-attention (vlm / enc-dec): kv from a source sequence, cached once
# ---------------------------------------------------------------------------

def cross_attn_specs(cfg):
    return attn_specs(cfg)


def compute_cross_kv(cfg, p, source):
    """source: (B, S_src, d) -> cached cross kv (no rope)."""
    k = jnp.einsum("bsd,dhk->bshk", source, p["wk"].astype(source.dtype))
    v = jnp.einsum("bsd,dhk->bshk", source, p["wv"].astype(source.dtype))
    return {"ck": k, "cv": v}


def cross_attention(cfg, p, x, cross_kv):
    hd = cfg.head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    B, Sq = q.shape[:2]
    Sk = cross_kv["ck"].shape[1]
    mask = jnp.ones((1, Sq, Sk), bool)
    out = gqa_attention(q, cross_kv["ck"], cross_kv["cv"], mask, hd ** -0.5)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
