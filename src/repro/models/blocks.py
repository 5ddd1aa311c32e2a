"""Unified decoder/encoder block: pre-norm temporal part + (MoE-)FFN.

Block kinds (cfg.layer_pattern entries):
  full       — causal full self-attention
  local      — sliding-window self-attention (ring cache)
  cross      — self-attention + cross-attention to a source sequence
  recurrent  — RG-LRU temporal block (hybrid family)
  rwkv       — RWKV6 time-mix/channel-mix (ssm family; FFN = channel-mix)
  enc        — bidirectional self-attention (encoder stacks)

Every ``block_apply`` returns ``(x, new_cache, aux)`` where aux is the MoE
load-balance loss contribution (0 otherwise).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import lru, moe, rwkv
from repro.models.common import ffn_apply, ffn_specs, norm_spec, rms_norm


def block_specs(cfg, kind: str):
    if kind == "rwkv":
        return rwkv.rwkv_block_specs(cfg)
    d = cfg.d_model
    s = {"ln1": norm_spec(d), "ln2": norm_spec(d)}
    if kind == "recurrent":
        s["rec"] = lru.recurrent_specs(cfg)
    else:
        s["attn"] = attn.attn_specs(cfg)
    if kind == "cross":
        s["lnx"] = norm_spec(d)
        s["xattn"] = attn.cross_attn_specs(cfg)
    if cfg.num_experts:
        s["ffn"] = moe.moe_specs(cfg)
    else:
        s["ffn"] = ffn_specs(d, cfg.d_ff)
    return s


def init_block_cache(cfg, kind: str, batch: int, max_seq: int, *,
                     pages: int = 0, page_size: int = 0, kv_dtype=None):
    """Zeroed decode cache for one block.

    ``pages > 0`` selects the paged layout for attention KV: page pools
    shared by all slots instead of per-slot dense rows.  Recurrent/RWKV
    state and cross-attention KV stay dense per slot (O(1)/write-once).
    ``kv_dtype`` picks the page-pool storage format (int8/fp8 modes add
    per-page scale tensors; see ``attn.init_paged_self_cache``).
    """
    if kind == "rwkv":
        return rwkv.init_rwkv_state(cfg, batch)
    if kind == "recurrent":
        return lru.init_lru_state(cfg, batch)
    if pages:
        c = attn.init_paged_self_cache(cfg, pages, page_size,
                                       kv_dtype=kv_dtype)
    else:
        c = attn.init_self_cache(cfg, kind, batch, max_seq)
    if kind == "cross":
        src = cfg.encoder_seq or cfg.cross_source_seq
        z = jnp.zeros((batch, src, cfg.num_kv_heads, cfg.head_dim),
                      jnp.dtype(cfg.dtype))
        c["ck"], c["cv"] = z, z
    return c


def _freeze(live, new, old):
    """Per-request state freeze: keep old state where live==False."""
    if live is None:
        return new
    def sel(n, o):
        mask = live.reshape((live.shape[0],) + (1,) * (n.ndim - 1))
        return jnp.where(mask, n, o.astype(n.dtype))
    return jax.tree.map(sel, new, old)


def block_apply(cfg, kind: str, p, x, *, mode: str, positions,
                cache=None, source=None, max_seq: int = 0,
                window_override: int = 0, live=None, pt=None, ctx: int = 0):
    if mode == "extend" and kind not in ("full", "local"):
        # recurrent state advances token by token; a block append is
        # defined for position-addressed attention K/V only
        raise NotImplementedError(f"extend mode on a {kind!r} block")
    if kind == "rwkv":
        state = cache if cache is not None else rwkv.init_rwkv_state(
            cfg, x.shape[0])
        y, new_state = rwkv.rwkv_block(cfg, p, x, state, mode)
        if mode == "train":
            new_state = None
        elif mode == "decode":
            new_state = _freeze(live, new_state, state)
        return y, new_state, 0.0

    aux = 0.0
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind == "recurrent":
        state = cache if cache is not None else lru.init_lru_state(
            cfg, x.shape[0])
        y, new_cache = lru.recurrent_block(cfg, p["rec"], h, state, mode)
        if mode == "train":
            new_cache = None
        elif mode == "decode":
            new_cache = _freeze(live, new_cache, state)
    else:
        self_cache = None
        if cache is not None:
            self_cache = {k: cache[k]
                          for k in ("k", "v", "kp", "vp", "ks", "vs")
                          if k in cache}
        y, new_cache = attn.self_attention(
            cfg, p["attn"], h, kind=("full" if kind in ("cross", "enc")
                                     else kind),
            mode=mode, positions=positions, cache=self_cache,
            window_override=window_override, max_seq=max_seq,
            causal=(kind != "enc"), pt=pt, live=live, ctx=ctx)
    x = x + y

    if kind == "cross":
        h = rms_norm(x, p["lnx"], cfg.norm_eps)
        if mode == "decode":
            ckv = {"ck": cache["ck"], "cv": cache["cv"]}
        else:
            ckv = attn.compute_cross_kv(cfg, p["xattn"], source)
        y = attn.cross_attention(cfg, p["xattn"], h, ckv)
        x = x + y
        if new_cache is not None:
            new_cache = dict(new_cache)
            new_cache.update(ckv)

    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.num_experts:
        y, aux = moe.moe_ffn(cfg, p["ffn"], h)
    else:
        y = ffn_apply(p["ffn"], h, d_ff=cfg.d_ff)
    return x + y, new_cache, aux
