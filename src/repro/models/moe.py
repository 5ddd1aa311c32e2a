"""Mixture-of-Experts FFN: top-k routing, shared experts, expert parallelism.

Distribution model (DESIGN.md §5): the routed experts are sharded over the
``model`` mesh axis (expert parallelism).  The layer runs under
``jax.shard_map`` so the dispatch is *local*: every device computes, for its
local token shard and its local expert shard, a capacity-bounded
gather -> grouped-matmul -> scatter, then ``psum`` over the ``model`` axis
combines each token's top-k expert outputs.  This avoids the O(T*E*C) GShard
one-hot dispatch tensor, which is infeasible at kimi-k2 scale.

Experts are padded to a multiple of the model-axis size (e.g. qwen2-moe's 60
routed experts are padded to 64); padding experts are masked out of the
router softmax.

Without a mesh (CPU unit tests) the same local math runs on the full arrays.
"""
from __future__ import annotations

import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.common import ParamSpec, spec
from repro.distributed import context as dctx


def padded_experts(cfg, model_axis: int = 16) -> int:
    m = max(model_axis, 1)
    return (cfg.num_experts + m - 1) // m * m


def moe_specs(cfg):
    d, ff = cfg.d_model, cfg.moe_d_ff
    e = padded_experts(cfg)
    s = {
        "router": spec((d, e), ("embed", "expert_in")),
        "we_gate": spec((e, d, ff), ("expert", "embed", "expert_mlp")),
        "we_up": spec((e, d, ff), ("expert", "embed", "expert_mlp")),
        "we_down": spec((e, ff, d), ("expert", "expert_mlp", "embed")),
    }
    if cfg.num_shared_experts:
        sff = cfg.num_shared_experts * cfg.moe_d_ff
        s["shared_gate"] = spec((d, sff), ("embed", "mlp"))
        s["shared_up"] = spec((d, sff), ("embed", "mlp"))
        s["shared_down"] = spec((sff, d), ("mlp", "embed"))
    return s


# ---------------------------------------------------------------------------
# Local (per-shard) expert computation
# ---------------------------------------------------------------------------

def _local_expert_ffn(cfg, p_local, x, top_w, top_e, e0, e_local, capacity):
    """x: (T,d); top_w/top_e: (T,k); experts [e0, e0+e_local) are local.

    Returns this shard's additive contribution (T,d) for its local experts.
    """
    T, d = x.shape
    k = top_e.shape[1]
    slots = T * k
    flat_e = top_e.reshape(slots) - e0                 # local expert index
    flat_w = top_w.reshape(slots)
    tok_of_slot = jnp.repeat(jnp.arange(T), k)
    valid = (flat_e >= 0) & (flat_e < e_local)
    bucket = jnp.where(valid, flat_e, e_local)         # drop bucket at end

    # rank of each slot within its expert bucket (stable counting sort)
    order = jnp.argsort(bucket, stable=True)           # (slots,)
    sorted_bucket = bucket[order]
    counts = jnp.bincount(bucket, length=e_local + 1)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(slots) - starts[sorted_bucket]   # rank among same expert

    keep = (sorted_bucket < e_local) & (rank < capacity)
    buf_pos = jnp.where(keep, sorted_bucket * capacity + rank,
                        e_local * capacity)            # overflow row
    src_tok = tok_of_slot[order]

    xbuf = jnp.zeros((e_local * capacity + 1, d), x.dtype)
    xbuf = xbuf.at[buf_pos].set(jnp.where(keep[:, None], x[src_tok], 0.0))
    xb = xbuf[:-1].reshape(e_local, capacity, d)

    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xb, p_local["we_gate"]))
    up = jnp.einsum("ecd,edf->ecf", xb, p_local["we_up"])
    h = jnp.einsum("ecf,efd->ecd", gate * up, p_local["we_down"])
    h = h.reshape(e_local * capacity, d)
    h = jnp.concatenate([h, jnp.zeros((1, d), h.dtype)], axis=0)

    contrib = h[buf_pos] * (flat_w[order] * keep)[:, None]
    out = jnp.zeros((T, d), x.dtype).at[src_tok].add(contrib)
    return out


def _route(cfg, router_w, x):
    """Router: softmax over real experts, top-k, renormalized weights."""
    e_pad = router_w.shape[1]
    logits = (x @ router_w).astype(jnp.float32)
    mask = jnp.arange(e_pad) < cfg.num_experts
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg.experts_per_token)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    # load-balance aux loss (Switch-style): E * sum_e f_e * p_e
    T = x.shape[0]
    me = jnp.mean(probs, axis=0)
    one_hot_load = jnp.zeros((T, e_pad)).at[
        jnp.arange(T)[:, None], top_e].add(1.0)
    fe = jnp.mean(one_hot_load, axis=0) / cfg.experts_per_token
    aux = cfg.num_experts * jnp.sum(fe * me)
    return top_w.astype(x.dtype), top_e, aux


def _shared_ffn(p, x):
    gate = jax.nn.silu(x @ p["shared_gate"])
    return (gate * (x @ p["shared_up"])) @ p["shared_down"]


def moe_ffn(cfg, p, x):
    """x: (B,S,d) -> (y, aux_loss).  shard_map EP when a mesh is active."""
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    mesh = dctx.get_mesh()
    e_pad = p["router"].shape[1]

    if mesh is None or "model" not in mesh.axis_names or mesh.size == 1:
        cap = _capacity(cfg, B * S, e_pad)
        top_w, top_e, aux = _route(cfg, p["router"].astype(xf.dtype), xf)
        y = _local_expert_ffn(cfg, p, xf, top_w, top_e, 0, e_pad, cap)
        if cfg.num_shared_experts:
            y = y + _shared_ffn(p, xf)
        return y.reshape(B, S, d), aux

    from jax.sharding import PartitionSpec as P
    tp = mesh.shape["model"]
    batch_axes = tuple(a for a in mesh.axis_names if a != "model")
    dp = math.prod(mesh.shape[a] for a in batch_axes)
    e_local = e_pad // tp

    # Two expert-parallel execution modes (EXPERIMENTS §Perf H2):
    #   gather — tokens sharded over data, experts over model; each device
    #            needs the FULL per-expert FFN weights (all-gathered over
    #            data when the params are EPxFSDP sharded).  Right for
    #            training/prefill (millions of tokens).
    #   repl   — tokens replicated, expert FFN dim sharded over data: no
    #            weight gathers at all, collectives are one activation psum.
    #            Right for decode, where T is tiny and the per-layer weight
    #            gather (GBs) dwarfs the compute.
    mode = os.environ.get("REPRO_MOE_MODE", "auto")
    if mode == "auto":
        mode = "repl" if (B * S) <= 2048 or (B * S) % dp != 0 else "gather"
    ff = p["we_gate"].shape[2]
    if mode == "repl" and (ff % dp != 0):
        mode = "gather"
    if mode == "gather" and (B * S) % dp != 0:
        batch_axes, dp = (), 1

    shared = None
    if cfg.num_shared_experts:
        shared = {"shared_gate": p["shared_gate"],
                  "shared_up": p["shared_up"],
                  "shared_down": p["shared_down"]}

    if mode == "repl":
        cap = _capacity(cfg, B * S, e_pad)
        psum_axes = tuple(batch_axes) + ("model",)

        def shard_fn(xl, router_w, wg, wu, wd, sh):
            # xl replicated; wg/wu: (E_local, d, ff_local); wd transposed
            top_w, top_e, aux = _route(cfg, router_w.astype(xl.dtype), xl)
            e0 = jax.lax.axis_index("model") * e_local
            p_local = {"we_gate": wg.astype(xl.dtype),
                       "we_up": wu.astype(xl.dtype),
                       "we_down": wd.astype(xl.dtype)}
            y = _local_expert_ffn(cfg, p_local, xl, top_w, top_e, e0,
                                  e_local, cap)
            if sh is not None:
                y = y + _shared_ffn(
                    {k: v.astype(xl.dtype) for k, v in sh.items()}, xl)
            return jax.lax.psum(y, psum_axes), \
                jax.lax.pmean(aux, psum_axes)

        data_ax = (batch_axes if len(batch_axes) > 1 else
                   (batch_axes[0] if batch_axes else None))
        y, aux = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(None, None), P(None, None),
                      P("model", None, data_ax), P("model", None, data_ax),
                      P("model", data_ax, None),
                      (None if shared is None else
                       {"shared_gate": P(None, ("model",) + batch_axes
                                         if batch_axes else "model"),
                        "shared_up": P(None, ("model",) + batch_axes
                                       if batch_axes else "model"),
                        "shared_down": P(("model",) + batch_axes
                                         if batch_axes else "model",
                                         None)})),
            out_specs=(P(None, None), P()), check_vma=False,
        )(xf, p["router"], p["we_gate"], p["we_up"], p["we_down"], shared)
        return y.reshape(B, S, d), jnp.mean(aux)

    t_local = (B * S) // dp
    cap = _capacity(cfg, t_local, e_pad)

    def shard_fn(xl, router_w, wg, wu, wd, sh):
        # xl: (T_local, d) (replicated over 'model'); w*: local expert shard
        top_w, top_e, aux = _route(cfg, router_w.astype(xl.dtype), xl)
        e0 = jax.lax.axis_index("model") * e_local
        p_local = {"we_gate": wg.astype(xl.dtype),
                   "we_up": wu.astype(xl.dtype),
                   "we_down": wd.astype(xl.dtype)}
        y = _local_expert_ffn(cfg, p_local, xl, top_w, top_e, e0, e_local,
                              cap)
        if sh is not None:
            y = y + _shared_ffn(
                {k: v.astype(xl.dtype) for k, v in sh.items()}, xl)
        y = jax.lax.psum(y, "model")
        aux = jax.lax.pmean(aux, "model")
        return y, aux

    if not batch_axes:
        tok_spec = P(None, None)
    else:
        tok_spec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0],
                     None)
    y, aux = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(tok_spec, P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None),
                  (None if shared is None else
                   {"shared_gate": P(None, "model"),
                    "shared_up": P(None, "model"),
                    "shared_down": P("model", None)})),
        out_specs=(tok_spec, P()), check_vma=False,
    )(xf, p["router"], p["we_gate"], p["we_up"], p["we_down"], shared)
    return y.reshape(B, S, d), jnp.mean(aux)


def _capacity(cfg, tokens_local: int, e_pad: int) -> int:
    # capacity per expert, w.r.t. the *real* expert count (padding experts
    # receive no traffic), rounded up to 8 for clean TPU tiling.
    c = int(math.ceil(tokens_local * cfg.experts_per_token / cfg.num_experts
                      * cfg.capacity_factor))
    return max(8, int(math.ceil(c / 8)) * 8)
