"""RWKV-6 "Finch" blocks: time-mix with data-dependent decay + channel-mix.

Faithful to arXiv:2404.05892 §3 (DDLerp token shift, LoRA decay, per-head
matrix-valued state) with two documented simplifications (DESIGN.md §6):
RMSNorm instead of LayerNorm, and a shared 32-dim LoRA rank for the five
token-shift mixes.

State per layer (decode): time-mix shift x_prev (B,d), WKV state (B,H,hd,hd),
channel-mix shift (B,d).  Training/prefill uses a sequence scan (the Pallas
``rwkv6_scan`` kernel implements the chunked TPU variant; this file is the
oracle semantics).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.models.common import adtype, rms_norm, spec

LORA_RANK = 32
DECAY_RANK = 64


def timemix_specs(cfg):
    d = cfg.d_model
    H, hd = cfg.num_heads, cfg.rwkv_head_dim
    return {
        "mu_x": spec((d,), ("embed",), "zeros"),
        "mu_5": spec((5, d), (None, "embed"), "zeros"),
        "tm_w1": spec((d, 5 * LORA_RANK), ("embed", None), scale=0.1),
        "tm_w2": spec((5, LORA_RANK, d), (None, None, "embed"), scale=0.1),
        "decay_base": spec((d,), ("embed",), "uniform_decay"),
        "decay_w1": spec((d, DECAY_RANK), ("embed", None), scale=0.1),
        "decay_w2": spec((DECAY_RANK, d), (None, "embed"), scale=0.1),
        "bonus_u": spec((H, hd), ("heads", "head"), scale=0.5),
        "wr": spec((d, d), ("embed", "heads_flat")),
        "wk": spec((d, d), ("embed", "heads_flat")),
        "wv": spec((d, d), ("embed", "heads_flat")),
        "wg": spec((d, d), ("embed", "heads_flat")),
        "wo": spec((d, d), ("heads_flat", "embed")),
        "ln_x": spec((d,), ("embed",), "zeros"),
    }


def channelmix_specs(cfg):
    d, ff = cfg.d_model, cfg.d_ff
    return {
        "mu_k": spec((d,), ("embed",), "zeros"),
        "mu_r": spec((d,), ("embed",), "zeros"),
        "wk": spec((d, ff), ("embed", "mlp")),
        "wv": spec((ff, d), ("mlp", "embed")),
        "wr": spec((d, d), ("embed", "embed_out")),
    }


def init_rwkv_state(cfg, batch: int):
    d = cfg.d_model
    H, hd = cfg.num_heads, cfg.rwkv_head_dim
    f32 = jnp.float32
    return {
        "tm_prev": jnp.zeros((batch, d), adtype(cfg)),
        "wkv": jnp.zeros((batch, H, hd, hd), f32),
        "cm_prev": jnp.zeros((batch, d), adtype(cfg)),
    }


def _ddlerp(p, x, sx):
    """Data-dependent token-shift mixes for (w,k,v,r,g).

    x, sx: (B,T,d) with sx = x_prev - x.  Returns 5 tensors (B,T,d).
    """
    base = x + sx * p["mu_x"]
    lo = jnp.tanh(base @ p["tm_w1"])            # (B,T,5*R)
    B, T = x.shape[:2]
    lo = lo.reshape(B, T, 5, LORA_RANK)
    delta = jnp.einsum("btfr,frd->btfd", lo, p["tm_w2"])  # (B,T,5,d)
    mixes = p["mu_5"][None, None] + delta
    out = x[:, :, None] + sx[:, :, None] * mixes
    return [out[:, :, i] for i in range(5)]


def _decay(p, xw):
    """Data-dependent per-channel decay w_t in (0,1).  xw: (B,T,d)."""
    lora = jnp.tanh(xw @ p["decay_w1"]) @ p["decay_w2"]
    log_w = -jnp.exp(
        jnp.clip((p["decay_base"] + lora).astype(jnp.float32), -8.0, 4.0))
    return jnp.exp(log_w)  # in (0,1)


def _wkv_scan(r, k, v, w, u, state):
    """Sequential WKV recurrence (oracle semantics).

    r,k,v: (B,T,H,hd); w: (B,T,H,hd) decays; u: (H,hd); state: (B,H,hd,hd).
    out_t = r_t . (S_{t-1} + u*k_t (x) v_t);  S_t = diag(w_t) S_{t-1} + k_t (x) v_t
    """
    rf, kf, vf, wf = (a.astype(jnp.float32) for a in (r, k, v, w))

    def step(S, inp):
        rt, kt, vt, wt = inp  # (B,H,hd)
        kv = kt[..., :, None] * vt[..., None, :]          # (B,H,hd,hd)
        out = jnp.einsum("bhk,bhkn->bhn", rt,
                         S + u[None, :, :, None] * kv)
        S_new = wt[..., :, None] * S + kv
        return S_new, out

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (rf, kf, vf, wf))
    state_new, outs = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(outs, 0, 1), state_new  # (B,T,H,hd)


def _use_chunked() -> bool:
    """Chunked-parallel WKV (matmul form) — used by the dry-run lowering.

    The sequential scan is exact but compiles one while-loop per layer with
    T iterations (pathological for the unrolled 512-device dry-run compile,
    and invisible to XLA's cost analysis).  The chunked form computes the
    same recurrence as NC unrolled blocks of within-chunk quadratic
    attention + cross-chunk state propagation — matching the Pallas
    kernel's blocking, with FLOPs ~1.5-2x the true linear cost (recorded in
    EXPERIMENTS §Roofline).  Numerics note: the factored within-chunk decay
    exp(L_t - L_s) can underflow for adversarial decays; the exact
    sequential path stays the default for execution and the Pallas kernel
    (sequential inner loop in VMEM) for TPU production.
    """
    return os.environ.get("REPRO_RWKV_CHUNKED", "0") == "1"


def _wkv_chunked(r, k, v, w, u, state, chunk: int = 256):
    """Chunked-parallel WKV6: same recurrence as _wkv_scan, in matmul form.

    r,k,v,w: (B,T,H,hd); u: (H,hd); state: (B,H,hd,hd) fp32.
    """
    B, T, H, hd = r.shape
    C = min(chunk, T)
    pad = (-T) % C
    if pad:
        z = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v = z(r), z(k), z(v)
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0), (0, 0)),
                    constant_values=1.0)
    NC = (T + pad) // C

    def cshape(a):  # (B,T,H,hd) -> (B,NC,C,H,hd) in fp32
        return a.astype(jnp.float32).reshape(B, NC, C, H, hd)

    rc, kc, vc, wc = cshape(r), cshape(k), cshape(v), cshape(w)
    logw = jnp.log(jnp.clip(wc, 1e-38))
    L = jnp.cumsum(logw, axis=2)                    # inclusive within chunk
    Lprev = L - logw                                # exclusive (L_{t-1})
    uf = u.astype(jnp.float32)

    S = state.astype(jnp.float32)
    outs = []
    for c in range(NC):                             # unrolled chunk blocks
        rcc, kcc, vcc = rc[:, c], kc[:, c], vc[:, c]
        Lc, Lp = L[:, c], Lprev[:, c]
        # intra-chunk: A[t,s] = sum_c r_t k_s exp(Lp_t - L_s), s < t
        P = rcc * jnp.exp(Lp)                       # (B,C,H,hd)
        Q = kcc * jnp.exp(-Lc)
        A = jnp.einsum("bthc,bshc->bhts", P, Q)
        tri = jnp.tril(jnp.ones((C, C), bool), -1)
        A = jnp.where(tri[None, None], A, 0.0)
        intra = jnp.einsum("bhts,bshj->bthj", A, vcc)
        # diagonal (bonus u) term
        diag = jnp.einsum("bthc,bthc->bth", rcc * uf[None, None], kcc)
        intra = intra + diag[..., None] * vcc
        # inter-chunk: r_t . diag(exp(Lp_t)) S_in
        inter = jnp.einsum("bthc,bhcj->bthj", P, S)
        outs.append(intra + inter)
        # state update: S = diag(exp(L_last)) S + sum_s diag(exp(L_last-L_s)) kv_s
        Llast = Lc[:, -1]                           # (B,H,hd)
        K2 = kcc * jnp.exp(Llast[:, None] - Lc)
        S = jnp.exp(Llast)[..., None] * S + jnp.einsum(
            "bshc,bshj->bhcj", K2, vcc)
    out = jnp.concatenate(outs, axis=1)[:, :T]
    return out, S


def time_mix(cfg, p, x, state, mode: str):
    """x: (B,T,d) (T=1 for decode). Returns (y, new_state)."""
    B, T, d = x.shape
    H, hd = cfg.num_heads, cfg.rwkv_head_dim

    prev = state["tm_prev"]  # (B,d)
    x_shift = jnp.concatenate([prev[:, None], x[:, :-1]], axis=1)
    sx = x_shift - x
    xw, xk, xv, xr, xg = _ddlerp(p, x, sx)

    r = (xr @ p["wr"]).reshape(B, T, H, hd)
    k = (xk @ p["wk"]).reshape(B, T, H, hd)
    v = (xv @ p["wv"]).reshape(B, T, H, hd)
    g = jax.nn.silu(xg @ p["wg"])
    w = _decay(p, xw).reshape(B, T, H, hd)
    u = p["bonus_u"].astype(jnp.float32)

    from repro.kernels import ops
    if ops.use_kernels() and T > 1:
        out, S = ops.rwkv6_scan(r, k, v, w, u, state["wkv"])
    elif _use_chunked() and T > 1:
        out, S = _wkv_chunked(r, k, v, w, u, state["wkv"],
                              chunk=int(os.environ.get("REPRO_RWKV_CHUNK",
                                                       "256")))
    else:
        out, S = _wkv_scan(r, k, v, w, u, state["wkv"])

    # per-head group norm
    out = out.reshape(B, T, H, hd)
    mean2 = jnp.mean(out * out, axis=-1, keepdims=True)
    out = out * jax.lax.rsqrt(mean2 + cfg.norm_eps)
    out = out.reshape(B, T, d).astype(x.dtype)
    out = out * (1.0 + p["ln_x"]) * g
    y = out @ p["wo"]

    new_state = dict(state)
    new_state["tm_prev"] = x[:, -1]
    new_state["wkv"] = S
    return y, new_state


def channel_mix(cfg, p, x, state, mode: str):
    prev = state["cm_prev"]
    x_shift = jnp.concatenate([prev[:, None], x[:, :-1]], axis=1)
    sx = x_shift - x
    xk = x + sx * p["mu_k"]
    xr = x + sx * p["mu_r"]
    k = jnp.square(jax.nn.relu(xk @ p["wk"]))
    y = jax.nn.sigmoid(xr @ p["wr"]) * (k @ p["wv"])
    new_state = dict(state)
    new_state["cm_prev"] = x[:, -1]
    return y, new_state


def rwkv_block_specs(cfg):
    return {
        "ln1": spec((cfg.d_model,), ("embed",), "zeros"),
        "tm": timemix_specs(cfg),
        "ln2": spec((cfg.d_model,), ("embed",), "zeros"),
        "cm": channelmix_specs(cfg),
    }


def rwkv_block(cfg, p, x, state, mode: str):
    h, state = time_mix(cfg, p["tm"], rms_norm(x, p["ln1"], cfg.norm_eps),
                        state, mode)
    x = x + h
    h, state = channel_mix(cfg, p["cm"], rms_norm(x, p["ln2"], cfg.norm_eps),
                           state, mode)
    return x + h, state
