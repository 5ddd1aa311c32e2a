"""Chunked WKV6 recurrence Pallas kernel (RWKV-6 time-mix inner loop).

Recurrence per head (hd x hd fp32 state S):

    out_t = r_t . (S + u * k_t (x) v_t)
    S     = diag(w_t) S + k_t (x) v_t

TPU adaptation (DESIGN.md §3): the GPU CUDA kernel parallelizes over
(B,H) thread blocks with S in registers; here the grid is (B*H, T/C) with S
in VMEM scratch, r/k/v/w streamed chunk-by-chunk (one HBM round-trip per
chunk instead of per step).  The inner loop is sequential over the chunk —
the data-dependent per-channel decay makes the parallel "divide by cumprod
of decays" form numerically unsafe (w can reach e^-54 per step), matching
the fp32-state choice of the reference CUDA kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sT_ref,
            s_ref, *, chunk: int, num_chunks: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = s0_ref[0]

    u = u_ref[0].astype(jnp.float32).T           # (hd, 1)

    def step(t, _):
        row = pl.ds(t, 1)
        rt = r_ref[0, row, :].T                  # (hd, 1)
        kt = k_ref[0, row, :].T                  # (hd, 1)
        vt = v_ref[0, row, :]                    # (1, hd)
        wt = w_ref[0, row, :].T                  # (hd, 1)
        S = s_ref[...]                           # (hd, hd)
        kv = kt * vt
        out = jnp.sum((S + u * kv) * rt, axis=0, keepdims=True)
        o_ref[0, row, :] = out
        s_ref[...] = wt * S + kv
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)

    @pl.when(c == num_chunks - 1)
    def _finish():
        sT_ref[0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan_pallas(r, k, v, w, u, state, *, chunk: int = 64,
                      interpret: bool = False):
    """r,k,v,w: (B,T,H,hd); u: (H,hd); state: (B,H,hd,hd) fp32.

    Returns (out (B,T,H,hd) fp32, final state (B,H,hd,hd) fp32).
    """
    B, T, H, hd = r.shape
    chunk = min(chunk, T)
    Tp = (T + chunk - 1) // chunk * chunk

    def prep(a, pad_val=0.0):
        # f32 in HBM: the kernel loads one row per step at a dynamic
        # sublane offset, which packed (bf16) tiles do not allow
        a = jnp.moveaxis(a, 2, 1).reshape(B * H, T, hd).astype(jnp.float32)
        if Tp != T:
            a = jnp.pad(a, ((0, 0), (0, Tp - T), (0, 0)),
                        constant_values=pad_val)
        return a

    rr, kk, vv = prep(r), prep(k), prep(v)
    # pad with decay=1, k=0 -> state unchanged on padded steps
    ww = prep(w, 1.0)
    uu = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, 1, hd)
    s0 = state.reshape(B * H, hd, hd).astype(jnp.float32)
    num_chunks = Tp // chunk

    out, sT = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, num_chunks=num_chunks),
        grid=(B * H, num_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, 1, hd), lambda g, c: (g, 0, 0)),
            pl.BlockSpec((1, hd, hd), lambda g, c: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hd), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, hd, hd), lambda g, c: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tp, hd), jnp.float32),
            jax.ShapeDtypeStruct((B * H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(rr, kk, vv, ww, uu, s0)

    out = jnp.moveaxis(out[:, :T].reshape(B, H, T, hd), 1, 2)
    return out, sT.reshape(B, H, hd, hd)
