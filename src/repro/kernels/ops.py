"""Jit'd public wrappers for the Pallas kernels.

Dispatch policy, decided by the platform:
  TPU  — the compiled Pallas kernels, always.  A kernel that the chip's
         compiler refuses raises; nothing falls back to the reference.
  CPU  — the pure-jnp reference path (tests, dry-run lowering), or the
         Pallas kernels in interpret mode when ``REPRO_USE_PALLAS=
         interpret`` (kernel correctness tests).

Flash attention and the RWKV6 scan also run under training's
``value_and_grad``.  A ``pallas_call`` has no reverse-mode rule, so both
are wrapped in a ``custom_vjp`` whose forward is the kernel and whose
backward is the reference's, recomputed from the inputs
(:func:`_kernel_with_ref_grad`).

Tensor-parallel serving note: under the mesh engine these wrappers run
*inside* ``shard_map``, so paged-attention gathers see the local KV-head
shard of each page pool (the KV-head dim is sharded over the ``model``
axis) — per-shard shapes, no collectives here; the output projections in
``repro.models`` all_gather afterwards.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref


def _mode() -> str:
    """"pallas" on a TPU; on the CPU "interpret" when
    ``REPRO_USE_PALLAS=interpret`` is set, else "ref"."""
    if jax.default_backend() == "tpu":
        return "pallas"
    if os.environ.get("REPRO_USE_PALLAS") == "interpret":
        return "interpret"
    return "ref"


def use_kernels() -> bool:
    """True when the model code should route through the kernels (any
    mode but the CPU reference)."""
    return _mode() != "ref"


def _interpret() -> bool:
    return _mode() == "interpret"


def _kernel_with_ref_grad(kernel, reference, *args):
    """``kernel(*args)``, differentiable: the cotangents are those of
    ``reference``, recomputed from the saved inputs."""
    @jax.custom_vjp
    def f(*a):
        return kernel(*a)

    def fwd(*a):
        return kernel(*a), a

    def bwd(a, g):
        out, vjp = jax.vjp(reference, *a)
        return vjp(jax.tree.map(lambda c, o: c.astype(o.dtype), g, out))

    f.defvjp(fwd, bwd)
    return f(*args)


# ---------------------------------------------------------------------------
# logprob_gather — the GSI scoring hot-spot
# ---------------------------------------------------------------------------

def logprob_gather(h, w, labels, vocab_size: int):
    """Fused log-softmax + label gather over the vocab dim.

    h: (B,S,d); w: (d,V); labels: (B,S) -> (B,S) fp32 log-probs.
    """
    if _mode() == "ref":
        return ref.logprob_gather_ref(h, w, labels, vocab_size)
    from repro.kernels.logprob_gather import logprob_gather_pallas
    return logprob_gather_pallas(h, w, labels, vocab_size,
                                 interpret=_interpret())


# ---------------------------------------------------------------------------
# flash attention (prefill / train)
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal=True, window=0, scale=None):
    """q: (B,Sq,H,hd); k/v: (B,Sk,KV,hd) -> (B,Sq,H,hd)."""
    if _mode() == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       scale=scale)
    from repro.kernels.flash_attention import flash_attention_pallas
    kw = dict(causal=causal, window=window, scale=scale)
    return _kernel_with_ref_grad(
        partial(flash_attention_pallas, interpret=_interpret(), **kw),
        partial(ref.flash_attention_ref, **kw), q, k, v)


# ---------------------------------------------------------------------------
# paged decode attention (serving; gathers K/V through the block table)
# ---------------------------------------------------------------------------

def paged_attention(q, kp, vp, pt, pos, *, window=0, scale=None):
    """q: (B,1,H,hd); kp/vp: (P,ps,KV,hd); pt: (B,nblk); pos: (B,)."""
    if _mode() == "ref":
        return ref.paged_attention_ref(q, kp, vp, pt, pos, window=window,
                                       scale=scale)
    from repro.kernels.paged_attention import paged_attention_pallas
    return paged_attention_pallas(q, kp, vp, pt, pos, window=window,
                                  scale=scale, interpret=_interpret())


def paged_attention_quant(q, kp, vp, ks, vs, pt, pos, *, window=0,
                          scale=None):
    """Quantized paged decode attention with fused dequantization.

    q: (B,1,H,hd); kp/vp: (P,ps,KV,hd) int8/fp8 codes; ks/vs: (P,KV)
    float32 per-page per-kv-head scales; pt: (B,nblk); pos: (B,).
    """
    if _mode() == "ref":
        return ref.paged_attention_quant_ref(q, kp, vp, ks, vs, pt, pos,
                                             window=window, scale=scale)
    from repro.kernels.paged_attention import paged_attention_quant_pallas
    return paged_attention_quant_pallas(q, kp, vp, ks, vs, pt, pos,
                                        window=window, scale=scale,
                                        interpret=_interpret())


# ---------------------------------------------------------------------------
# RWKV6 chunked scan
# ---------------------------------------------------------------------------

def rwkv6_scan(r, k, v, w, u, state):
    """r,k,v,w: (B,T,H,hd); u: (H,hd); state: (B,H,hd,hd) fp32."""
    if _mode() == "ref":
        return ref.rwkv6_scan_ref(r, k, v, w, u, state)
    from repro.kernels.rwkv6_scan import rwkv6_scan_pallas
    return _kernel_with_ref_grad(
        partial(rwkv6_scan_pallas, interpret=_interpret()),
        ref.rwkv6_scan_ref, r, k, v, w, u, state)
