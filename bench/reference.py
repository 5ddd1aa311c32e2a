"""The plain reference: each model's forward pass over a whole sequence,
in float32 at the highest matmul precision, with no kernel, cache or
batching.  It imports nothing of the program; it reads the weights the
benchmark made (``bench/weights.py``) by their names.

The architecture is the program's dense decoder as its configuration
states it: token embedding; per layer a pre-norm grouped-query attention
with rotary positions (rotate-half, ``rope_theta``) and a pre-norm SwiGLU
feed-forward, each added to the residual; a final RMS norm; logits over
the real vocabulary from the tied embedding or the untied ``unembed``.
RMS norms scale by ``(1 + gamma)``.  The PRM's reward at a position is
``sigmoid(h @ w + b)`` of its final hidden state.

``lowp=True`` gives the control: the same pass computed in float8 (e4m3),
the precision below the configuration's bfloat16.  Every matmul operand
is rounded to float8 (one scale per row of activations, per output column
of weights), and so is every activation at the points where a bfloat16
program rounds its own: the residual stream, norm outputs, projections,
attention probabilities and output, the feed-forward's hidden and the
logits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _fake_fp8(a, axes):
    """Round ``a`` through float8 e4m3 with one scale per slice that
    reduces over ``axes``."""
    amax = jnp.max(jnp.abs(a), axis=axes, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / F8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _r8(a, lowp, axes=-1):
    return _fake_fp8(a, axes) if lowp else a


def _mm(spec, x, w, x_axes, w_axes, lowp):
    if lowp:
        x, w = _fake_fp8(x, x_axes), _fake_fp8(w, w_axes)
    return jnp.einsum(spec, x, w, precision=HI)


def _rms(x, gamma, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gamma)


def _rope(x, theta):
    """x: (S, H, hd) at positions 0..S-1, rotate-half convention."""
    S, _, hd = x.shape
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(S, dtype=np.float64)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lw, *, m, lowp):
    """One decoder layer on x: (S, d) float32."""
    f = {k: v.astype(jnp.float32) for k, v in lw.items()}
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    S = x.shape[0]
    H, KV = int(m["num_attention_heads"]), int(m["num_key_value_heads"])
    hd = int(m["head_dim"])
    h = _r8(_rms(x, f["ln1"], eps), lowp)
    q = _r8(_rope(_mm("sd,dhk->shk", h, f["wq"], -1, 0, lowp), theta), lowp)
    k = _r8(_rope(_mm("sd,dhk->shk", h, f["wk"], -1, 0, lowp), theta), lowp)
    v = _r8(_mm("sd,dhk->shk", h, f["wv"], -1, 0, lowp), lowp)
    k = jnp.repeat(k, H // KV, axis=1)           # head h reads kv h // G
    v = jnp.repeat(v, H // KV, axis=1)
    scores = jnp.einsum("qhk,shk->hqs", q, k, precision=HI) * hd ** -0.5
    causal = np.tril(np.ones((S, S), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = _r8(jax.nn.softmax(scores, -1), lowp)
    o = _r8(jnp.einsum("hqs,shk->qhk", probs, v, precision=HI), lowp)
    x = _r8(x + _mm("shk,hkd->sd", o, f["wo"], (-2, -1), (0, 1), lowp), lowp)
    h = _r8(_rms(x, f["ln2"], eps), lowp)
    gate = jax.nn.silu(_mm("sd,df->sf", h, f["wi_gate"], -1, 0, lowp))
    up = _mm("sd,df->sf", h, f["wi_up"], -1, 0, lowp)
    ffn = _mm("sf,fd->sd", _r8(gate * up, lowp), f["wo_ffn"], -1, 0, lowp)
    return _r8(x + ffn, lowp)


def _layers(w):
    b = w["blocks"]["p0"]
    return {"ln1": b["ln1"], "ln2": b["ln2"], **b["attn"],
            "wi_gate": b["ffn"]["wi_gate"], "wi_up": b["ffn"]["wi_up"],
            "wo_ffn": b["ffn"]["wo"]}


@functools.partial(jax.jit, static_argnames=("mkey", "lowp"))
def _forward(w, tokens, nxt, *, mkey, lowp):
    m = dict(mkey)
    x = _r8(w["embed"]["embedding"][tokens].astype(jnp.float32), lowp)

    def body(x, lw):
        return _layer(x, lw, m=m, lowp=lowp), None

    x, _ = jax.lax.scan(body, x, _layers(w))
    x = _r8(_rms(x, w["final_ln"].astype(jnp.float32),
                 float(m["rms_norm_eps"])), lowp)
    if "unembed" in w["embed"]:
        logits = _mm("sd,dv->sv", x, w["embed"]["unembed"].astype(
            jnp.float32), -1, 0, lowp)
    else:
        logits = _mm("sd,vd->sv", x, w["embed"]["embedding"].astype(
            jnp.float32), -1, 1, lowp)
    V = int(m["vocab_size"])
    logits = _r8(logits, lowp)
    logits = jnp.where(jnp.arange(logits.shape[-1]) < V, logits, -jnp.inf)
    out = {"lse": jax.nn.logsumexp(logits, -1),
           "next": jnp.take_along_axis(logits, nxt[:, None], -1)[:, 0],
           "pad": logits[:, 0]}
    if "reward_head" in w:
        rh = w["reward_head"]
        z = jnp.dot(x, rh["w"].astype(jnp.float32), precision=HI)[:, 0]
        out["reward"] = jax.nn.sigmoid(z + rh["b"].astype(jnp.float32)[0])
    return out


def forward(m: dict, w: dict, seq: np.ndarray, length: int, *,
            lowp: bool = False) -> dict:
    """Per-position readings of one model over one sequence.

    ``seq`` is padded to a fixed width so that every sequence of a cell
    runs one compiled program; positions at or past ``length`` are
    padding.  At position p: ``lse`` (log-sum-exp of the logits),
    ``next`` (the logit of ``seq[p+1]``), ``pad`` (the logit of token 0)
    and, for a PRM, ``reward``.
    """
    seq = np.asarray(seq, np.int32)
    nxt = np.concatenate([seq[1:], [0]]).astype(np.int32)
    mkey = tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, bool, str))))
    out = _forward(w, jnp.asarray(seq), jnp.asarray(nxt), mkey=mkey,
                   lowp=lowp)
    return {k: np.asarray(v)[:length] for k, v in out.items()}
