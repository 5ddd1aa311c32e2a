"""The dense decoder, as the program's ``family="dense"`` states it.

Token embedding; per layer a pre-norm grouped-query attention with rotary
positions (rotate-half, ``rope_theta``) and a pre-norm SwiGLU feed-forward,
each added to the residual; a final RMS norm; logits over the real
vocabulary from the tied embedding or the untied ``unembed``.  RMS norms
scale by ``(1 + gamma)``.  The PRM's reward at a position is
``sigmoid(h @ w + b)`` of its final hidden state.

Keys read: ``hidden_size``, ``num_hidden_layers``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``intermediate_size``,
``vocab_size``, ``rope_theta``, ``rms_norm_eps``, ``tie_word_embeddings``
and ``reward_head``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HI, _mm, _r8, _rms, _rope
from bench.weights import INIT_RANGE, padded_vocab
from bench.work import BF16, Cost


def program(role: str, m: dict, dtype: str):
    from repro.config import ModelConfig
    return ModelConfig(
        name=f"{role}:{m['hf']}", family="dense",
        num_layers=int(m["num_hidden_layers"]), d_model=int(m["hidden_size"]),
        num_heads=int(m["num_attention_heads"]),
        num_kv_heads=int(m["num_key_value_heads"]),
        d_ff=int(m["intermediate_size"]), vocab_size=int(m["vocab_size"]),
        head_dim=int(m["head_dim"]), rope_theta=float(m["rope_theta"]),
        norm_eps=float(m["rms_norm_eps"]),
        tie_embeddings=bool(m["tie_word_embeddings"]),
        dtype=dtype, param_dtype=dtype, reward_head=bool(m.get("reward_head")))


def shapes(m: dict) -> dict:
    """Leaf name -> (shape, std) of one model; std 0 means zeros."""
    d, L = int(m["hidden_size"]), int(m["num_hidden_layers"])
    H, KV = int(m["num_attention_heads"]), int(m["num_key_value_heads"])
    hd, ff, V = int(m["head_dim"]), int(m["intermediate_size"]), \
        padded_vocab(m)
    out = {
        "embed/embedding": ((V, d), INIT_RANGE),
        "final_ln": ((d,), 0.0),
        "blocks/p0/ln1": ((L, d), 0.0),
        "blocks/p0/ln2": ((L, d), 0.0),
        "blocks/p0/attn/wq": ((L, d, H, hd), d ** -0.5),
        "blocks/p0/attn/wk": ((L, d, KV, hd), d ** -0.5),
        "blocks/p0/attn/wv": ((L, d, KV, hd), d ** -0.5),
        "blocks/p0/attn/wo": ((L, H, hd, d), hd ** -0.5),
        "blocks/p0/ffn/wi_gate": ((L, d, ff), d ** -0.5),
        "blocks/p0/ffn/wi_up": ((L, d, ff), d ** -0.5),
        "blocks/p0/ffn/wo": ((L, ff, d), ff ** -0.5),
    }
    if not m["tie_word_embeddings"]:
        out["embed/unembed"] = ((d, V), INIT_RANGE)
    if m.get("reward_head"):
        out["reward_head/w"] = ((d, 1), INIT_RANGE)
        out["reward_head/b"] = ((1,), 0.0)
    return out


def cost(m: dict) -> Cost:
    d, L = int(m["hidden_size"]), int(m["num_hidden_layers"])
    H, KV = int(m["num_attention_heads"]), int(m["num_key_value_heads"])
    hd, ff = int(m["head_dim"]), int(m["intermediate_size"])
    body = L * (2 * d * H * hd + 2 * d * KV * hd + 3 * d * ff)
    return Cost(L, body, d * int(m["vocab_size"]), 4 * H * hd * L,
                2 * KV * hd * BF16 * L)


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

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
    seq = np.asarray(seq, np.int32)
    nxt = np.concatenate([seq[1:], [0]]).astype(np.int32)
    mkey = tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, bool, str))))
    out = _forward(w, jnp.asarray(seq), jnp.asarray(nxt), mkey=mkey,
                   lowp=lowp)
    return {k: np.asarray(v)[:length] for k, v in out.items()}
