"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.logprob_gather import logprob_gather_pallas
from repro.kernels.rwkv6_scan import rwkv6_scan_pallas


@pytest.mark.parametrize("B,S,d,V,vocab,dtype", [
    (2, 8, 64, 512, 500, jnp.float32),
    (1, 17, 128, 1024, 1024, jnp.float32),
    (3, 5, 32, 768, 700, jnp.bfloat16),
    (1, 1, 16, 256, 256, jnp.float32),
])
def test_logprob_gather(B, S, d, V, vocab, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(V + S), 3)
    h = jax.random.normal(k1, (B, S, d), dtype)
    w = (jax.random.normal(k2, (d, V), jnp.float32) * 0.05).astype(dtype)
    lab = jax.random.randint(k3, (B, S), 0, vocab)
    out = logprob_gather_pallas(h, w, lab, vocab, tt=8, vt=256,
                                interpret=True)
    want = ref.logprob_gather_ref(h, w, lab, vocab)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(out, want, atol=tol, rtol=tol)


@pytest.mark.parametrize("B,Sq,H,KV,hd,causal,window,dtype", [
    (2, 32, 4, 2, 16, True, 0, jnp.float32),
    (1, 40, 3, 1, 32, True, 16, jnp.float32),
    (2, 24, 2, 2, 8, False, 0, jnp.float32),
    (1, 33, 4, 4, 16, True, 0, jnp.bfloat16),
])
def test_flash_attention(B, Sq, H, KV, hd, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(Sq + H), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Sq, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, Sq, KV, hd), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 qt=16, kt=16, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("B,T,H,hd,chunk", [
    (2, 24, 3, 8, 8),
    (1, 17, 2, 16, 8),   # ragged T vs chunk
    (2, 32, 1, 4, 16),
    (1, 8, 2, 8, 64),    # chunk > T
])
def test_rwkv6_scan(B, T, H, hd, chunk):
    ks = jax.random.split(jax.random.PRNGKey(T + hd), 6)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, hd)) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, hd))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (H, hd)) * 0.3
    s0 = jax.random.normal(ks[5], (B, H, hd, hd)) * 0.1
    out, sT = rwkv6_scan_pallas(r, k, v, w, u, s0, chunk=chunk,
                                interpret=True)
    oref, sref = ref.rwkv6_scan_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(out, oref, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(sT, sref, atol=1e-4, rtol=1e-4)


def test_ops_dispatch_interpret(monkeypatch):
    """REPRO_USE_PALLAS=interpret routes through the kernels."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "interpret")
    from repro.kernels import ops
    h = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 32))
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 256)) * 0.1
    lab = jax.random.randint(jax.random.PRNGKey(2), (1, 4), 0, 256)
    np.testing.assert_allclose(
        ops.logprob_gather(h, w, lab, 256),
        ref.logprob_gather_ref(h, w, lab, 256), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("name", ["flash_attention", "rwkv6_scan"])
def test_kernel_gradient_is_the_references(monkeypatch, name):
    """Pallas calls have no reverse-mode rule: ops wraps the kernels that
    training differentiates so that their cotangents are the reference's."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "interpret")
    from repro.kernels import ops
    ks = jax.random.split(jax.random.PRNGKey(7), 7)
    if name == "flash_attention":
        args = (jax.random.normal(ks[0], (2, 24, 4, 16)),
                jax.random.normal(ks[1], (2, 24, 2, 16)),
                jax.random.normal(ks[2], (2, 24, 2, 16)))
        fn, want_fn = ops.flash_attention, ref.flash_attention_ref
    else:
        B, T, H, hd = 1, 12, 2, 8
        r, k, v = (jax.random.normal(ks[i], (B, T, H, hd)) for i in range(3))
        w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, hd))) * 0.5 \
            + 0.45
        args = (r, k, v, w, jax.random.normal(ks[4], (H, hd)) * 0.3,
                jax.random.normal(ks[5], (B, H, hd, hd)) * 0.1)
        fn, want_fn = ops.rwkv6_scan, ref.rwkv6_scan_ref

    def loss(f):
        def go(*a):
            outs = jax.tree.leaves(f(*a))
            return sum(jnp.sum(o * jnp.cos(jnp.arange(o.size).reshape(
                o.shape))) for o in outs)
        return go

    argnums = tuple(range(len(args)))
    assert "pallas_call" in str(jax.make_jaxpr(
        jax.grad(loss(fn), argnums))(*args))
    got = jax.grad(loss(fn), argnums)(*args)
    want = jax.grad(loss(want_fn), argnums)(*args)
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(g, w_, atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_trainer_step_through_kernels(monkeypatch, family):
    """A Trainer step differentiates through flash attention / the RWKV6
    scan in kernel mode and agrees with the reference path."""
    import dataclasses

    from repro.config import ModelConfig, TrainConfig
    from repro.train.trainer import Trainer
    cfg = ModelConfig(name=f"tiny-{family}", family=family, num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=64, head_dim=16, dtype="float32",
                      param_dtype="float32")
    if family == "ssm":
        cfg = dataclasses.replace(cfg, num_kv_heads=4, rwkv_head_dim=16)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 16),
                                           0, 64))
    batch = {"tokens": tokens, "loss_mask": np.ones((2, 16), np.float32)}
    hist = {}
    for mode in ("interpret", "0"):
        monkeypatch.setenv("REPRO_USE_PALLAS", mode)
        tr = Trainer(cfg, TrainConfig(seed=0))
        if mode == "interpret":
            jaxpr = jax.make_jaxpr(tr._step)(
                tr.params, tr.opt_state,
                {k: jnp.asarray(v) for k, v in batch.items()})
            assert "pallas_call" in str(jaxpr)
        hist[mode] = tr.fit([batch], steps=1)[0]
    assert np.isfinite(hist["interpret"]["loss"])
    np.testing.assert_allclose(hist["interpret"]["loss"], hist["0"]["loss"],
                               rtol=1e-5)
    np.testing.assert_allclose(hist["interpret"]["grad_norm"],
                               hist["0"]["grad_norm"], rtol=1e-4)
