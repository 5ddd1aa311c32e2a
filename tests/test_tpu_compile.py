"""Compile every main-path Pallas kernel for a described TPU v5e.

No chip is needed: the TPU compiler is installed with jax and compiles for
a topology that is described, not attached (``v5e:2x2``, one chip of it).
What it refuses here (block shapes off the (8, 128) tiling, scoped VMEM
over budget, unsupported vector ops) it would refuse on the chip.  Shapes
are the real widths of the one-chip serving triple: qwen3-1.7b
(H/KV = 16/8) and qwen2.5-math-1.5b (12/2), head dim 128, d_model 2048,
vocab 151936, bf16; the RWKV scan at rwkv6-3b's widths and at hd 128.
Training differentiates through flash attention and the RWKV scan, so
their gradients are compiled too, as is the toy triple's train step
(head dims 16 and 40, off the 128-lane tile) that the launcher runs by
default.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a time,
and under pytest-xdist every worker imports this file.  JAX's persistent
compilation cache is switched off around these compiles (an entry written
for a described chip cannot be read back without one).
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.logprob_gather import logprob_gather_pallas
from repro.kernels.paged_attention import (paged_attention_pallas,
                                           paged_attention_quant_pallas)
from repro.kernels.rwkv6_scan import rwkv6_scan_pallas

#: (num_heads, num_kv_heads) of the triple's two attention shapes
HEADS = [(16, 8), (12, 2)]
HD = 128
#: paged serving shapes, as chip_smoke.py serves them: capacity 8 x n 4
#: branch rows, max_seq 1024 in 16-token pages (+ the trash column), a
#: pool of 256 allocatable pages
BRANCH_ROWS, PAGE, NBLK, POOL = 32, 16, 65, 256


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture
def kernels_on(monkeypatch):
    """The dispatch of ``kernels.ops`` as on a TPU."""
    monkeypatch.setattr(ops, "_mode", lambda: "pallas")


def _compile(fn, sharding, *shapes):
    """Lower + compile ``fn`` for the described chip; return its HLO."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return compiled


@pytest.mark.parametrize("heads", HEADS)
def test_paged_attention_compiles(one_chip, heads):
    H, KV = heads
    fn = functools.partial(paged_attention_pallas, interpret=False)
    _compile(fn, one_chip,
             ((BRANCH_ROWS, 1, H, HD), jnp.bfloat16),
             ((POOL, PAGE, KV, HD), jnp.bfloat16),
             ((POOL, PAGE, KV, HD), jnp.bfloat16),
             ((BRANCH_ROWS, NBLK), jnp.int32),
             ((BRANCH_ROWS,), jnp.int32))


@pytest.mark.parametrize("heads", HEADS)
def test_paged_attention_int8_compiles(one_chip, heads):
    H, KV = heads
    fn = functools.partial(paged_attention_quant_pallas, interpret=False)
    _compile(fn, one_chip,
             ((BRANCH_ROWS, 1, H, HD), jnp.bfloat16),
             ((POOL, PAGE, KV, HD), jnp.int8),
             ((POOL, PAGE, KV, HD), jnp.int8),
             ((POOL, KV), jnp.float32),
             ((POOL, KV), jnp.float32),
             ((BRANCH_ROWS, NBLK), jnp.int32),
             ((BRANCH_ROWS,), jnp.int32))


@pytest.mark.parametrize("heads", HEADS)
def test_flash_attention_compiles(one_chip, heads):
    H, KV = heads
    B, S = 2, 1024
    fn = functools.partial(flash_attention_pallas, interpret=False)
    _compile(fn, one_chip,
             ((B, S, H, HD), jnp.bfloat16),
             ((B, S, KV, HD), jnp.bfloat16),
             ((B, S, KV, HD), jnp.bfloat16))


@pytest.mark.parametrize("d", [2048, 1536])
def test_logprob_gather_compiles(one_chip, d):
    B, S, V = BRANCH_ROWS, 9, 151936
    fn = functools.partial(logprob_gather_pallas, vocab_size=V,
                           interpret=False)
    compiled = _compile(fn, one_chip,
                        ((B, S, d), jnp.bfloat16),
                        ((d, V), jnp.bfloat16),
                        ((B, S), jnp.int32))
    # the whole program (logits never materialised) stays far below HBM
    mem = compiled.memory_analysis()
    if mem is not None:
        assert mem.temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("H,hd", [(16, 128), (40, 64)])
def test_rwkv6_scan_compiles(one_chip, H, hd):
    B, T = 1, 256
    fn = functools.partial(rwkv6_scan_pallas, interpret=False)
    _compile(fn, one_chip,
             ((B, T, H, hd), jnp.bfloat16),
             ((B, T, H, hd), jnp.bfloat16),
             ((B, T, H, hd), jnp.bfloat16),
             ((B, T, H, hd), jnp.float32),
             ((H, hd), jnp.float32),
             ((B, H, hd, hd), jnp.float32))


def _value_and_grad(fn):
    def loss(*args):
        return sum(jnp.sum(o.astype(jnp.float32))
                   for o in jax.tree.leaves(fn(*args)))
    return jax.value_and_grad(loss, argnums=(0, 1, 2))


@pytest.mark.parametrize("kernel", ["flash_attention", "rwkv6_scan"])
def test_kernel_gradient_compiles(one_chip, kernels_on, kernel):
    if kernel == "flash_attention":
        H, KV = HEADS[0]
        B, S = 2, 1024
        _compile(_value_and_grad(ops.flash_attention), one_chip,
                 ((B, S, H, HD), jnp.bfloat16),
                 ((B, S, KV, HD), jnp.bfloat16),
                 ((B, S, KV, HD), jnp.bfloat16))
    else:
        B, T, H, hd = 1, 256, 40, 64
        _compile(_value_and_grad(ops.rwkv6_scan), one_chip,
                 *[((B, T, H, hd), jnp.bfloat16)] * 3,
                 ((B, T, H, hd), jnp.float32),
                 ((H, hd), jnp.float32),
                 ((B, H, hd, hd), jnp.float32))


@pytest.mark.parametrize("which", [0, 1, 2])
def test_toy_train_step_compiles(one_chip, kernels_on, which):
    from repro.config import TrainConfig
    from repro.launch.serve import toy_triple
    from repro.models import build_model
    from repro.optim import AdamW
    from repro.train.trainer import make_prm_train_step, make_train_step
    cfg = toy_triple()[which]
    tcfg = TrainConfig()
    params = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(AdamW(tcfg).init, params)
    if cfg.reward_head:
        step = make_prm_train_step(cfg, tcfg)
        batch = {"tokens": ((4, 32), jnp.int32),
                 "reward_labels": ((4, 32), jnp.float32),
                 "reward_mask": ((4, 32), jnp.float32)}
    else:
        step = make_train_step(cfg, tcfg)
        batch = {"tokens": ((4, 32), jnp.int32),
                 "loss_mask": ((4, 32), jnp.float32)}

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    batch = {k: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
             for k, (s, d) in batch.items()}
    text = jax.jit(step).lower(place(params), place(opt), batch
                               ).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
