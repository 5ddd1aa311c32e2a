"""Launcher plumbing: registered triples, seeded init, replica placement,
the compile-cache rule and the platform's kernel dispatch."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.kernels import ops
from repro.launch import compile_cache
from repro.launch.serve import init_triple, param_bytes, resolve_triple, \
    toy_triple

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_resolve_triple_adds_reward_head_and_shares_vocab():
    d, t, p = resolve_triple("qwen2.5-math-1.5b", "qwen3-1.7b",
                             "qwen2.5-math-1.5b")
    assert (d.d_model, t.d_model, p.d_model) == (1536, 2048, 1536)
    assert p.reward_head and not d.reward_head
    assert d.vocab_size == t.vocab_size == p.vocab_size == 151936
    with pytest.raises(ValueError, match="vocabulary"):
        resolve_triple("qwen2.5-math-1.5b", "qwen2.5-math-7b",
                       "qwen2.5-math-1.5b")


def test_init_triple_is_seeded():
    cfgs = toy_triple()
    a = init_triple(cfgs, seed=3)
    b = init_triple(cfgs, seed=3)
    c = init_triple(cfgs, seed=4)
    leaf = lambda ps: jax.tree.leaves(ps[1])[0]  # noqa: E731
    assert (leaf(a) == leaf(b)).all()
    assert not (leaf(a) == leaf(c)).all()
    assert param_bytes(a[0]) == sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(a[0]))


PLACEMENT_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import numpy as np
    from repro.config import GSIConfig
    from repro.data import SyntheticReasoningTask
    from repro.launch.serve import (build_engines, evaluate_queued,
                                    init_triple, toy_triple)

    cfgs = toy_triple()
    params = init_triple(cfgs, seed=0)
    g = GSIConfig(n=2, max_step_tokens=4, max_steps=2, temperature=0.0)
    devs = jax.devices()
    assert len(devs) == 8, devs
    engines = build_engines(cfgs, params, g, replicas=3, max_seq=32)
    for r, eng in enumerate(engines):
        assert eng.device == devs[r], (r, eng.device)
        for leaf in jax.tree.leaves(eng.params):
            assert leaf.devices() == {devs[r]}, (r, leaf.devices())
        state = eng.fresh_state(2)
        for leaf in jax.tree.leaves(state):
            if isinstance(leaf, jax.Array):
                assert leaf.devices() == {devs[r]}, (r, leaf.devices())

    # the same greedy requests through two pinned replicas and through one
    task = SyntheticReasoningTask(seed=0)
    problems = [task.sample_problem() for _ in range(4)]
    toks = []
    for replicas in (2, 1):
        engs = build_engines(cfgs, params, g, replicas=replicas,
                             max_seq=32)
        res = evaluate_queued(engs if replicas > 1 else engs[0], task,
                              problems, jax.random.PRNGKey(1), capacity=2,
                              sync=False)
        toks.append([np.asarray(res["responses"][i].tokens)
                     for i in res["ids"]])
    for a, b in zip(*toks):
        assert a.shape == b.shape and (a == b).all()
    print("PLACEMENT_OK")
""")


def test_build_engines_places_replica_r_on_device_r_mod_count():
    """On 8 (forced host) devices replica r's weights and state live on
    device r, and two pinned replicas serve the tokens one replica does."""
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", PLACEMENT_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "PLACEMENT_OK" in out.stdout, out.stdout + out.stderr


def test_compile_cache_follows_env(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/some/where"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path.endswith(".jax_cache")
        assert (compile_cache.checkout_cache().parent / "src" / "repro"
                ).is_dir()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_refuses_outside_a_checkout(tmp_path):
    module = tmp_path / "lib" / "site-packages" / "repro" / "launch" / "x.py"
    with pytest.raises(RuntimeError, match="not a checkout"):
        compile_cache.checkout_cache(module)


@pytest.mark.parametrize("backend,env,mode", [
    ("tpu", None, "pallas"),
    ("tpu", "interpret", "pallas"),     # the env never overrides a TPU
    ("cpu", None, "ref"),
    ("cpu", "1", "ref"),
    ("cpu", "interpret", "interpret"),
])
def test_kernel_dispatch_follows_platform(monkeypatch, backend, env, mode):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if env is None:
        monkeypatch.delenv("REPRO_USE_PALLAS", raising=False)
    else:
        monkeypatch.setenv("REPRO_USE_PALLAS", env)
    assert ops._mode() == mode
    assert ops.use_kernels() == (mode != "ref")


def test_toy_triple_unchanged_by_registered_path():
    d, t, p = toy_triple()
    assert d.vocab_size == t.vocab_size == p.vocab_size == 16
    assert p == dataclasses.replace(t, name="sx-prm", reward_head=True)
