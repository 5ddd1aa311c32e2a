"""Sharding rules + a real 8-device pjit/shard_map integration (subprocess).

The multi-device tests run in subprocesses because the placeholder
device count must be set before jax initializes (conftest keeps the main
test process on the single real CPU device).

Tensor-parallel *serving* coverage (the mesh engine):
  * sharded == unsharded BIT-IDENTICAL tokens through the scheduler —
    a 2-replica router where each replica owns a (data=1, model=2)
    submesh, sync AND async, greedy AND temperature>0 (subprocess);
  * the same identity across local and hybrid attention stacks;
  * sharding-spec assertions: target weights and target KV pool carry
    the ``model`` axis, draft/PRM stay replicated, submeshes disjoint;
  * an in-process (1,1)-mesh engine for tier-1 coverage of the
    shard_map decode path on the single real CPU device, including
    page-ledger conservation under the sharded pool.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.models.common import ParamSpec

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class FakeMesh:
    axis_names = ("data", "model")

    class devices:  # noqa: N801
        shape = (4, 2)

    shape = {"data": 4, "model": 2}


def _pspec(shape, axes, mode="train"):
    from repro.distributed.sharding import spec_pspec
    return spec_pspec(ParamSpec(shape, axes, "normal", 1.0), FakeMesh(),
                      mode)


def test_divisibility_fallback():
    # heads=3 not divisible by model=2 -> replicated
    assert _pspec((64, 3, 16), ("embed", "heads", "head"))[1] is None
    # heads=4 divisible -> sharded
    assert _pspec((64, 4, 16), ("embed", "heads", "head"))[1] == "model"
    # embed FSDP over data in train mode
    assert _pspec((64, 4, 16), ("embed", "heads", "head"))[0] == "data"
    # serve mode: embed replicated
    assert _pspec((64, 4, 16), ("embed", "heads", "head"),
                  "serve")[0] is None


def test_no_axis_reuse_within_one_param():
    # expert -> model and expert_mlp -> data must not collide with embed
    p = _pspec((8, 64, 32), ("expert", "embed", "expert_mlp"))
    used = [a for a in p if a]
    assert len(used) == len(set(used))


def test_batch_pspec():
    from repro.distributed.sharding import batch_pspec
    assert batch_pspec(_mesh_like((4, 2), ("data", "model")), 8) == "data"
    assert batch_pspec(_mesh_like((4, 2), ("data", "model")), 3) is None


def _mesh_like(shape, axes):
    class M:
        axis_names = axes

        class devices:  # noqa: N801
            pass
    M.devices.shape = shape
    return M()


MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.config import get_config, reduced_config, TrainConfig
    from repro.distributed import context as dctx
    from repro.distributed.sharding import (as_shardings, param_pspecs,
                                            batch_pspec)
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models import build_model
    from repro.optim import AdamW
    from repro.train import make_train_step

    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh((4, 2))
    # MoE arch exercises the shard_map expert-parallel path for real
    cfg = reduced_config(get_config("qwen2-moe-a2.7b"))
    model = build_model(cfg)
    tcfg = TrainConfig(total_steps=5, warmup_steps=1)
    with dctx.use_mesh(mesh):
        p_sh = as_shardings(param_pspecs(model.param_specs(), mesh,
                                         "train"), mesh)
        params = jax.jit(model.init, out_shardings=p_sh)(
            jax.random.PRNGKey(0))
        opt = AdamW(tcfg)
        opt_state = opt.init(params)
        step = jax.jit(make_train_step(cfg, tcfg))
        B, S = 8, 16
        batch = {
            "tokens": jnp.asarray(
                np.random.randint(3, cfg.vocab_size, (B, S)), jnp.int32),
            "loss_mask": jnp.ones((B, S), jnp.float32),
        }
        sh = NamedSharding(mesh, P("data", None))
        batch = {k: jax.device_put(v, sh) for k, v in batch.items()}
        for i in range(3):
            params, opt_state, m = step(params, opt_state, batch)
        loss = float(m["loss"])
        assert np.isfinite(loss), loss
        # both expert-parallel modes agree (H2's repl vs gather dispatch)
        model = build_model(cfg)
        outs = []
        for mode in ("gather", "repl"):
            os.environ["REPRO_MOE_MODE"] = mode
            lg, _ = jax.jit(model.forward)(params, batch["tokens"][:, :8])
            outs.append(np.asarray(lg, np.float32))
        os.environ["REPRO_MOE_MODE"] = "auto"
        np.testing.assert_allclose(outs[0], outs[1], atol=2e-3, rtol=2e-3)
        print("MULTIDEV_OK", loss)
""")


@pytest.mark.slow
def test_multidevice_train_step_runs():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "MULTIDEV_OK" in out.stdout, out.stdout + out.stderr


def _run_subprocess(script, marker, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert marker in out.stdout, out.stdout + out.stderr


SHARDED_ROUTER_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    import jax.tree_util as jtu
    from repro.config import GSIConfig
    from repro.launch.mesh import carve_submeshes
    from repro.launch.serve import make_frontend, toy_triple
    from repro.models import build_model
    from repro.serving.gsi_engine import GSIServingEngine

    draft, target, prm = toy_triple()
    rng = jax.random.PRNGKey(0)
    ps = build_model(draft).init(jax.random.fold_in(rng, 1))
    pb = build_model(target).init(jax.random.fold_in(rng, 2))
    pp = build_model(prm).init(jax.random.fold_in(rng, 3))
    prompts = [[5, 6, 7, 8, 9, 3, 2, 11, 4, 4],
               [5, 6, 7, 8, 9, 3, 2, 11, 6], [2, 3, 4], [9, 8, 7, 6],
               [5, 6, 7, 8, 9, 3, 2, 11, 12], [1, 2]]

    def serve(meshes, temperature, sync):
        g = GSIConfig(n=2, max_step_tokens=6, max_steps=3,
                      temperature=temperature)
        engs = [GSIServingEngine(draft, target, prm, ps, pb, pp, g,
                                 paged=True, page_size=4, mesh=m)
                for m in meshes]
        sched = make_frontend(engs, capacity=2, sync=sync)
        ids = [sched.submit(np.asarray(p, np.int32)) for p in prompts]
        res = sched.run(jax.random.PRNGKey(42))
        return [np.asarray(res[i].tokens) for i in ids], engs

    subs = carve_submeshes(2, (1, 2))
    for sync, temp in ((True, 0.0), (True, 0.7), (False, 0.7)):
        base, _ = serve([None, None], temp, sync)
        shard, engs = serve(subs, temp, sync)
        for a, b in zip(base, shard):
            assert a.shape == b.shape and (a == b).all(), (sync, temp)
        print(f"identical sync={sync} temp={temp}")

    # sharding-spec assertions on the last sharded fleet
    eng = engs[0]
    tspecs = [str(l.sharding.spec)
              for l in jtu.tree_leaves(eng.params[1])]
    assert any("model" in s for s in tspecs), "target not sharded"
    rep = [str(l.sharding.spec)
           for l in jtu.tree_leaves((eng.params[0], eng.params[2]))]
    assert all("model" not in s for s in rep), "draft/PRM not replicated"
    state = eng.init_state(np.asarray([[3, 4, 5, 6]], np.int32))
    kv = [str(l.sharding.spec)
          for p, l in jtu.tree_flatten_with_path(state)[0]
          if "'B'" in str(p) and getattr(l, "ndim", 0) >= 4]
    assert any("model" in s for s in kv), "target KV pool not sharded"
    ids0 = {d.id for d in subs[0].devices.flat}
    ids1 = {d.id for d in subs[1].devices.flat}
    assert not ids0 & ids1, "submeshes overlap"
    print("SHARDED_ROUTER_OK")
""")


@pytest.mark.slow
def test_sharded_router_bitwise_identity():
    """2 replicas x (data=1, model=2) submeshes through the router are
    bit-identical to the unsharded 2-replica fleet — sync and async,
    greedy and temperature>0 — with target weights/KV verifiably on the
    ``model`` axis and draft/PRM replicated."""
    _run_subprocess(SHARDED_ROUTER_SCRIPT, "SHARDED_ROUTER_OK")


SHARDED_STACKS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import numpy as np
    import jax
    from repro.config import GSIConfig
    from repro.launch.mesh import carve_submeshes
    from repro.launch.serve import make_frontend, toy_triple
    from repro.models import build_model
    from repro.serving.gsi_engine import GSIServingEngine

    draft, target, prm = toy_triple()
    rng = jax.random.PRNGKey(0)
    ps = build_model(draft).init(jax.random.fold_in(rng, 1))
    pp = build_model(prm).init(jax.random.fold_in(rng, 3))
    mesh = carve_submeshes(1, (1, 2))[0]
    prompts = [[3, 4, 5, 6, 7], [2, 3, 4], [9, 8, 7, 6, 5, 4]]

    for name, pat in (("local", ("local",)),
                      ("hybrid", ("full", "local"))):
        tgt = dataclasses.replace(target, layer_pattern=pat,
                                  window_size=8)
        pb = build_model(tgt).init(jax.random.fold_in(rng, 2))
        for temp in (0.0, 0.7):
            toks = []
            for m in (None, mesh):
                g = GSIConfig(n=2, max_step_tokens=6, max_steps=3,
                              temperature=temp)
                eng = GSIServingEngine(draft, tgt, prm, ps, pb, pp, g,
                                       paged=True, page_size=4, mesh=m)
                sched = make_frontend(eng, capacity=2, sync=True)
                ids = [sched.submit(np.asarray(p, np.int32))
                       for p in prompts]
                res = sched.run(jax.random.PRNGKey(9))
                toks.append([np.asarray(res[i].tokens) for i in ids])
            for a, b in zip(*toks):
                assert a.shape == b.shape and (a == b).all(), (name,
                                                               temp)
        print("stack", name, "ok")
    print("SHARDED_STACKS_OK")
""")


@pytest.mark.slow
def test_sharded_stacks_bitwise_identity():
    """Sliding-window (local) and hybrid full/local target stacks keep
    the sharded==unsharded token identity through the scheduler."""
    _run_subprocess(SHARDED_STACKS_SCRIPT, "SHARDED_STACKS_OK")


def test_mesh_single_device_engine_matches_unsharded(tiny_dense):
    """In-process tier-1 coverage: a (1,1) mesh engine routes decode
    through shard_map on the single real CPU device and stays
    bit-identical to the plain jit engine, with the sharded page pool's
    ledger conserved (bytes-weighted eviction armed via page_bytes)."""
    from repro.config import GSIConfig
    from repro.launch.mesh import carve_submeshes
    from repro.models import build_model
    from repro.serving import GSIScheduler, GSIServingEngine

    target = dataclasses.replace(tiny_dense, name="t1-tgt", num_layers=3)
    prm = dataclasses.replace(target, name="t1-prm", reward_head=True)
    params = (build_model(tiny_dense).init(jax.random.PRNGKey(0)),
              build_model(target).init(jax.random.PRNGKey(1)),
              build_model(prm).init(jax.random.PRNGKey(2)))
    g = GSIConfig(n=2, max_step_tokens=5, max_steps=3, temperature=0.7)
    mesh = carve_submeshes(1, (1, 1))[0]
    prompts = [[5, 6, 7, 8, 9], [2, 3, 4]]
    toks = []
    for m in (None, mesh):
        eng = GSIServingEngine(tiny_dense, target, prm, *params, g,
                               max_seq=64, paged=True, page_size=4,
                               mesh=m)
        sched = GSIScheduler(eng, capacity=2)
        ids = [sched.submit(np.asarray(p, np.int32)) for p in prompts]
        res = sched.run(jax.random.PRNGKey(5))
        toks.append([np.asarray(res[i].tokens) for i in ids])
    for a, b in zip(*toks):
        assert a.shape == b.shape and (a == b).all()
    assert eng.tp == 1 and eng.mesh is not None
    pool = eng.pager
    assert pool.page_bytes > 0  # bytes-weighted LRU armed in production
    assert pool.num_free + pool.num_referenced + pool.num_cached \
        == eng.num_pages


def test_tensor_parallel_context_is_per_thread():
    """The fleet loop traces each replica's shard_map body on its own
    thread: one replica leaving its tensor-parallel context must not end
    another's, nor leak into an unsharded trace on a third thread."""
    import threading
    from repro.distributed import tp as dtp

    errors = []
    start = threading.Barrier(24)

    def sharded():
        start.wait(timeout=30)
        for _ in range(300):
            with dtp.tensor_parallel("model"):
                for _ in range(5):
                    if dtp.axis() != "model":
                        errors.append("lost")
            if dtp.axis() is not None:
                errors.append("leaked")

    def unsharded():
        start.wait(timeout=30)
        for _ in range(1500):
            if dtp.axis() is not None:
                errors.append("foreign")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=sharded if i % 2 else unsharded)
                   for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[:5]
