"""Distributed training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --steps 200 --batch 16 --seq 256 [--synthetic] [--ckpt path]

On the CPU container this runs a real (small-batch) training loop on the
single device; on a TPU pod the same code path shards params/batch with the
production rules (pjit) — the mesh is chosen from the available device count.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.config import TrainConfig, get_config, reduced_config
from repro.data import SyntheticReasoningTask
from repro.data.lm import lm_batches, prefetch
from repro.distributed import context as dctx
from repro.distributed.sharding import (as_shardings, batch_pspec,
                                        param_pspecs)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import AdamW
from repro.train import make_train_step


def main() -> None:
    """CLI entry point (see module docstring for usage)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced (smoke) variant of the arch")
    ap.add_argument("--synthetic", action="store_true",
                    help="use the synthetic reasoning task data")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    tcfg = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                       warmup_steps=max(10, args.steps // 20))
    model = build_model(cfg)
    opt = AdamW(tcfg)

    n_dev = len(jax.devices())
    if n_dev > 1:
        import math
        model_ax = math.gcd(n_dev, 16)
        mesh = make_mesh((n_dev // model_ax, model_ax), ("data", "model"))
        dctx.set_mesh(mesh)
        p_sh = as_shardings(param_pspecs(model.param_specs(), mesh, "train"),
                            mesh)
        params = jax.jit(model.init, out_shardings=p_sh)(
            jax.random.PRNGKey(tcfg.seed))
    else:
        params = model.init(jax.random.PRNGKey(tcfg.seed))
    opt_state = opt.init(params)

    step_fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))

    if args.synthetic:
        task = SyntheticReasoningTask(seed=tcfg.seed)
        it = (task.lm_batch(args.batch, args.seq) for _ in iter(int, 1))
    else:
        it = lm_batches(cfg.vocab_size, args.batch, args.seq, seed=tcfg.seed)
    it = prefetch(it)

    t0 = time.time()
    for i, batch in enumerate(it):
        if i >= args.steps:
            break
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt_state, m = step_fn(params, opt_state, batch)
        if i % args.log_every == 0 or i == args.steps - 1:
            dt = time.time() - t0
            print(f"step {i:5d} loss {float(m['loss']):8.4f} "
                  f"gnorm {float(m['grad_norm']):7.3f} "
                  f"lr {float(m['lr']):.2e} [{dt:6.1f}s]", flush=True)
    if args.ckpt:
        save_checkpoint(args.ckpt, params)
        print(f"saved checkpoint to {args.ckpt}")


if __name__ == "__main__":
    main()
