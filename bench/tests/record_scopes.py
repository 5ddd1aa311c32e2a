"""Record ``data/scopes.xplane.pb`` on one TPU: a jitted ``_decode_core``
with the engine's phase scopes, run inside a ``window`` span.

    python3 -m bench.tests.record_scopes bench/tests/data/scopes.xplane.pb

The program: ``gsi.draft`` (one matmul), ``gsi.verify`` with a child
``prm`` (a matmul and a reduction), a ``lax.cond`` whose taken branch
opens ``gsi.fallback`` (two matmuls), and ``gsi.commit`` with a child
``draft`` (a matmul).  Both branches are compiled before the trace starts;
in the window the program runs four times, the fallback branch on the
first and third run only, with host sleeps between.
"""
from __future__ import annotations

import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

N = 512


def _decode_core(x, w, run_fallback):
    with jax.named_scope("gsi.draft"):
        h = jnp.tanh(x @ w)
    with jax.named_scope("gsi.verify"), jax.named_scope("prm"):
        r = jnp.max(h @ w, axis=-1)

    def fallback(h):
        with jax.named_scope("gsi.fallback"):
            return jnp.tanh((h @ w) @ w)

    h = jax.lax.cond(run_fallback, fallback, lambda h: h, h)
    with jax.named_scope("gsi.commit"), jax.named_scope("draft"):
        return (h @ w).astype(jnp.float32).sum() + r.sum()


def main(out: str) -> None:
    step = jax.jit(_decode_core)
    key = jax.random.key(0)
    x = jax.random.normal(key, (N, N), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1), (N, N),
                          jnp.bfloat16) / N ** 0.5
    for flag in (True, False):
        step(x, w, flag).block_until_ready()
    directory = tempfile.mkdtemp(prefix="scopes-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0       # keeps the file small
    jax.profiler.start_trace(directory, profiler_options=options)
    with TraceAnnotation("window"):
        for flag in (True, False, True, False):
            step(x, w, flag).block_until_ready()
            time.sleep(0.002)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{directory}/**/*.xplane.pb",
                            recursive=True))[-1]
    shutil.copyfile(path, out)
    shutil.rmtree(directory, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
