#!/usr/bin/env python3
"""Smoke run of the GSI serving path on a TPU (a smoke run, not a benchmark).

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the tensor-parallel fleet only

One chip, in one process, through the launcher's own functions
(``repro.launch.serve``):

  (a) device   — the first JAX device must be a TPU; otherwise exit 1
                 and print no result line;
  (b) kernels  — each main-path Pallas kernel (paged attention fp and
                 int8, flash attention, logprob gather) at the triple's
                 real widths, against its ``kernels/ref.py`` oracle in
                 float32 at the stated bf16 tolerance;
  (c) serving  — the one-chip triple at published widths in bf16 with
                 seeded random weights: draft qwen2.5-math-1.5b, target
                 qwen3-1.7b, PRM qwen2.5-math-1.5b + reward head.  16
                 seeded synthetic requests, paged KV with the prefix
                 cache, capacity 8, n = 4, max_seq 1024: first sampled
                 and async, then greedy sync and greedy async, whose
                 tokens must be identical.

``--four-chips`` runs only the multi-chip path and what it is compared
with: ``--replicas 2 --tp 2`` over four chips against two unsharded
single-device replicas (replica r on device r) on the same greedy
requests — tokens must be identical — and a check that each of the four
devices holds a share of the target's weights and KV.

Any failed phase exits non-zero.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
TRIPLE = ("qwen2.5-math-1.5b", "qwen3-1.7b", "qwen2.5-math-1.5b")
#: bf16 tolerance of a kernel against its float32 oracle: inputs are
#: bf16, the kernels accumulate in float32, outputs round to bf16
KERNEL_TOL = 2e-2
#: serving shapes, shared by the kernel and serving phases: CAPACITY
#: requests in flight x N branches each, MAX_SEQ tokens in PAGE-token
#: pages, NUM_PAGES allocatable pages.  256 pages is half the
#: dense-equivalent pool (8 x 64): any slot can still reach MAX_SEQ, not
#: all eight at once.  The full pool would put the step program at
#: ~15.8 GiB with its temporaries (compiled for v5e), over what one
#: 16 GiB chip can hand out.
CAPACITY, N, MAX_SEQ, PAGE, NUM_PAGES = 8, 4, 1024, 16, 256


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# (a) device
# ---------------------------------------------------------------------------

def require_tpu(min_count: int = 1) -> dict:
    from repro.launch.serve import device_report
    dev = device_report()
    check(dev["platform"] == "tpu",
          f"no TPU: JAX runs on {dev['platform']} ({dev['kind']})")
    check(dev["count"] >= min_count,
          f"need {min_count} TPU devices, JAX sees {dev['count']}")
    log(f"device: platform={dev['platform']} device_kind={dev['kind']} "
        f"count={dev['count']}")
    return dev


# ---------------------------------------------------------------------------
# (b) kernels
# ---------------------------------------------------------------------------

def _close(name: str, got, want) -> None:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(np.isfinite(got).all(), f"{name}: non-finite kernel output")
    err = float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))
    log(f"kernel {name}: max |err|/(1+|ref|) = {err:.3e} "
        f"(tolerance {KERNEL_TOL})")
    check(err <= KERNEL_TOL, f"{name}: error {err:.3e} > {KERNEL_TOL}")


def kernel_phase(cfgs, seed: int) -> None:
    """Each main-path kernel, through the platform dispatch of
    ``kernels.ops`` (on a TPU: the compiled Pallas kernel, never the
    reference), against its oracle run in float32 at the highest matmul
    precision, at the serving phase's shapes: one row per branch, a
    block table of MAX_SEQ / PAGE pages plus the trash column, a pool of
    NUM_PAGES pages (the engine adds its copy-on-write scratch and trash
    page on top) and MAX_SEQ-token prefills."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    def f32(*xs):
        return [x.astype(jnp.float32) for x in xs]

    def oracle(fn, *args, **kw):
        # only the oracle runs at the highest matmul precision: a bf16
        # dot inside a kernel cannot take a float32 contract precision
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)

    B, ps, P, S = CAPACITY * N, PAGE, NUM_PAGES, MAX_SEQ
    nblk = MAX_SEQ // PAGE + 1
    key = jax.random.PRNGKey(seed)
    for cfg in cfgs[:2]:                # draft (12/2) and target (16/8)
        H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        tag = f"{H}/{KV}x{hd}"
        ks = jax.random.split(jax.random.fold_in(key, H), 8)
        q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.bfloat16)
        kp = jax.random.normal(ks[1], (P, ps, KV, hd), jnp.bfloat16)
        vp = jax.random.normal(ks[2], (P, ps, KV, hd), jnp.bfloat16)
        pt = jax.random.randint(ks[3], (B, nblk), 0, P)
        pos = jax.random.randint(ks[4], (B,), 0, nblk * ps)
        _close(f"paged_attention[{tag}]",
               ops.paged_attention(q, kp, vp, pt, pos),
               oracle(ref.paged_attention_ref, *f32(q, kp, vp), pt, pos))

        codes_k = jax.random.randint(ks[5], (P, ps, KV, hd), -127, 128
                                     ).astype(jnp.int8)
        codes_v = jax.random.randint(ks[6], (P, ps, KV, hd), -127, 128
                                     ).astype(jnp.int8)
        sc = jax.random.uniform(ks[7], (2, P, KV), jnp.float32, 0.005, 0.02)
        _close(f"paged_attention_int8[{tag}]",
               ops.paged_attention_quant(q, codes_k, codes_v, sc[0], sc[1],
                                         pt, pos),
               oracle(ref.paged_attention_quant_ref, q.astype(jnp.float32),
                      codes_k, codes_v, sc[0], sc[1], pt, pos))

        qf = jax.random.normal(ks[0], (2, S, H, hd), jnp.bfloat16)
        kf = jax.random.normal(ks[1], (2, S, KV, hd), jnp.bfloat16)
        vf = jax.random.normal(ks[2], (2, S, KV, hd), jnp.bfloat16)
        _close(f"flash_attention[{tag}]", ops.flash_attention(qf, kf, vf),
               oracle(ref.flash_attention_ref, *f32(qf, kf, vf)))

    target = cfgs[1]
    d, V = target.d_model, target.vocab_size
    ks = jax.random.split(jax.random.fold_in(key, d), 3)
    h = jax.random.normal(ks[0], (B, 9, d), jnp.bfloat16)
    w = (jax.random.normal(ks[1], (d, V), jnp.float32) * 0.02
         ).astype(jnp.bfloat16)
    lab = jax.random.randint(ks[2], (B, 9), 0, V)
    want = oracle(ref.logprob_gather_ref, *f32(h, w), lab, V)
    check(np.isfinite(np.asarray(want)).all(), "logprob oracle non-finite")
    _close(f"logprob_gather[d={d},V={V}]",
           ops.logprob_gather(h, w, lab, V), want)


# ---------------------------------------------------------------------------
# (c) serving
# ---------------------------------------------------------------------------

class CompileClock:
    """Seconds the backend compiler spent, from JAX's own events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        self._event = "/jax/core/compile/backend_compile_duration"

        def on_event(event, duration, **_):
            if event == self._event:
                self.seconds += duration
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def take(self):
        out = (self.seconds, self.count)
        self.seconds, self.count = 0.0, 0
        return out


def requests(count: int, seed: int):
    from repro.data import SyntheticReasoningTask
    task = SyntheticReasoningTask(seed=seed)
    return task, [task.sample_problem() for _ in range(count)]


def serve_run(label, engines, task, problems, *, capacity, sync, seed,
              clock, vocab):
    """Serve ``problems`` once; check finish reasons, token range and
    rewards; return the tokens per request in submission order."""
    import jax
    import numpy as np
    from repro.launch.serve import evaluate_queued

    t0 = time.perf_counter()
    res = evaluate_queued(engines if len(engines) > 1 else engines[0],
                          task, problems, jax.random.PRNGKey(seed + 1),
                          capacity=capacity, sync=sync)
    wall = time.perf_counter() - t0
    comp_s, comp_n = clock.take()
    responses = [res["responses"][rid] for rid in res["ids"]]
    check(len(responses) == len(problems),
          f"{label}: {len(responses)} of {len(problems)} responses")
    tokens = []
    for resp in responses:
        check(resp.finish_reason != "",
              f"{label}: request {resp.request_id} has no finish reason")
        toks = resp.tokens
        check(((toks >= 0) & (toks < vocab)).all(),
              f"{label}: request {resp.request_id} has tokens outside "
              f"[0, {vocab})")
        tokens.append(toks)
    stats = res["stats"]
    rewards = [np.asarray(r, np.float32) for r in stats.raw_rewards]
    check(rewards and all(np.isfinite(r).all() for r in rewards),
          f"{label}: PRM rewards missing or non-finite")
    reasons = sorted({r.finish_reason for r in responses})
    log(f"serve {label}: {len(responses)} requests, tokens served="
        f"{res['tokens']}, accept_rate={res['accept_rate']:.3f}, "
        f"engine_steps={res['steps']}, finish={reasons}, "
        f"wall={wall:.1f}s incl. compile={comp_s:.1f}s "
        f"({comp_n} programs)")
    return tokens, [r.steps for r in responses]


def first_difference(a_steps, b_steps):
    """Index of the first reasoning step at which two responses differ."""
    import numpy as np
    for i, (x, y) in enumerate(zip(a_steps, b_steps)):
        if not np.array_equal(np.asarray(x), np.asarray(y)):
            return i
    return min(len(a_steps), len(b_steps))


def compare(label, ref, got) -> None:
    (ref_tok, ref_steps), (got_tok, got_steps) = ref, got
    bad = [i for i, (x, y) in enumerate(zip(ref_tok, got_tok))
           if not (x.shape == y.shape and (x == y).all())]
    for i in bad:
        log(f"{label}: request {i} differs from reasoning step "
            f"{first_difference(ref_steps[i], got_steps[i])}")
    check(not bad, f"{label}: {len(bad)} of {len(ref_tok)} requests "
                   f"differ")
    log(f"{label}: tokens identical on all {len(ref_tok)} requests")


def memory_line() -> None:
    import jax
    for d in jax.devices():
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            log(f"memory {d}: peak_bytes_in_use={st['peak_bytes_in_use']} "
                f"bytes_limit={st.get('bytes_limit', 'n/a')}")


def serving_phase(cfgs, seed: int, clock) -> None:
    import gc
    from repro.config import GSIConfig
    from repro.launch.serve import build_engines, init_triple, param_bytes

    t0 = time.perf_counter()
    params = init_triple(cfgs, seed)
    for cfg, p in zip(cfgs, params):
        log(f"params {cfg.name}{' +reward head' if cfg.reward_head else ''}"
            f": {param_bytes(p)} bytes ({cfg.param_dtype})")
    log(f"init from seed {seed}: {time.perf_counter() - t0:.1f}s")
    task, problems = requests(16, seed)
    vocab = cfgs[1].vocab_size
    g = GSIConfig(n=N, max_step_tokens=8, max_steps=8)
    kw = dict(mode="gsi", max_seq=MAX_SEQ, paged=True, page_size=PAGE,
              num_pages=NUM_PAGES, prefix_cache=True)

    engines = build_engines(cfgs, params, g, **kw)
    check(engines[0].prefix_cache, "prefix cache is off")
    run = dict(capacity=CAPACITY, seed=seed, clock=clock, vocab=vocab)
    serve_run("sampled/async/paged+prefix", engines, task, problems,
              sync=False, **run)
    memory_line()
    del engines
    gc.collect()

    greedy = dataclasses.replace(g, temperature=0.0)
    engines = build_engines(cfgs, params, greedy, **kw)
    sync = serve_run("greedy/sync", engines, task, problems, sync=True,
                     **run)
    asyn = serve_run("greedy/async", engines, task, problems, sync=False,
                     **run)
    compare("greedy async vs sync", sync, asyn)
    calls = kernel_calls_in_step(engines[0], CAPACITY)
    log(f"engine decode step holds {calls} Pallas kernel call(s)")
    check(calls > 0, "the engine's decode step runs no Pallas kernel")
    memory_line()


def kernel_calls_in_step(engine, capacity: int) -> int:
    """Pallas calls (``tpu_custom_call``) in the lowered decode step."""
    import jax
    key = jax.random.PRNGKey(0)
    lowered = jax.jit(engine._decode_core).lower(
        engine.params, engine.fresh_state(capacity), key, key)
    return lowered.as_text().count("tpu_custom_call")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def shard_bytes_by_device(tree) -> dict:
    """device id -> bytes of ``tree``'s shards on it, and whether any
    leaf is split (a shard smaller than its array)."""
    import jax
    out, split = {}, False
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
            split |= sh.data.shape != leaf.shape
    return out, split


def four_chip_phase(cfgs, seed: int, clock) -> None:
    import gc
    import jax
    from repro.config import GSIConfig
    from repro.launch.serve import build_engines, init_triple

    # weights on the host, so that no device holds a stray full copy
    # next to the sharded one
    params = jax.device_get(init_triple(cfgs, seed))
    task, problems = requests(16, seed)
    vocab = cfgs[1].vocab_size
    g = GSIConfig(n=N, max_step_tokens=8, max_steps=8, temperature=0.0)
    kw = dict(mode="gsi", max_seq=MAX_SEQ, paged=True, page_size=PAGE,
              prefix_cache=True)

    engines = build_engines(cfgs, params, g, replicas=2, **kw)
    log("reference: 2 unsharded replicas on devices "
        f"{[e.device.id for e in engines]}")
    ref = serve_run("greedy/unsharded x2", engines, task, problems,
                    capacity=4, sync=False, seed=seed, clock=clock,
                    vocab=vocab)
    del engines
    gc.collect()

    engines = build_engines(cfgs, params, g, replicas=2, mesh_shape=(1, 2),
                            **kw)
    del params
    gc.collect()
    tp = serve_run("greedy/tp2 x2", engines, task, problems, capacity=4,
                   sync=False, seed=seed, clock=clock, vocab=vocab)
    compare("tp2 x2 vs unsharded x2", ref, tp)

    # where each replica puts its target weights and a state's target KV
    weights, kv, split_w, split_kv = {}, {}, False, False
    for eng in engines:
        w, sw = shard_bytes_by_device(eng.params[1])
        for dev, n in w.items():
            weights[dev] = weights.get(dev, 0) + n
        split_w |= sw
        c, sc = shard_bytes_by_device(eng.fresh_state(4)["caches"]["B"])
        for dev, n in c.items():
            kv[dev] = kv.get(dev, 0) + n
        split_kv |= sc
    log(f"target weight bytes by device: {dict(sorted(weights.items()))}")
    log(f"target KV bytes by device: {dict(sorted(kv.items()))}")
    ids = {d.id for d in jax.devices()[:4]}
    check(split_w and split_kv, "target weights or KV are not sharded")
    check(ids <= {d for d, n in weights.items() if n > 0},
          "a device holds no target weights")
    check(ids <= {d for d, n in kv.items() if n > 0},
          "a device holds no target KV")
    memory_line()


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip tensor-parallel fleet and "
                         "its unsharded reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))
    try:
        try:
            from repro.launch.compile_cache import enable_compile_cache
            from repro.launch.serve import resolve_triple
        except ImportError as e:
            raise SmokeFailure(f"the repo's sources are not next to this "
                               f"script ({e})") from e
        dev = require_tpu(4 if args.four_chips else 1)
        log("a smoke run, not a benchmark: times include compilation")
        log(f"compile cache: {enable_compile_cache()}")
        cfgs = resolve_triple(*TRIPLE)
        clock = CompileClock()
        if args.four_chips:
            four_chip_phase(cfgs, args.seed, clock)
        else:
            t0 = time.perf_counter()
            kernel_phase(cfgs, args.seed)
            log(f"kernels: {time.perf_counter() - t0:.1f}s "
                f"(compile {clock.take()[0]:.1f}s)")
            serving_phase(cfgs, args.seed, clock)
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
