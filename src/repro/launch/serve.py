"""GSI serving launcher: build a draft/target/PRM triple, then serve
queued requests through the continuous-batching scheduler and report
accuracy / acceptance / throughput / latency numbers.

    PYTHONPATH=src python -m repro.launch.serve --requests 16 --n 4 \
        --method gsi --capacity 8 [--train-steps 300] \
        [--paged --replicas 2 --router affinity] [--sync | --async] \
        [--mesh-shape 1x2 | --tp 2]

By default the triple is the toy one (vocab 16), trained on the
synthetic reasoning task first — the CPU configuration of the tests.
``--draft/--target/--prm NAME`` serve registered configs at their
published widths instead (e.g. ``qwen2.5-math-1.5b`` / ``qwen3-1.7b`` /
``qwen2.5-math-1.5b``; the PRM gets a reward head), with seeded random
weights from ``--seed`` and no training; ``--max-seq`` sizes the KV
cache.  Start-up prints the platform, device kind and device count.

``--replicas N`` serves through N data-parallel replicas (one engine,
page pool and radix index each) behind the preamble-affinity router.
``--mesh-shape DxM`` (or ``--tp M``) additionally carves the visible
devices into one disjoint submesh per replica and runs each replica's
*target* model tensor-parallel over the submesh's ``model`` axis
(draft and PRM stay replicated); tokens are bit-identical to the
unsharded engine.
Serving is asynchronous by default (``--async``): each scheduler keeps
one decode step in flight and overlaps harvest/admission with device
execution, and replicas are driven by a thread-per-replica fleet loop;
``--sync`` selects the lock-step loop (bit-identical tokens).  See
docs/SERVING.md for the full flag reference.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import numpy as np

from repro.config import GSIConfig, ModelConfig, TrainConfig, get_config
from repro.data import SyntheticReasoningTask
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import carve_submeshes
from repro.models import build_model
from repro.serving import GSIScheduler, GSIServingEngine, ReplicaRouter
from repro.serving.router import HASH_TIERS, POLICIES
from repro.train import Trainer


#: XLA / allocator environment tuning: step markers at the outer while
#: loop so profiles attribute whole decode steps, no up-front
#: preallocation, and quiet allocator large-alloc warnings.
#: ``setdefault`` semantics — anything the operator already exported
#: wins.  Flags for the TPU runtime belong in ``LIBTPU_INIT_ARGS``, which
#: a command appends to and never overwrites.
TUNED_ENV = {
    "XLA_FLAGS": "--xla_step_marker_location="
                 "STEP_MARK_AT_TOP_LEVEL_WHILE_LOOP",
    "XLA_PYTHON_CLIENT_PREALLOCATE": "false",
    "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000",
    "TF_CPP_MIN_LOG_LEVEL": "4",
}


def apply_tuned_env(env=None) -> dict:
    """Apply :data:`TUNED_ENV` to ``os.environ`` (or ``env``) and return
    the settings actually applied (operator-exported values win).

    Must run before the first ``import jax`` *use* touches a backend —
    XLA reads these at client construction, so ``--tuned-env`` applies
    them at the very top of ``main`` and prints the result.
    """
    target = os.environ if env is None else env
    applied = {}
    for key, val in TUNED_ENV.items():
        if target.setdefault(key, val) == val:
            applied[key] = val
    return applied


def device_report() -> dict:
    """The devices this process runs on, as JAX reports them."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def parse_mesh_shape(text: str):
    """Parse ``"DxM"`` (e.g. ``1x2``) into a ``(data, model)`` tuple.

    ``--tp N`` is shorthand for ``--mesh-shape 1xN``; both feed
    :func:`repro.launch.mesh.carve_submeshes`, which slices the visible
    devices into one disjoint submesh per replica.
    """
    parts = text.lower().split("x")
    if len(parts) != 2:
        raise ValueError(f"--mesh-shape wants DxM (e.g. 1x2), got {text!r}")
    data, model = (int(p) for p in parts)
    if data < 1 or model < 1:
        raise ValueError(f"--mesh-shape axes must be >= 1, got {text!r}")
    return data, model


def toy_triple(vocab: int = 16):
    """Small draft / larger target / PRM configs for the synthetic task."""
    draft = ModelConfig(
        name="sx-draft", family="dense", num_layers=2, d_model=64,
        num_heads=4, num_kv_heads=2, d_ff=192, vocab_size=vocab, head_dim=16,
        dtype="float32", param_dtype="float32")
    target = dataclasses.replace(draft, name="sx-target", num_layers=4,
                                 d_model=160, head_dim=40, d_ff=448)
    prm = dataclasses.replace(target, name="sx-prm", reward_head=True)
    return draft, target, prm


def resolve_triple(draft: str, target: str, prm: str):
    """Registered configs by name; the PRM config gets a reward head
    (the same architecture plus the scalar head, as in :func:`toy_triple`).
    All three must share one vocabulary."""
    cfgs = (get_config(draft), get_config(target),
            dataclasses.replace(get_config(prm), reward_head=True))
    vocabs = {c.vocab_size for c in cfgs}
    if len(vocabs) != 1:
        raise ValueError(f"draft/target/PRM must share a vocabulary, got "
                         f"{[c.vocab_size for c in cfgs]}")
    return cfgs


def init_triple(cfgs, seed: int):
    """Seeded random weights for each config (model i from
    ``fold_in(key(seed), i)``), materialized directly on the first
    device in each config's ``param_dtype``.

    The key is an RBG key: XLA's bit generator compiles the init of a
    published-width model in seconds, where threefry takes most of a
    minute per model."""
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    key = jax.random.key(seed, impl="rbg")
    return tuple(
        jax.jit(build_model(cfg).init, out_shardings=sharding)(
            jax.random.fold_in(key, i))
        for i, cfg in enumerate(cfgs))


def param_bytes(params) -> int:
    """Bytes held by one parameter tree."""
    return sum(int(a.size) * a.dtype.itemsize
               for a in jax.tree.leaves(params))


def build_engines(cfgs, params, gcfg, *, replicas: int = 1,
                  mesh_shape=None, **engine_kw):
    """One :class:`GSIServingEngine` per replica over ``params``.

    With ``mesh_shape`` the visible devices are carved into one
    ``(data, model)`` submesh per replica and each replica's target runs
    tensor-parallel over it.  Without one, replica r lives on device
    ``r mod count``, so replicas spread over the chips instead of
    stacking on the first.  ``engine_kw`` goes to every engine.
    """
    draft_cfg, target_cfg, prm_cfg = cfgs
    devices = jax.devices()
    if mesh_shape is not None:
        submeshes = carve_submeshes(replicas, mesh_shape)
        print(f"mesh: {replicas} replica(s) x "
              f"{mesh_shape[0]}x{mesh_shape[1]} (data x model) submesh "
              f"over {len(devices)} visible device(s)", flush=True)
        place = [dict(mesh=m) for m in submeshes]
    else:
        place = [dict(device=devices[r % len(devices)])
                 for r in range(replicas)]
    return [GSIServingEngine(draft_cfg, target_cfg, prm_cfg, *params, gcfg,
                             **engine_kw, **place[r])
            for r in range(replicas)]


def train_triple(task, draft_cfg, target_cfg, prm_cfg, *, steps_draft=200,
                 steps_target=600, batch=32, seq=64, seed=0):
    """Target trained longer => genuinely stronger than the draft."""
    tc = TrainConfig(learning_rate=1e-3, total_steps=steps_target,
                     warmup_steps=20, seed=seed)
    tr_s = Trainer(draft_cfg, dataclasses.replace(tc,
                                                  total_steps=steps_draft))
    tr_s.fit((task.lm_batch(batch, seq) for _ in iter(int, 1)), steps_draft)
    tr_b = Trainer(target_cfg, tc)
    tr_b.fit((task.lm_batch(batch, seq) for _ in iter(int, 1)), steps_target)
    tr_p = Trainer(prm_cfg, tc, prm=True)
    tr_p.fit((task.prm_batch(batch, seq) for _ in iter(int, 1)),
             steps_target)
    return tr_s.params, tr_b.params, tr_p.params


def evaluate(engine, task, problems, rng):
    """Fixed-batch evaluation through ``engine.run`` (one gang)."""
    Lp = max(len(p.prompt) for p in problems)
    prompts = np.zeros((len(problems), Lp), np.int32)
    for i, p in enumerate(problems):
        prompts[i, :len(p.prompt)] = p.prompt
    t0 = time.time()
    responses, stats = engine.run(prompts, rng)
    wall = time.time() - t0
    correct = 0
    for prob, steps in zip(problems, responses):
        flat = [t for s in steps for t in s]
        correct += task.is_correct(prob, flat)
    return {"accuracy": correct / len(problems),
            "accept_rate": stats.accept_rate, "steps": stats.steps,
            "wall_s": wall, "stats": stats}


def make_frontend(engines, *, capacity: int, continuous: bool = True,
                  collect_stats: bool = False, policy: str = "affinity",
                  sync: bool = True, hash_tier: str = "mod",
                  chunk_tokens: int = 0):
    """One serving frontend over one or many engines.

    A single engine (or a 1-list) gets a plain :class:`GSIScheduler`;
    a list of N > 1 engines gets a :class:`ReplicaRouter` fronting N
    replicas of ``capacity`` slots each, routed by ``policy`` (tier-2
    preamble hashing per ``hash_tier``).  ``sync=False`` selects the
    pipelined decode loop (and, for routers, the thread-per-replica
    fleet loop); ``chunk_tokens`` meters prompt prefill (chunked
    prefill, 0 = unmetered).  Both frontends expose the same
    submit()/run()/stats/prefix_stats()/pipeline_stats() surface.
    """
    if isinstance(engines, GSIServingEngine):
        engines = [engines]
    if len(engines) == 1:
        return GSIScheduler(engines[0], capacity=capacity,
                            continuous=continuous,
                            collect_stats=collect_stats, sync=sync,
                            chunk_tokens=chunk_tokens)
    return ReplicaRouter(engines, capacity=capacity, policy=policy,
                         continuous=continuous,
                         collect_stats=collect_stats, sync=sync,
                         threaded=not sync, hash_tier=hash_tier,
                         chunk_tokens=chunk_tokens)


def _frontend_schedulers(sched):
    """The per-engine schedulers behind a frontend (router or single)."""
    if isinstance(sched, ReplicaRouter):
        return [rep.scheduler for rep in sched.replicas]
    return [sched]


def load_frontend_cache(sched, cache_dir: str) -> int:
    """Warm-restart a frontend from ``cache_dir`` snapshots.

    Loads ``cache-r{i}.npz`` (written by :func:`save_frontend_cache`)
    into replica ``i``'s state through the engine's snapshot codec —
    restored radix subtrees serve their first requests from spliced KV
    pages instead of a cold prefill.  Missing files are skipped (a
    replica added since the last save simply starts cold).  Returns the
    number of replicas restored.
    """
    loaded = 0
    for i, s in enumerate(_frontend_schedulers(sched)):
        path = os.path.join(cache_dir, f"cache-r{i}.npz")
        if not os.path.exists(path):
            continue
        s.state = s.engine.load_cache(s.state, path)
        loaded += 1
    return loaded


def save_frontend_cache(sched, cache_dir: str) -> int:
    """Persist every replica's hot radix cache to ``cache_dir``.

    One ``cache-r{i}.npz`` per replica (engines without a live prefix
    cache are skipped).  Returns the number of snapshots written.
    """
    os.makedirs(cache_dir, exist_ok=True)
    saved = 0
    for i, s in enumerate(_frontend_schedulers(sched)):
        eng = s.engine
        if not getattr(eng, "paged", False) or not eng.prefix_cache:
            continue
        eng.save_cache(s.state, os.path.join(cache_dir, f"cache-r{i}.npz"))
        saved += 1
    return saved


def evaluate_queued(engine, task, problems, rng, *, capacity: int,
                    continuous: bool = True, policy: str = "affinity",
                    sync: bool = True, hash_tier: str = "mod",
                    chunk_tokens: int = 0, priority_every: int = 0,
                    deadline_s=None, stream=None, cache_dir: str = ""):
    """Queued evaluation through the continuous-batching scheduler.

    All requests are submitted up front (offered load >= capacity); the
    scheduler packs them onto ``capacity`` slots, re-admitting queued
    prompts into freed slots.  ``engine`` may also be a list of engines —
    one per data-parallel replica, fronted by a :class:`ReplicaRouter`
    with ``policy`` placement.  ``sync=False`` serves through the async
    pipeline (identical tokens, overlapped host work).

    ``priority_every=k`` submits every k-th request at priority 1 (with
    ``deadline_s`` as its SLO), arming preemption; ``stream`` attaches a
    token-stream callback to the first request.  ``cache_dir`` enables
    warm restarts: per-replica radix-cache snapshots are loaded from it
    before serving (if present) and saved back after the run.  Returns
    accuracy plus throughput/latency, and the request ids in submission
    order (``ids``; ``responses`` is keyed by id in finish order).
    """
    sched = make_frontend(engine, capacity=capacity, continuous=continuous,
                          collect_stats=True, policy=policy, sync=sync,
                          hash_tier=hash_tier, chunk_tokens=chunk_tokens)
    if cache_dir:
        warm = load_frontend_cache(sched, cache_dir)
        print(f"cache-dir {cache_dir}: restored {warm} replica "
              f"snapshot(s)", flush=True)
    ids = []
    for i, p in enumerate(problems):
        hi = bool(priority_every) and i % priority_every == 0
        ids.append(sched.submit(np.asarray(p.prompt, np.int32),
                                priority=1 if hi else 0,
                                deadline_s=deadline_s if hi else None,
                                stream=stream if i == 0 else None))
    t0 = time.time()
    results = sched.run(rng)
    wall = time.time() - t0
    if cache_dir:
        saved = save_frontend_cache(sched, cache_dir)
        print(f"cache-dir {cache_dir}: saved {saved} replica "
              f"snapshot(s)", flush=True)
    correct, tokens = 0, 0
    latencies = []
    for prob, rid in zip(problems, ids):
        resp = results[rid]
        correct += task.is_correct(prob, list(resp.tokens))
        tokens += resp.num_tokens
        latencies.append(resp.latency)
    lat = np.sort(np.asarray(latencies)) if latencies else np.zeros(1)
    ttft = [results[r].ttft for r in ids
            if not np.isnan(results[r].ttft)]
    return {"accuracy": correct / len(problems),
            "accept_rate": sched.stats.accept_rate,
            "steps": sched.engine_steps, "wall_s": wall,
            "tokens": tokens, "tokens_per_s": tokens / max(wall, 1e-9),
            "latency_p50": float(np.percentile(lat, 50)),
            "latency_p95": float(np.percentile(lat, 95)),
            "ttft_p50": float(np.percentile(ttft, 50)) if ttft else 0.0,
            "preemptions": sched.stats.preemptions,
            "deadline_misses": sched.stats.deadline_misses,
            "prefill_commit_max": sched.stats.prefill_commit_max,
            "prefix": sched.prefix_stats(),
            "pipeline": sched.pipeline_stats(),
            "stats": sched.stats, "responses": results, "ids": ids}


def main() -> None:
    """CLI entry point (see module docstring for usage)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--n", type=int, default=4)
    ap.add_argument("--method", default="gsi",
                    choices=["gsi", "gsi_norej", "rsd", "sbon_s", "sbon_b"])
    ap.add_argument("--beta", type=float, default=20.0)
    ap.add_argument("--u", type=float, default=0.5)
    ap.add_argument("--train-steps", type=int, default=400)
    ap.add_argument("--capacity", type=int, default=0,
                    help="scheduler slots (0 = half the request count)")
    ap.add_argument("--gang", action="store_true",
                    help="fixed-batch gang scheduling instead of "
                         "continuous batching")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV-cache (page pools + copy-on-write "
                         "candidate branching) instead of dense rows")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=0,
                    help="page pool size (0 = dense-equivalent capacity)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable the radix prefix cache (cross-request "
                         "KV sharing; on by default for --paged)")
    ap.add_argument("--kv-dtype", default="fp",
                    choices=["fp", "bf16", "int8", "fp8"],
                    help="paged KV-page storage format (requires --paged): "
                         "fp keeps the activation dtype; int8/fp8 store "
                         "quantized codes with per-page scales, dequant "
                         "fused into the paged-attention kernel")
    ap.add_argument("--quantize-draft", action="store_true",
                    help="round the draft model's matmul weights through "
                         "int8 (per-channel scales) at engine load")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel serving replicas (each gets its "
                         "own engine, page pool and radix index; "
                         "capacity is per replica)")
    ap.add_argument("--mesh-shape", default="", metavar="DxM",
                    help="per-replica device submesh shape as "
                         "data x model (e.g. 1x2 = 2-way tensor "
                         "parallelism); carves the visible devices into "
                         "one disjoint submesh per replica and shards "
                         "each target model over its 'model' axis")
    ap.add_argument("--tp", type=int, default=0, metavar="N",
                    help="shorthand for --mesh-shape 1xN (N-way tensor "
                         "parallelism per replica)")
    ap.add_argument("--router", default="affinity", choices=list(POLICIES),
                    help="replica placement policy (preamble-affinity "
                         "keeps shared-prefix requests on one replica)")
    ap.add_argument("--hash-tier", default="mod", choices=list(HASH_TIERS),
                    help="affinity tier-2 preamble hash: mod (blake2b "
                         "mod N) or rendezvous (adding a replica remaps "
                         "only ~1/N of preamble groups)")
    grp = ap.add_mutually_exclusive_group()
    grp.add_argument("--async", dest="sync", action="store_false",
                     help="pipelined serving (default): one step ticket "
                          "in flight, harvest/admission overlap device "
                          "decode; thread-per-replica fleet loop")
    grp.add_argument("--sync", dest="sync", action="store_true",
                     help="lock-step serving loop (identical tokens)")
    ap.set_defaults(sync=False)
    ap.add_argument("--chunk-tokens", type=int, default=0,
                    help="per-step prefill token budget (chunked "
                         "prefill; 0 = admit whole prompts at once)")
    ap.add_argument("--priority", type=int, default=0, metavar="K",
                    help="submit every K-th request at priority 1 "
                         "(arms preemption of priority-0 slots under "
                         "pressure; 0 = uniform priority)")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="SLO deadline (seconds, arrival->finish) "
                         "attached to the priority-1 requests")
    ap.add_argument("--stream", action="store_true",
                    help="print the first request's tokens as they are "
                         "harvested (per-step streaming callback)")
    ap.add_argument("--cache-dir", default="",
                    help="warm-restart directory: per-replica radix "
                         "cache snapshots (cache-rN.npz) are restored "
                         "from here before serving and saved back after "
                         "(requires --paged with the prefix cache on)")
    ap.add_argument("--tuned-env", action="store_true",
                    help="apply the XLA/allocator env tuning "
                         "(XLA_FLAGS step markers, no preallocation) "
                         "before serving and print what was applied")
    ap.add_argument("--draft", default="",
                    help="registered draft config (with --target/--prm: "
                         "serve published widths from seeded random "
                         "weights; default: the trained toy triple)")
    ap.add_argument("--target", default="",
                    help="registered target config")
    ap.add_argument("--prm", default="",
                    help="registered PRM base config (a reward head is "
                         "added)")
    ap.add_argument("--max-seq", type=int, default=128,
                    help="per-request KV capacity in tokens")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.tuned_env:
        applied = apply_tuned_env()
        for key in sorted(TUNED_ENV):
            mark = "applied" if key in applied else "kept"
            print(f"tuned-env [{mark}] {key}={os.environ[key]}",
                  flush=True)

    enable_compile_cache()
    dev = device_report()
    print(f"device: platform={dev['platform']} "
          f"device_kind={dev['kind']} count={dev['count']}", flush=True)

    task = SyntheticReasoningTask(seed=args.seed)
    names = (args.draft, args.target, args.prm)
    if any(names):
        if not all(names):
            raise SystemExit("--draft, --target and --prm go together")
        cfgs = resolve_triple(*names)
        print(f"initialising {'/'.join(names)} from seed {args.seed} "
              f"(published widths, random weights) ...", flush=True)
        params = init_triple(cfgs, args.seed)
    else:
        cfgs = toy_triple()
        print("training draft/target/PRM triple ...", flush=True)
        params = train_triple(task, *cfgs,
                              steps_draft=args.train_steps // 2,
                              steps_target=args.train_steps,
                              seed=args.seed)

    g = GSIConfig(n=args.n, beta=args.beta, threshold_u=args.u,
                  max_step_tokens=8, max_steps=8)
    capacity = args.capacity or max(1, args.requests // 2)
    if args.replicas > 1:
        # per-replica capacity so --replicas scales the fleet, not the
        # footprint of each engine
        capacity = max(1, capacity // args.replicas)
    kv_dtype = None if args.kv_dtype == "fp" else args.kv_dtype
    if args.mesh_shape and args.tp:
        raise SystemExit("use --mesh-shape or --tp, not both")
    mesh_shape = None
    if args.mesh_shape:
        mesh_shape = parse_mesh_shape(args.mesh_shape)
    elif args.tp > 1:
        mesh_shape = (1, args.tp)
    engines = build_engines(
        cfgs, params, g, replicas=args.replicas, mesh_shape=mesh_shape,
        mode=args.method, max_seq=args.max_seq, paged=args.paged,
        page_size=args.page_size, num_pages=args.num_pages,
        prefix_cache=not args.no_prefix_cache, kv_dtype=kv_dtype,
        quantize_draft=args.quantize_draft)
    engine = engines[0]
    problems = [task.sample_problem() for _ in range(args.requests)]

    def _print_stream(event):
        tag = f"[{event.finish_reason}]" if event.final \
            else " ".join(map(str, event.tokens.tolist()))
        print(f"stream {event.request_id} step {event.step}: {tag}",
              flush=True)

    res = evaluate_queued(engines if args.replicas > 1 else engine,
                          task, problems,
                          jax.random.PRNGKey(args.seed + 1),
                          capacity=capacity, continuous=not args.gang,
                          policy=args.router, sync=args.sync,
                          hash_tier=args.hash_tier,
                          chunk_tokens=args.chunk_tokens,
                          priority_every=args.priority,
                          deadline_s=args.deadline or None,
                          stream=_print_stream if args.stream else None,
                          cache_dir=args.cache_dir)
    if args.priority or args.chunk_tokens:
        print(f"slo: preemptions={res['preemptions']} "
              f"deadline_misses={res['deadline_misses']} "
              f"prefill_commit_max={res['prefill_commit_max']} "
              f"ttft_p50={res['ttft_p50']*1e3:.0f}ms", flush=True)
    if args.paged:
        rep = engine.cache_memory_report(capacity)
        print(f"paged cache [{rep['kv_dtype']}]: {rep['num_pages']} pages "
              f"x {rep['bytes_per_page']} B "
              f"(+{rep['scale_bytes_per_page']} B scales, "
              f"fp page {rep['fp_bytes_per_page']} B); "
              f"capacity {rep['capacity_tokens']} tokens / "
              f"{rep['capacity_bytes']>>10} KiB; branch scratch "
              f"{rep['paged_branch_bytes']>>10} KiB vs dense "
              f"{rep['dense_branch_bytes']>>10} KiB "
              f"({rep['branch_reduction']:.1f}x); "
              f"peak assigned {rep.get('pages_peak', 0)} pages")
        if rep["devices"] > 1:
            print(f"  sharded over {rep['devices']} devices: "
                  f"{rep['bytes_per_device']>>10} KiB/device "
                  f"({rep['capacity_tokens_per_device']} tokens/device "
                  f"at target-KV parity)")
        px = res["prefix"]
        print(f"prefix cache: hit_rate={px['hit_rate']:.2f} "
              f"prefill_tokens_skipped={px['hit_tokens']} "
              f"pages_reused={px['pages_reused']} "
              f"evicted={px['pages_evicted']} cached={px['pages_cached']}")
        if args.replicas > 1:
            for i, p in enumerate(px.get("per_replica", [])):
                print(f"  replica {i} ({args.router}): "
                      f"hit_rate={p['hit_rate']:.2f} "
                      f"({p['hits']}/{p['queries']} admissions) "
                      f"prefill_tokens={p['prefill_tokens']}")
    if not args.sync:
        pipe = res["pipeline"]
        print(f"async pipeline: overlap_fraction="
              f"{pipe['overlap_fraction']:.2f} "
              f"overlap_host={pipe['overlap_host_s']*1e3:.0f}ms "
              f"serial_host={pipe['serial_host_s']*1e3:.0f}ms "
              f"materialize_wait={pipe['materialize_wait_s']*1e3:.0f}ms")
    print(f"method={args.method} n={args.n} capacity={capacity} "
          f"({'async' if not args.sync else 'sync'}, "
          f"{'gang' if args.gang else 'continuous'}"
          f"{', paged' if args.paged else ''}"
          f"{f', {args.replicas} replicas/{args.router}' if args.replicas > 1 else ''}): "
          f"accuracy={res['accuracy']:.3f} "
          f"accept={res['accept_rate']:.2f} steps={res['steps']} "
          f"wall={res['wall_s']:.1f}s tokens/s={res['tokens_per_s']:.1f} "
          f"p50={res['latency_p50']*1e3:.0f}ms "
          f"p95={res['latency_p95']*1e3:.0f}ms")


if __name__ == "__main__":
    main()
