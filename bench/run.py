"""One run of one cell of ``BENCHMARK.json``.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

In order: find a TPU with as many chips as the cell asks for (else exit
non-zero with no result); turn on JAX's compilation cache in the
checkout; make the weights on each chip from the configuration's fixed
``weights_seed``; build the engines and the serving frontend the program
offers (``repro.launch.serve.make_frontend``, async pipeline); warm the
shapes the cell's traffic uses and start its clients; then measure for
``--seconds`` while the clients' requests flow.  The run stops at the
window's end without draining, and is refused (non-zero exit, no result)
if any program was compiled or loaded inside the window.  Afterwards it
reads device memory, frees the program's state and checks a sample of
what was served against the plain reference (``bench/check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, ``breakdown`` (traced
runs) and, last, ``checks``: each number compared beside its limit.  The
same numbers end standard error.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

import numpy as np  # noqa: E402

from bench import arch  # noqa: E402
from bench.errors import Refused  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
FILL_STEPS = 4          # engine steps between the clients' start and the window
TRACE_SECONDS = 8.0     # the traced part of a --trace 1 window lasts at
TRACE_STEPS = 3         # least this long and holds this many engine steps
SAMPLE = 8              # requests compared with the reference per run
TABLE_BLOCKS = 4        # table entries per slot that set-up warms


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# The cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    calib: dict            # bench/cells/<name>.json: threshold_u, limits
    end_to_end: list       # names of the end-to-end metrics it reports
    per_layer: list        # names of the per-layer metrics it reports
    units: dict


def _read(path: Path) -> dict:
    if not path.is_file():
        raise Refused(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _read(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _read(root / cfg_entry["file"])
    traffic = _read(root / "bench" / "traffic" / f"{w['traffic']}.json")
    calib_path = root / "bench" / "cells" / f"{name}.json"
    calib = json.loads(calib_path.read_text()) if calib_path.is_file() \
        else {}

    def here(metric):
        return name in metric.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if here(m)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if here(m) and m["moves"] in reported]
    units = {m["name"]: m["unit"] for m in e2e + layer}
    return Cell(name, int(w["chips"]), config, traffic, calib,
                [m["name"] for m in e2e], [m["name"] for m in layer], units)


def require_chips(count: int):
    """The TPU devices this run uses; Refused without enough of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < count:
        raise Refused(f"the cell needs {count} chips, JAX sees {len(devs)}")
    return devs[:count]


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------

def model_config(role: str, m: dict, dtype: str):
    """The program's configuration of one model, from its architecture."""
    return arch.module(m).program(role, m, dtype)


class CompileClock:
    """Programs compiled or loaded from the persistent cache, from JAX's
    own monitoring events: ``count`` and ``seconds`` of backend compiles
    (a cache load is timed as one), ``misses`` of the cache (real
    compiles)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax
        self.seconds, self.count, self.misses = 0.0, 0, 0

        def on_duration(event, duration, **_):
            if event == self.EVENT:
                self.seconds += duration
                self.count += 1

        def on_event(event, **_):
            if event == self.MISS:
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def take(self):
        out = (self.seconds, self.count, self.misses)
        self.seconds, self.count, self.misses = 0.0, 0, 0
        return out


def counters(schedulers) -> dict:
    """The program's counters, summed over replicas."""
    out = {k: 0.0 for k in ("accepted", "decisions", "serial_host_s",
                            "engine_steps")}
    for s in schedulers:
        out["accepted"] += s.stats.accepted
        out["decisions"] += s.stats.decisions
        out["serial_host_s"] += s.pipeline_stats()["serial_host_s"]
        out["engine_steps"] += s.engine_steps
    return out


def warm_table_updates(state, entries: int, capacity: int) -> None:
    """Compile the engine's eager block-table updates for the entry counts
    a step or an admission of this cell assigns: ``_ensure_blocks``
    scatters ``k`` (slot, block, page) entries, ``_flush_released`` resets
    ``r`` released rows, and each new count is a new program that would
    otherwise compile inside the window.  The ops mirror the engine's,
    dtypes included; should the engine's ever differ, its programs compile
    in the window and the run is refused."""
    import jax
    import jax.numpy as jnp
    pt, outs = state["pt"], []
    for k in range(1, entries + 1):
        idx = np.zeros((k,), np.int64)
        outs.append(pt.at[idx, idx].set(jnp.asarray(np.zeros((k,),
                                                             np.int32))))
    for r in range(1, capacity + 1):
        outs.append(pt.at[np.zeros((r,), np.int64)].set(0))
    jax.block_until_ready(outs)


def buffers(arrays) -> set:
    """The device buffers behind ``arrays``: two arrays can share one."""
    return {s.data.unsafe_buffer_pointer() for a in arrays
            for s in a.addressable_shards}


def seed32(seed: int) -> int:
    return int(np.random.default_rng(seed).integers(0, 2 ** 31 - 1))


def percentile(values, q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if len(values) else None


# ---------------------------------------------------------------------------
# A run
# ---------------------------------------------------------------------------

def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             devices, t0: float = T0, engine_hook=None,
             threshold_u: Optional[float] = None,
             control: bool = False) -> dict:
    """Serve the cell's traffic for ``seconds`` and check what it served.

    ``engine_hook(engines)`` may change the engines before they serve (the
    tests plant faults through it); ``threshold_u`` overrides the cell's
    calibrated threshold (calibration runs); ``control`` also reads the
    float8 control on the same sampled requests (``bench/control.py``).
    Returns the result line's fields and, under ``"extra"``, what
    calibration and the tests read.
    """
    import jax
    from jax.profiler import TraceAnnotation
    from repro.config import GSIConfig
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import make_frontend
    from repro.models import build_model
    from repro.serving import GSIServingEngine

    from bench import check, work
    from bench import trace as tr
    from bench import weights as wts
    from bench.drive import Clients, Observer, clock
    from bench.metrics import Context, reader
    from bench.traffic import Traffic

    own = buffers(jax.live_arrays())            # held before this run
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    log(f"compile cache: {cache}")
    clocks = CompileClock()
    cfg, eng = cell.config, cell.config["engine"]
    models = cfg["models"]
    roles = ("draft", "target", "prm")
    mcfgs = [model_config(r, models[r], cfg["dtype"]) for r in roles]
    if threshold_u is None and "threshold_u" not in cell.calib:
        raise Refused(f"bench/cells/{cell.name}.json holds no threshold_u "
                      f"(python3 -m bench.calibrate finds one)")
    u = float(cell.calib["threshold_u"] if threshold_u is None
              else threshold_u)
    traffic = Traffic(cell.traffic, vocab=int(models["target"]["vocab_size"]),
                      seed=seed)
    gcfg = GSIConfig(n=int(eng["n"]), beta=float(eng["beta"]),
                     threshold_u=u, temperature=float(eng["temperature"]),
                     min_step_reward=float(eng["min_step_reward"]),
                     max_step_tokens=traffic.max_step_tokens,
                     max_steps=traffic.max_budget)
    replicas = int(cfg.get("replicas", 1))

    # weights, made by the benchmark on each chip
    t = clock()
    params = [wts.make_triple(models, cfg["weights_seed"], devices[r])
              for r in range(replicas)]
    jax.block_until_ready(params)
    key = jax.random.key(0, impl="rbg")
    wts.check_layout(params[0], tuple(jax.eval_shape(
        build_model(c).init, key) for c in mcfgs))
    log(f"weights: {replicas} x {wts.param_bytes(params[0])} bytes in "
        f"{clock() - t:.1f}s")

    engines = [GSIServingEngine(
        *mcfgs, *params[r], gcfg, mode="gsi", max_seq=int(eng["max_seq"]),
        paged=True, page_size=int(eng["page_size"]),
        num_pages=int(eng["num_pages"]), prefix_cache=True,
        device=devices[r]) for r in range(replicas)]
    if engine_hook is not None:
        engine_hook(engines)
    frontend = make_frontend(engines if replicas > 1 else engines[0],
                             capacity=int(eng["capacity"]), sync=False,
                             policy="affinity")
    router = replicas > 1
    scheds = [r.scheduler for r in frontend.replicas] if router \
        else [frontend]
    observers = [Observer(s.engine, s) for s in scheds]
    clients = Clients(frontend, traffic)
    key = jax.random.PRNGKey(seed32(seed))

    def step():
        nonlocal key
        key, k = jax.random.split(key)
        with TraceAnnotation("step"):
            frontend.step(k)

    # warm the admission program at the mix's widest prompt on every
    # replica, the decode step, the release path and the table updates:
    # a step assigns each live slot at most TABLE_BLOCKS new blocks, and
    # admissions of up to that many slots' worth of pages fall in range
    for s in scheds:
        warm_table_updates(s.state, int(eng["capacity"]) * TABLE_BLOCKS,
                           int(eng["capacity"]))
    for i, s in enumerate(scheds):
        req = traffic.longest(i)
        sub = frontend.replicas[i].submit if router else s.submit
        sub(req.prompt, request_id=f"warm-{i}", max_steps=1)
    for _ in range(64):
        step()
        if all(f"warm-{i}" in s.responses for i, s in enumerate(scheds)):
            break
    else:
        raise RuntimeError("the warm-up requests did not finish")
    clients.start(clock(), seconds + 60.0)
    for _ in range(FILL_STEPS):
        clients.refill(clock())
        step()
    warm_s, warm_n, warm_miss = clocks.take()
    log(f"set-up compiled or loaded {warm_n} programs in {warm_s:.1f}s, "
        f"{warm_miss} not in the cache")

    # the measured window
    trace_dir = None
    before = counters(scheds)
    if trace:
        jax.block_until_ready([s.state for s in scheds])
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
        win = TraceAnnotation("window")
        win.__enter__()
    start = clock()
    setup_s = start - t0
    n_disp0 = [o.dispatches for o in observers]
    n_adm0 = [len(o.admissions) for o in observers]
    deadline, trace_end = start + seconds, start + min(seconds, TRACE_SECONDS)
    tracing = trace
    while True:
        now = clock()
        if tracing and (now >= deadline or now >= trace_end and sum(
                o.dispatches for o in observers) - sum(n_disp0)
                >= TRACE_STEPS):
            jax.block_until_ready([s.state for s in scheds])
            win.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = False
            n_disp1 = [o.dispatches for o in observers]
            n_adm1 = [len(o.admissions) for o in observers]
        if now >= deadline:
            break
        clients.refill(now)
        step()
    # the window is exactly ``seconds`` long: what reached the clients
    # after it, while the last step call returned, is not counted
    end, over = deadline, clock() - deadline
    after = counters(scheds)
    _, win_n, win_miss = clocks.take()
    log(f"window {end - start:.3f}s, last call returned {over:.3f}s after "
        f"it; programs compiled or loaded in it: {win_n}, {win_miss} not "
        f"in the cache")
    if win_n:
        raise Refused(f"{win_n} programs were compiled or loaded inside the "
                      f"measured window: set-up did not warm every shape")

    # the device: finish what is in flight, read memory, free the state
    jax.block_until_ready([s.state for s in scheds])
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    delta = {k: after[k] - before[k] for k in after}
    records = clients.records
    rows = {}
    for o in observers:
        rows.update(o.rows)
    traced_work = traced_adm = None
    if trace:
        traced_work, traced_adm = work.Work(), 0
        costs = {r: work.Cost.of(models[r]) for r in roles}
        for o, a0, a1, d0, d1 in zip(observers, n_adm0, n_adm1, n_disp0,
                                     n_disp1):
            for adm in o.admissions[a0:a1]:
                traced_adm += 1
                traced_work.add(work.prefill(costs, prompt_len=adm.prompt_len,
                                             hit=adm.hit))
            for rid, rs in o.rows.items():
                if rid not in records:
                    continue            # a warm-up request
                c = records[rid].prompt.size
                for r in rs:
                    if d0 <= r.dispatch < d1:
                        traced_work.add(work.step(
                            costs, n=gcfg.n, context=c,
                            length=r.tokens.size, accepted=r.accept))
                    c += r.tokens.size
    refused = clients.refused
    stops = sum(o.stops for o in observers)
    # free the device: every array this run made but the weights the
    # reference reads
    weights = dict(zip(roles, params[0]))
    keep = own | buffers(jax.tree.leaves(params[0]))
    made = [a for a in jax.live_arrays() if not buffers([a]) & keep]
    for a in made:
        if not a.is_deleted():
            a.delete()
    del params, frontend, scheds, observers, clients, engines
    gc.collect()

    # the end-to-end metrics, from what the clients saw
    toks = sum(n for r in records.values() for t, n in r.events
               if start <= t <= end)
    ttft, gaps = [], []
    for r in records.values():
        times = [t for t, _ in r.events]
        if start <= r.due <= end:
            first = times[0] if times else math.inf
            ttft.append(min(first, end) - r.due)
        for a, b in zip(times, times[1:]):
            if start <= b <= end:
                gaps.append(b - a)
        if times and times[-1] <= end and (r.finished is None
                                           or r.finished > end):
            gaps.append(end - times[-1])
    e2e = {"tokens_per_s": toks / (end - start),
           "ttft_p90_s": percentile(ttft, 90),
           "step_gap_p90_s": percentile(gaps, 90),
           "setup_s": setup_s}
    attempted = sum(1 for r in records.values() if start <= r.due <= end)

    # correctness, on the chip's freed memory
    t = clock()
    finished = {rid: check.Sample(rid, r.prompt, r.tokens, rows.get(rid, []))
                for rid, r in records.items()
                if r.finished is not None and r.finished <= end}
    samples = check.choose(finished, np.random.default_rng(seed), SAMPLE)
    width = -(-traffic.max_tokens // 128) * 128
    result = check.compare(samples, models, weights, beta=gcfg.beta,
                           width=width)
    result["counts"]["pad_excluded"] = sum(
        any(r.pad_inside for r in smp.rows) for smp in finished.values())
    verdict = check.judge(result, cell.calib.get("limits", {}))
    log(f"check: {result['counts']} in {clock() - t:.1f}s")
    ctl = check.compare(samples, models, weights, beta=gcfg.beta,
                        width=width, control=True) if control else None
    for p in result["problems"]:
        log(f"check problem: {p}")

    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    out = {"correct": verdict["correct"], "attempted": attempted,
           "failed": refused + len(result["problems"]),
           "metrics": {}, "device": dev}
    if trace:
        summary = tr.reduce(tr.find(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        dev["busy_s"] = summary.busy_mean_s
        dev["window_s"] = summary.window_s
        out["breakdown"] = tr.breakdown(summary)
        peaks = _read(ROOT / "bench" / "peaks.json")["devices"]
        if devices[0].device_kind not in peaks:
            raise Refused(f"no peaks for {devices[0].device_kind!r} in "
                          f"bench/peaks.json")
        ctx = Context(delta, len(devices), peaks[devices[0].device_kind],
                      summary, traced_work, traced_adm)
        for name in cell.per_layer:
            v = reader(name)(ctx)
            if v is not None:
                out["metrics"][name] = {"value": v, "unit": cell.units[name]}
    else:
        for name in cell.end_to_end:
            if e2e.get(name) is not None:
                out["metrics"][name] = {"value": e2e[name],
                                        "unit": cell.units[name]}
    out["checks"] = verdict["checks"]
    out["extra"] = {"rows": rows, "records": records, "counters": delta,
                    "stops": stops, "check": result, "control": ctl}
    return out


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        sys.path.insert(0, str(ROOT / "src"))
        try:
            import repro  # noqa: F401  the program under test
        except ImportError as e:
            raise Refused(f"the program is not beside the benchmark: {e}")
        devices = require_chips(cell.chips)
        out = run_cell(cell, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), devices=devices)
    except Refused as e:
        log(f"refused: {e}")
        return 2
    out.pop("extra")
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
