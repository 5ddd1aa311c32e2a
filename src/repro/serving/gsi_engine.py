"""The GSI three-model serving engine (Algorithm 1, end to end).

Co-locates draft pi_S, target pi_B and the PRM on one mesh and runs the
step-level loop:

  draft phase   — n scratch copies of the committed draft cache; sample n
                  candidate steps; score them under pi_B (one parallel pass,
                  ``score_and_append`` on a scratch target cache) and under
                  the PRM; tilted-S-BoN select + threshold (core.gsi).
  target phase  — on rejection: n candidate steps sampled from pi_B, PRM
                  rewards, raw-reward S-BoN (lines 9-12).
  commit        — append the chosen step to all three committed caches.

The same engine, re-parameterized, implements every baseline of the paper:
RSD (raw rewards + threshold), S-BoN(draft), S-BoN(base), and the
"GSI w/o rejection" ablation.  Host-side loop + jitted phases; per-request
divergence handled with live-masking (PAD) rather than re-batching.

The decode step is split into an asynchronous pipeline pair:
``dispatch_decode`` enqueues one whole engine step (draft phase, the
rejection-fallback target phase under a device-side ``lax.cond``, commit
and the done fold) as a single jitted computation and returns an in-flight
:class:`StepTicket` of device arrays without ever blocking the host, and
``materialize`` transfers the finished ticket to host numpy in one batched
``device_get``.  ``step_decode`` is exactly ``dispatch`` + ``materialize``
back-to-back, so the synchronous and pipelined schedulers run the same
compiled computation with the same rng keys — async == sync tokens
bit-identically, whatever the pipeline depth.
"""
from __future__ import annotations

import dataclasses
import threading
from dataclasses import dataclass, field
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import GSIConfig, ModelConfig
from repro.core import gsi_select, rsd_select, soft_bon_select
from repro.distributed import tp as dtp
from repro.distributed.sharding import (as_shardings, mesh_axis_sizes,
                                        serve_state_pspecs,
                                        serve_target_pspecs)
from repro.kernels import quant
from repro.models import build_model
from repro.sampling import sample_steps, score_and_append
from repro.serving.engine import (branch_cache, branch_pages,
                                  expand_requests, fold_candidates,
                                  paged_view, repeat_cache, reset_cache_rows,
                                  take_candidates, take_per_request)
from repro.serving.pages import PagePool, RadixIndex, pages_for
from repro.serving.slots import pack_tails

PAD = 0


class StepResult(NamedTuple):
    """Host-side outcome of one engine decode step (all numpy, (B,...)).

    The trailing fields (``done`` onward) were added with the async
    pipeline: ``done``/``pos`` are the post-step bookkeeping a pipelined
    caller needs without touching device state, and the ``*_tokens`` /
    trace fields carry everything ``fold_step_stats`` records, so stats
    folding can be deferred off the dispatch critical path.
    """

    chosen: np.ndarray       # (B, L) committed step tokens (PAD-padded)
    done_prev: np.ndarray    # (B,) slot was already done before this step
    eos: np.ndarray          # (B,) step emitted EOS
    failed: np.ndarray       # (B,) B.2 early-stop: all draft rewards low
    accept: np.ndarray       # (B,) draft step accepted (True in sbon_b)
    done: Optional[np.ndarray] = None    # (B,) done *after* this step
    pos: Optional[np.ndarray] = None     # (B,) cache position after commit
    draft_tokens: int = 0    # non-PAD draft candidate tokens this step
    target_tokens: int = 0   # non-PAD target candidate tokens this step
    rewards: Optional[np.ndarray] = None      # (B, n) PRM rewards
    tilted: Optional[np.ndarray] = None       # (B, n) tilted rewards (gsi)
    logp_ratio: Optional[np.ndarray] = None   # (B, n) log pi_B - log pi_S


class StepTicket(NamedTuple):
    """An in-flight engine step: device arrays, no host synchronisation.

    Returned by ``dispatch_decode`` the moment the step is *enqueued* on
    the device stream; every field is a jax array (or None for fields the
    engine mode does not produce).  ``materialize`` turns a ticket into a
    :class:`StepResult` with one batched ``device_get`` — until then the
    host is free to run admission, harvest and page bookkeeping for
    neighbouring steps.  Tickets are immutable snapshots: releasing or
    re-admitting the slots they cover can never corrupt them.
    """

    chosen: jax.Array
    done_prev: jax.Array
    eos: jax.Array
    failed: jax.Array
    accept: jax.Array
    done: jax.Array
    pos: jax.Array
    draft_tokens: jax.Array          # () int32
    target_tokens: jax.Array         # () int32
    rewards: Optional[jax.Array]
    tilted: Optional[jax.Array]
    logp_ratio: Optional[jax.Array]


@dataclass
class EngineStats:
    """Serving counters + bounded trace arrays for one engine/scheduler.

    Scalar counters accumulate monotonically over a serving phase;
    ``record_trace`` keeps at most ``trace_limit`` arrays per trace while
    folding every array into exact running moments.  Fleet-level views
    (the replica router) combine per-replica instances with
    :func:`merge_engine_stats`.

    Instances are safe to update from concurrent replica threads: the
    compound read-modify-write paths (``bump`` for counters,
    ``record_trace`` for the moment fold) serialize on an internal lock,
    and ``merge_engine_stats`` snapshots each part under that lock.
    Plain attribute reads stay lock-free (single writes are atomic under
    the GIL; readers may observe a slightly stale counter, never a torn
    moment triple).
    """

    steps: int = 0
    accepted: int = 0
    decisions: int = 0
    draft_tokens: int = 0
    target_tokens: int = 0
    requests_finished: int = 0
    # prefix-cache counters (filled by the scheduler's admission path)
    prefix_queries: int = 0       # admissions that consulted the radix index
    prefix_hits: int = 0          # admissions with matched_len > 0
    prefix_hit_tokens: int = 0    # prompt tokens whose prefill was skipped
    prefix_pages_reused: int = 0  # cached/shared pages spliced into tables
    prefill_tokens: int = 0       # prompt tokens actually prefill-committed
    pages_evicted: int = 0        # cached pages evicted to admit (LRU)
    # decode-time publication: generated pages made matchable as they fill
    decode_pages_published: int = 0
    # SLO-aware scheduling counters (priority preemption + chunked prefill)
    preemptions: int = 0          # live slots paused for a higher priority
    resumes: int = 0              # paused requests re-admitted
    deadline_misses: int = 0      # finished requests past their deadline_s
    # largest prompt-token count committed by a single jitted admit/extend
    # call — the decode-stall proxy chunked prefill bounds (merged with max)
    prefill_commit_max: int = 0
    # per-step trace arrays are bounded: at most ``trace_limit`` arrays are
    # retained per trace, while running moments keep exact aggregate
    # mean/variance for arbitrarily long serving runs (collect_stats=True
    # under the scheduler must not grow memory without limit).
    trace_limit: int = 512
    tilted_rewards: list = field(default_factory=list)
    raw_rewards: list = field(default_factory=list)
    logp_ratio: list = field(default_factory=list)   # log pi_B - log pi_S
    moments: dict = field(default_factory=dict)      # name -> [n, mean, M2]
    # serializes compound updates from concurrent replica threads
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False, compare=False)

    @property
    def accept_rate(self) -> float:
        """Fraction of live-slot decisions that accepted the draft step."""
        return self.accepted / max(1, self.decisions)

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of admissions whose prompt matched cached pages."""
        return self.prefix_hits / max(1, self.prefix_queries)

    def bump(self, **deltas: int) -> None:
        """Atomically add ``deltas`` to the named scalar counters.

        The counter += paths run on engine and scheduler threads; routing
        them through one locked method keeps fleet totals exact when a
        stats object is (mis)shared across threads.
        """
        with self._lock:
            for name, d in deltas.items():
                setattr(self, name, getattr(self, name) + d)

    def record_trace(self, name: str, arr) -> None:
        """Append ``arr`` to the named trace (bounded) and fold it into
        the running moments (unbounded-safe Chan/Welford merge)."""
        arr = np.asarray(arr)
        x = arr.astype(np.float64).ravel()
        with self._lock:
            lst = getattr(self, name)
            if len(lst) < self.trace_limit:
                lst.append(arr)
            if x.size == 0:
                return
            n_a, mean_a, m2_a = self.moments.setdefault(name,
                                                        [0, 0.0, 0.0])
            n_b = x.size
            mean_b = float(x.mean())
            m2_b = float(((x - mean_b) ** 2).sum())
            n = n_a + n_b
            delta = mean_b - mean_a
            self.moments[name] = [
                n,
                mean_a + delta * n_b / n,
                m2_a + m2_b + delta * delta * n_a * n_b / n,
            ]

    def trace_mean(self, name: str) -> float:
        """Exact mean of every value ever recorded into ``name``."""
        return self.moments.get(name, [0, 0.0, 0.0])[1]

    def trace_var(self, name: str) -> float:
        """Exact population variance of the named trace."""
        n, _, m2 = self.moments.get(name, [0, 0.0, 0.0])
        return m2 / n if n else 0.0

    def trace_count(self, name: str) -> int:
        """Total values folded into the named trace's moments."""
        return self.moments.get(name, [0, 0.0, 0.0])[0]


def merge_engine_stats(parts: Sequence[EngineStats]) -> EngineStats:
    """Combine per-replica :class:`EngineStats` into one fleet view.

    Scalar counters sum; running moments merge exactly (the same
    Chan/Welford combine ``record_trace`` uses, so fleet-level
    ``trace_mean``/``trace_var`` equal what one scheduler would have
    measured); bounded trace lists concatenate up to ``trace_limit``.
    Each part is snapshotted under its own lock (replica threads may
    still be recording), and the inputs are left untouched.
    """
    out = EngineStats()
    if not parts:
        return out
    out.trace_limit = parts[0].trace_limit
    counters = ("steps", "accepted", "decisions", "draft_tokens",
                "target_tokens", "requests_finished", "prefix_queries",
                "prefix_hits", "prefix_hit_tokens", "prefix_pages_reused",
                "prefill_tokens", "pages_evicted",
                "decode_pages_published", "preemptions",
                "resumes", "deadline_misses")
    for p in parts:
        with p._lock:
            for f in counters:
                setattr(out, f, getattr(out, f) + getattr(p, f))
            # a max, not a sum: the fleet's worst single prefill commit
            out.prefill_commit_max = max(out.prefill_commit_max,
                                         p.prefill_commit_max)
            for trace in ("tilted_rewards", "raw_rewards", "logp_ratio"):
                lst = getattr(out, trace)
                lst.extend(getattr(p, trace)[:max(out.trace_limit
                                                  - len(lst), 0)])
            part_moments = {k: list(v) for k, v in p.moments.items()}
        for name, (n_b, mean_b, m2_b) in part_moments.items():
            n_a, mean_a, m2_a = out.moments.setdefault(name,
                                                       [0, 0.0, 0.0])
            n = n_a + n_b
            if n == 0:
                continue
            delta = mean_b - mean_a
            out.moments[name] = [
                n,
                mean_a + delta * n_b / n,
                m2_a + m2_b + delta * delta * n_a * n_b / n,
            ]
    return out


class GSIServingEngine:
    """mode: gsi | gsi_norej | rsd | sbon_s | sbon_b."""

    def __init__(self, draft_cfg: ModelConfig, target_cfg: ModelConfig,
                 prm_cfg: ModelConfig, params_s, params_b, params_p,
                 gcfg: GSIConfig, *, mode: str = "gsi",
                 rsd_threshold: float = 0.7, max_seq: int = 512,
                 shared_scoring: bool = False, paged: bool = False,
                 page_size: int = 16, num_pages: int = 0,
                 prefix_cache: bool = True, decode_publish: bool = True,
                 kv_dtype: Optional[str] = None,
                 quantize_draft: bool = False, mesh=None, device=None):
        """Build the three models and jit the engine's serving phases.

        ``mesh`` (a ``jax.sharding.Mesh`` with a ``model`` axis — usually
        one replica's submesh from ``launch.mesh.carve_submeshes``) turns
        on tensor-parallel serving: the *target* model's attention /
        FFN / vocab weights and its paged KV pools shard over the
        ``model`` axis (``distributed.sharding.serve_target_pspecs``,
        with per-group divisibility fallback to replication), while the
        draft and PRM stay replicated — speculation is local, only
        target scoring pays collectives.  Every jitted phase runs under
        one ``shard_map``, so draft phase + rejection-fallback target
        phase + commit remain ONE device-side step and the collectives
        overlap host admission through the same ``StepTicket``
        dispatch/materialize split; tokens stay bit-identical to the
        unsharded engine (collect-then-compute collectives, see
        ``repro.distributed.tp``).

        ``paged``/``page_size``/``num_pages`` select the paged KV layout
        (``num_pages=0`` sizes the pool to the dense capacity at state
        creation); ``prefix_cache`` enables the radix prefix index on
        paged engines (auto-disabled for recurrent/RWKV stacks).
        ``decode_publish`` additionally lets the scheduler publish a
        live slot's *generated* pages as its decode commits fill them
        (not just prompt pages at admission), so best-of-n retries and
        duplicate requests splice whole trajectories; publication is
        ordered after the on-stream commit exactly like ``admit``'s,
        and tokens are bit-identical with it on or off.

        ``kv_dtype`` picks the paged-pool storage format: ``None`` keeps
        the model activation dtype, ``"bf16"`` casts pages, ``"int8"`` /
        ``"fp8"`` store quantized codes with per-page per-kv-head scales
        (dequant fused into the paged-attention kernel).
        ``quantize_draft`` rounds the draft model's matmul weights
        through int8 at load (serving/quant.py).

        ``device`` (without ``mesh``) pins the engine to one device: its
        params and every state it creates live there, so replicas of a
        fleet can each own a chip.  ``None`` leaves placement to JAX.
        """
        assert prm_cfg.reward_head
        quant.validate_kv_dtype(kv_dtype)
        if kv_dtype is not None and not paged:
            raise ValueError("kv_dtype requires the paged KV layout "
                             "(pass paged=True)")
        self.kv_dtype = kv_dtype
        self.quantize_draft = bool(quantize_draft)
        self.mode = mode
        self.gcfg = gcfg
        self.rsd_threshold = rsd_threshold
        self.max_seq = max_seq
        # beyond-paper: score candidates against ONE shared cache instead of
        # n scratch copies (models/scoring.py); identical math, far less HBM.
        self.shared_scoring = shared_scoring
        # paged KV-cache: page pools + per-slot block table instead of dense
        # (B, max_seq) rows; candidate branching is copy-on-write page-table
        # aliasing (serving/engine.py) and slots draw pages from a host-side
        # allocator (serving/pages.py).  num_pages=0 sizes the pool to the
        # dense capacity (batch * nblk) at state creation.
        self.paged = paged
        self.page_size = page_size
        self.nblk = -(-max_seq // page_size)
        self.nmax = max(gcfg.n, gcfg.n_target or gcfg.n)
        # pages a single candidate branch can write in one reasoning step:
        # positions pos .. pos+max_step_tokens, worst-case page phase
        self.span = (page_size - 1 + gcfg.max_step_tokens) // page_size + 1
        self._num_pages = num_pages
        self.num_pages = 0            # set when a paged state is created
        self.pager: Optional[PagePool] = None
        self._trash = 0               # trash page id (last pool row)
        self._released: set = set()   # slots whose pt rows await trash-reset
        self._gen = 0                 # live-state generation (see fresh_state)
        self.draft = build_model(draft_cfg)
        self.target = build_model(target_cfg)
        self.prm = build_model(prm_cfg)
        if quantize_draft:
            # fake-quant at load: every draft matmul sees int8-rounded
            # weights, target/PRM weights stay untouched (serving/quant.py)
            from repro.serving.quant import quantize_draft_params
            params_s = quantize_draft_params(draft_cfg, params_s)
        self.params = (params_s, params_b, params_p)
        # cross-request prefix sharing (radix index over full committed
        # pages) is exact for pure-attention stacks: KV row i is a function
        # of tokens[0..i] only, and paged layers store absolute positions.
        # Recurrent/RWKV layers keep *dense per-slot* state that a spliced
        # page cannot carry, so sharing is auto-disabled there to preserve
        # bit-identical outputs.
        self.prefix_cache = bool(prefix_cache and paged
                                 and self._prefix_supported())
        self.decode_publish = bool(decode_publish and self.prefix_cache)
        self.mesh = mesh
        self.device = None if mesh is not None else device
        if self.device is not None:
            self.params = jax.device_put(self.params, self.device)
        self.tp = 1
        self._tp_plan = {"attn": False, "mlp": False, "vocab": False}
        if mesh is not None:
            if "model" not in mesh.axis_names:
                raise ValueError("mesh mode needs a 'model' axis; got "
                                 f"axes {mesh.axis_names}")
            if shared_scoring:
                raise NotImplementedError(
                    "shared_scoring under a mesh is not supported yet "
                    "(score_candidates bypasses the tp unembed hook)")
            if target_cfg.num_experts:
                raise NotImplementedError(
                    "MoE targets under the serving mesh are not "
                    "supported yet (moe_ffn runs its own expert-parallel "
                    "shard_map, which cannot nest inside the engine's)")
            self.tp = mesh_axis_sizes(mesh).get("model", 1)
            # only stacks made of hooked layer kinds may shard; a
            # recurrent/rwkv/hybrid target serves replicated (mesh mode
            # still works — every collective hook simply no-ops).
            kinds = list(self.target.pattern) * self.target.repeats \
                + list(self.target.remainder)
            if all(k in ("full", "local", "cross", "enc") for k in kinds):
                self._tp_plan = dtp.tp_plan(target_cfg, self.tp)
            self._target_pspecs = serve_target_pspecs(
                self.target.param_specs(), mesh, plan=self._tp_plan)
            rep = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())
            params_s = jax.device_put(
                params_s, jax.tree.map(lambda _: rep, params_s))
            params_b = jax.device_put(
                params_b, as_shardings(self._target_pspecs, mesh))
            params_p = jax.device_put(
                params_p, jax.tree.map(lambda _: rep, params_p))
            self.params = (params_s, params_b, params_p)
            # the shard_map'd jits need the state *structure* (dense vs
            # paged, batch size) — built lazily by fresh_state()
            self._jit_step = self._jit_commit = None
            self._jit_admit = self._jit_extend = None
            self._jit_draft_phase = self._jit_target_phase = None
        else:
            self._jit_step = self._bind(self._decode_core)
            self._jit_commit = self._bind(self._commit)
            self._jit_admit = self._bind(self._admit)
            self._jit_extend = self._bind(self._extend)
            # standalone phase jits: not on the decode path (the fused
            # _decode_core is), kept for phase-level tests and debugging
            self._jit_draft_phase = self._bind(self._draft_phase)
            self._jit_target_phase = self._bind(self._target_phase)
        # host-side mirrors of per-slot bookkeeping, updated at admit /
        # materialize time: dispatch_decode assigns pages from these (a
        # read of the live device state would block on the in-flight
        # step and serialize the pipeline)
        self._known_pos = np.zeros((0,), np.int64)
        self._known_done = np.zeros((0,), bool)
        self._inflight_steps = 0      # dispatched but not yet materialized

    def _bind(self, phase):
        """Jit a params-threading phase and call it on ``self.params``.

        The phases take the three param trees as an explicit first
        argument, and the jitted function receives them as arguments:
        arrays a jitted function closes over would be embedded in the
        program as constants (gigabytes at published widths).  The
        bound call keeps the signature ``(state, ...)``.
        """
        jitted = jax.jit(phase)

        def call(state, *extra):
            return jitted(self.params, state, *extra)
        return call

    def _build_mesh_jits(self, state) -> None:
        """Compile the engine's phases as one ``shard_map`` each.

        Needs a structural ``state`` template (dense vs paged layout,
        batch size), so it runs from :meth:`fresh_state` rather than
        ``__init__``.  Every phase body traces inside the
        ``tensor_parallel`` context: the target's sharded leaves enter
        as local shards per ``serve_target_pspecs`` /
        ``serve_state_pspecs`` and the model hooks supply the
        collectives; draft/PRM params, rng keys, block tables and all
        control state stay replicated (spec ``P()``).
        """
        mesh = self.mesh
        R = jax.sharding.PartitionSpec()
        state_specs = serve_state_pspecs(
            state, mesh, shard_attn=self._tp_plan["attn"])

        def rep(tree):
            return jax.tree.map(lambda _: R, tree)

        pspecs = (rep(self.params[0]), self._target_pspecs,
                  rep(self.params[2]))

        def wrap(phase, n_extra, out_specs):
            def body(params, st, *extra):
                with dtp.tensor_parallel("model"):
                    return phase(params, st, *extra)
            # replication checking off: the bodies mix sharded and
            # replicated leaves freely
            sm = jax.shard_map(
                body, mesh=mesh,
                in_specs=(pspecs, state_specs) + (R,) * n_extra,
                out_specs=out_specs, check_vma=False)
            jitted = jax.jit(sm)

            def call(st, *extra):
                return jitted(self.params, st, *extra)
            return call

        def commit(params, st, tokens):
            return self._commit(params, st, tokens)

        self._jit_step = wrap(self._decode_core, 2, (state_specs, R))
        self._jit_commit = wrap(commit, 1, state_specs)
        self._jit_admit = wrap(self._admit, 4, state_specs)
        self._jit_extend = wrap(self._extend, 3, state_specs)
        self._jit_draft_phase = wrap(self._draft_phase, 1, R)
        self._jit_target_phase = wrap(self._target_phase, 1, R)

    def _prefix_supported(self) -> bool:
        """Sharing is exact iff every layer of all three models keeps its
        serving state in the paged (position-addressed) KV pools."""
        def attention_only(model):
            kinds = list(model.pattern) * model.repeats \
                + list(model.remainder)
            return all(k in ("full", "local") for k in kinds)
        return all(attention_only(m)
                   for m in (self.draft, self.target, self.prm))

    # ------------------------------------------------------------------
    # State
    # ------------------------------------------------------------------
    def _fresh_caches(self, batch: int, *, pages: int = 0):
        kw = dict(pages=pages, page_size=self.page_size,
                  kv_dtype=self.kv_dtype) if pages else {}
        return {
            "S": self.draft.init_cache(batch, self.max_seq, **kw),
            "B": self.target.init_cache(batch, self.max_seq, **kw),
            "P": self.prm.init_cache(batch, self.max_seq, **kw),
        }

    def fresh_state(self, batch: int):
        """An all-free slot-pool state: every row is done/inert until a
        prompt is admitted into it (scheduler API)."""
        state = {
            "pending": jnp.full((batch,), PAD, jnp.int32),
            "pos": jnp.zeros((batch,), jnp.int32),
            "done": jnp.ones((batch,), bool),
        }
        self._known_pos = np.zeros((batch,), np.int64)
        self._known_done = np.ones((batch,), bool)
        self._inflight_steps = 0
        if not self.paged:
            state["caches"] = self._fresh_caches(batch)
            return self._place_state(state)
        # paged layout: `num_pages` allocatable pages + a static scratch
        # region for copy-on-write branching + one trash page that absorbs
        # the benign garbage-at-pos writes of done/never-admitted rows.
        self.num_pages = self._num_pages or batch * self.nblk
        n_scratch = batch * self.nmax * self.span
        total = self.num_pages + n_scratch + 1
        index = RadixIndex(self.page_size) if self.prefix_cache else None
        # bytes-weighted LRU: the pool knows what one page of this
        # engine's kv_dtype actually costs (payload + quant scales), so
        # cached quantized pages are evicted at half the priority of
        # full-precision ones of equal staleness
        mem = self.cache_memory_report(batch)
        self.pager = PagePool(self.num_pages, self.page_size, index=index,
                              kv_dtype=self.kv_dtype,
                              page_bytes=mem["bytes_per_page"]
                              + mem["scale_bytes_per_page"])
        self._trash = total - 1
        self._released = set()
        scratch = (self.num_pages
                   + np.arange(n_scratch, dtype=np.int32)
                   ).reshape(batch, self.nmax, self.span)
        state["caches"] = self._fresh_caches(batch, pages=total)
        # block table: one extra (trash) column absorbs clamped writes at
        # pos == max_seq; unassigned entries also point at the trash page
        state["pt"] = jnp.full((batch, self.nblk + 1), total - 1, jnp.int32)
        state["scratch"] = jnp.asarray(scratch)
        # the page allocator is engine-held host state, so a paged engine
        # backs ONE live state at a time: creating a new state invalidates
        # every older one (stepping a stale state raises, see _check_gen)
        self._gen += 1
        state["gen"] = jnp.asarray(self._gen, jnp.int32)
        return self._place_state(state)

    def _place_state(self, state):
        """Mesh mode: place a fresh state on the replica's submesh (the
        target's KV leaves sharded over the kv-head axis, everything
        else replicated) and build the shard_map'd phase jits against
        its structure.  Single-device engines put the state on their
        ``device`` (identity when they have none)."""
        if self.mesh is None:
            return state if self.device is None \
                else jax.device_put(state, self.device)
        specs = serve_state_pspecs(state, self.mesh,
                                   shard_attn=self._tp_plan["attn"])
        state = jax.device_put(state, as_shardings(specs, self.mesh))
        self._build_mesh_jits(state)
        return state

    def _check_gen(self, state):
        if int(state["gen"]) != self._gen:
            raise RuntimeError(
                "stale paged state: fresh_state()/init_state() was called "
                "on this engine after the state was created, resetting the "
                "page allocator.  A paged engine backs one live state at a "
                "time; build a separate engine for concurrent states.")

    @staticmethod
    def _with_gen(new_state, state):
        """Re-attach the *concrete* generation stamp to a jitted output.

        The jitted phases thread ``gen`` through as a device array, which
        would turn ``_check_gen``'s ``int()`` into a blocking sync on the
        in-flight step.  The stamp never changes within a live state, so
        the host keeps the original concrete array attached instead.
        """
        if "gen" in state:
            new_state = dict(new_state)
            new_state["gen"] = state["gen"]
        return new_state

    def init_state(self, prompts: np.ndarray):
        """prompts: (B, Lp) PAD-padded token array.

        All-PAD rows (padding a partial batch up to capacity) start done,
        so they never decode or hold up ``run``'s all-done early exit.
        """
        B = prompts.shape[0]
        prompts = np.asarray(prompts)
        state = self.fresh_state(B)
        state["pending"] = jnp.asarray(prompts[:, 0], jnp.int32)
        done = (prompts == PAD).all(axis=1)
        state["done"] = jnp.asarray(done)
        lengths = (prompts != PAD).sum(axis=1)
        self._known_done = done.copy()
        if self.paged:
            for b in range(B):
                if lengths[b]:
                    self.pager.claim(b, self.blocks_needed(
                        int(lengths[b]), self.gcfg.max_steps))
            state = self._assign_pages(state,
                                       np.maximum(lengths - 1, 0))
        if prompts.shape[1] > 1:
            state = self._with_gen(
                self._jit_commit(state, jnp.asarray(prompts[:, 1:],
                                                    jnp.int32)), state)
        self._known_pos = np.maximum(lengths - 1, 0).astype(np.int64)
        return state

    # ------------------------------------------------------------------
    # Page accounting (host side; no-ops for the dense engine)
    # ------------------------------------------------------------------
    def positions_needed(self, prompt_len: int, budget: int) -> int:
        """Worst-case cache positions a request can touch: committed
        prompt + ``budget`` full reasoning steps.  The single source of
        the cost model — scheduler admission (max_seq check) and page
        reservation both derive from it."""
        return prompt_len - 1 + budget * self.gcfg.max_step_tokens

    def blocks_needed(self, prompt_len: int, budget: int) -> int:
        """Worst-case pages a request can touch (admission reservation)."""
        # +1 position: the trailing garbage-at-pos write of the last commit
        need = self.positions_needed(prompt_len, budget) + 1
        return min(self.nblk, pages_for(need, self.page_size))

    def match_prefix(self, prompt) -> Tuple[List[int], int]:
        """Radix lookup: the longest cached page-aligned prefix of
        ``prompt`` whose KV pages can be spliced into a new slot's block
        table (one splice covers draft/target/PRM — the unified page-id
        space keeps the three models position-aligned).

        At most the first ``len(prompt) - 1`` tokens are matchable: the
        engine invariant leaves the last prompt token *pending* (its KV row
        is written by the first decode step), so the page holding it is
        never full at admission.  Returns ``([], 0)`` when prefix caching
        is off or unsupported for this stack.
        """
        if not self.paged or self.pager is None or not self.prefix_cache:
            return [], 0
        prompt = np.asarray(prompt).reshape(-1)
        lim = (prompt.size - 1) // self.page_size * self.page_size
        return self.pager.match(prompt[:max(lim, 0)])

    def admit_ok(self, prompt_len: int, budget: int,
                 shared: Sequence[int] = ()) -> bool:
        """Can a request be admitted now?  Paged engines gate on free
        (unclaimed) pages — counting matched ``shared`` pages as already
        covered and LRU-evictable cached pages as reclaimable — so False
        means true back-pressure: defer the request."""
        if not self.paged or self.pager is None:
            return True
        tail = self.blocks_needed(prompt_len, budget) - len(shared)
        return self.pager.can_claim(tail, shared)

    def claim_slot(self, slot: int, prompt_len: int, budget: int,
                   shared: Sequence[int] = ()) -> None:
        """Reserve the request's worst-case *tail* pages, splicing the
        matched ``shared`` pages in as blocks 0..len(shared)-1 (they are
        pinned before any eviction the claim itself triggers)."""
        if self.paged:
            tail = self.blocks_needed(prompt_len, budget) - len(shared)
            self.pager.claim(slot, tail, shared=shared)

    def release_slot(self, slot: int) -> int:
        """Return a finished request's pages to the pool (no zeroing).

        The slot's block-table row is lazily re-pointed at the trash page
        before the next jitted phase, so the freed slot's benign
        garbage-at-``pos`` writes can never land in a reassigned page.
        """
        if self.paged and slot in self.pager.assigned:
            self._released.add(slot)
            return self.pager.release(slot)
        return 0

    def _flush_released(self, state):
        """Point released slots' table rows at the trash page."""
        if not self._released:
            return state
        rows = np.asarray(sorted(self._released))
        self._released = set()
        state = dict(state)
        state["pt"] = state["pt"].at[rows].set(self._trash)
        return state

    def cache_memory_report(self, batch: int) -> dict:
        """HBM accounting: dense per-slot caches vs the paged pool, and —
        the headline numbers — per-draft-step candidate-branch scratch
        (dense ``repeat_cache`` materializes n full cache copies; paged
        branching allocates ``n * span`` copy-on-write pages per slot) and
        pool *capacity* (pages / tokens / bytes at the engine's
        ``kv_dtype``: page bytes are computed from the actual pool leaf
        dtype, per-page scale tensors accounted separately, so two engines
        differing only in ``kv_dtype`` report the exact storage ratio)."""
        from repro.models.attention import _cache_len
        from repro.models.common import adtype
        g = self.gcfg

        def attn_layers(model):
            kinds = list(model.pattern) * model.repeats \
                + list(model.remainder)
            return [k for k in kinds if k not in ("rwkv", "recurrent")]

        def row_bytes(model, dtype=None):
            """Bytes per pool cache position (k+v over attention layers),
            at the *actual* page storage dtype unless overridden."""
            cfg = model.cfg
            dt = dtype or quant.pool_dtype(self.kv_dtype, adtype(cfg))
            item = jnp.dtype(dt).itemsize
            return sum(2 * cfg.num_kv_heads * cfg.head_dim * item
                       for _ in attn_layers(model))

        def scale_bytes(model):
            """Per-page bytes of the (P, KV) float32 k/v scale tensors."""
            if not quant.is_quantized(self.kv_dtype):
                return 0
            return sum(2 * model.cfg.num_kv_heads * 4
                       for _ in attn_layers(model))

        def dense_bytes(model):
            cfg = model.cfg
            item = jnp.dtype(adtype(cfg)).itemsize
            return batch * sum(
                2 * cfg.num_kv_heads * cfg.head_dim * item
                * _cache_len(cfg, k, self.max_seq)
                for k in attn_layers(model))

        n = g.n
        branched = [self.draft, self.prm]
        if self.mode in ("gsi", "gsi_norej") and not self.shared_scoring:
            branched.append(self.target)
        dense_branch = n * sum(dense_bytes(m) for m in branched)
        models = (self.draft, self.target, self.prm)
        page_b = sum(row_bytes(m) for m in models) * self.page_size
        scale_b = sum(scale_bytes(m) for m in models)
        fp_page_b = sum(row_bytes(m, adtype(m.cfg))
                        for m in models) * self.page_size
        num_pages = self.num_pages or batch * self.nblk
        n_scratch = batch * self.nmax * self.span
        total_pages = num_pages + n_scratch + 1
        rep = {
            "kv_dtype": self.kv_dtype or "fp",
            "page_size": self.page_size,
            "num_pages": num_pages,
            "scratch_pages": n_scratch,
            "bytes_per_page": page_b,
            "scale_bytes_per_page": scale_b,
            "fp_bytes_per_page": fp_page_b,
            # pool capacity at this kv_dtype: allocatable pages / tokens /
            # the HBM they cost (page payload + per-page scales)
            "capacity_pages": num_pages,
            "capacity_tokens": num_pages * self.page_size,
            "capacity_bytes": num_pages * (page_b + scale_b),
            "dense_committed_bytes": sum(dense_bytes(m) for m in models),
            "dense_branch_bytes": dense_branch,
            "paged_pool_bytes": total_pages * (page_b + scale_b),
            "paged_branch_bytes": n_scratch * (page_b + scale_b),
        }
        rep["branch_reduction"] = (
            rep["dense_branch_bytes"] / max(1, rep["paged_branch_bytes"]))
        # per-device split under the serving mesh: the target's KV pages
        # shard tp-ways along the kv-head axis; draft/PRM pages (and the
        # target's when attention can't shard) replicate on every device,
        # so each device's effective tokens-worth of HBM is the capacity
        # scaled by its byte share.
        shard = self.tp if self._tp_plan["attn"] else 1
        tgt_page = row_bytes(self.target) * self.page_size \
            + scale_bytes(self.target)
        per_dev_page = (page_b + scale_b) - tgt_page + tgt_page // shard
        rep["devices"] = 1 if self.mesh is None else \
            int(np.prod(self.mesh.devices.shape))
        rep["bytes_per_device"] = num_pages * per_dev_page
        rep["capacity_tokens_per_device"] = round(
            rep["capacity_tokens"] * rep["bytes_per_device"]
            / max(1, rep["capacity_bytes"]))
        if self.pager is not None:
            # distinct pages (num_referenced) are the HBM truth: a page
            # spliced into several slots' tables occupies one page
            rep["pages_assigned"] = self.pager.num_referenced
            rep["pages_slot_view"] = self.pager.num_assigned
            rep["pages_peak"] = self.pager.peak_assigned
            rep["paged_assigned_bytes"] = self.pager.num_referenced * page_b
            rep["paged_peak_bytes"] = self.pager.peak_assigned * page_b
            rep["pages_cached"] = self.pager.num_cached
            rep["pages_evicted"] = self.pager.evicted
            rep["prefix_cached_bytes"] = self.pager.num_cached * page_b
        return rep

    def _ensure_blocks(self, state, wants: dict, splice=None):
        """Assign pages so each slot covers ``wants[slot]`` table blocks,
        then push the new (block -> page) entries into the device table.
        ``splice`` ((rows, cols, vals) lists) folds extra table updates —
        the prefix-cache splice of shared pages — into the same scatter."""
        rows, cols, vals = splice if splice is not None else ([], [], [])
        for slot, nb in wants.items():
            for blk, page in self.pager.ensure(slot, nb):
                rows.append(slot)
                cols.append(blk)
                vals.append(page)
        if rows:
            state = dict(state)
            state["pt"] = state["pt"].at[
                np.asarray(rows), np.asarray(cols)].set(
                jnp.asarray(np.asarray(vals, np.int32)))
        return state

    def _assign_pages(self, state, ahead):
        """Lazily assign pages so every live slot's table covers the blocks
        the next jitted phase may write (up to ``pos + ahead``).

        Positions come from the engine's *host-side* mirrors
        (``_known_pos``/``_known_done``, refreshed at admit and
        materialize time) rather than the device state, so a pipelined
        dispatch never blocks on the step still executing.  When steps
        are dispatched ahead of the last materialize, the caller widens
        ``ahead`` by one ``max_step_tokens`` per in-flight step; the
        per-slot want is capped at the slot's reservation, which the
        force-done budget guarantee makes an upper bound on what it can
        actually write.
        """
        state = self._flush_released(state)
        pos = self._known_pos
        done = self._known_done
        ahead = np.broadcast_to(np.asarray(ahead), pos.shape)
        wants = {}
        for slot in list(self.pager.assigned):
            if done[slot] and self.pager.blocks_assigned(slot):
                continue          # pos is frozen; blocks already cover it
            wants[slot] = min(
                self.nblk,
                self.pager.max_blocks(slot),
                pages_for(int(pos[slot]) + int(ahead[slot]) + 1,
                          self.page_size))
        return self._ensure_blocks(state, wants)

    def force_done(self, state, mask) -> dict:
        """Mark ``mask`` slots done on the device *and* in the host
        mirror (scheduler budget exhaustion — the one finish condition
        the device cannot see).  No-op when the mask is empty."""
        mask = np.asarray(mask, bool)
        if not mask.any():
            return state
        state = dict(state)
        state["done"] = state["done"] | jnp.asarray(mask)
        self._known_done = self._known_done | mask
        return state

    # ------------------------------------------------------------------
    # Jitted phases
    # ------------------------------------------------------------------
    def _commit(self, params, state, step_tokens, row_live=None):
        """Append step_tokens (B,L) to the three committed caches."""
        ps, pb, pp = params
        caches = state["caches"]
        pt = state.get("pt")
        new = {}
        _, new["S"], pos = score_and_append(
            self.draft, ps, caches["S"], state["pending"], state["pos"],
            step_tokens, row_live=row_live, pt=pt)
        _, new["B"], _ = score_and_append(
            self.target, pb, caches["B"], state["pending"], state["pos"],
            step_tokens, row_live=row_live, pt=pt)
        _, new["P"], _, _ = score_and_append(
            self.prm, pp, caches["P"], state["pending"], state["pos"],
            step_tokens, return_rewards=True, row_live=row_live, pt=pt)
        length = jnp.sum(step_tokens != PAD, axis=1)
        if row_live is not None:
            length = jnp.where(row_live, length, 0)
        pending = jnp.where(
            length > 0,
            jnp.take_along_axis(
                step_tokens, jnp.maximum(length - 1, 0)[:, None],
                axis=1)[:, 0],
            state["pending"])
        out = {"caches": new, "pending": pending, "pos": pos,
               "done": state["done"]}
        if pt is not None:
            out["pt"], out["scratch"] = pt, state["scratch"]
            out["gen"] = state["gen"]
        return out

    def _admit(self, params, state, admit_mask, tails, starts, live):
        """Prefill prompt *tails* (B,Lt; PAD-padded) into the slots where
        ``admit_mask`` is True; every other slot passes through untouched.

        ``tails`` holds each admitted prompt shifted past its prefix-cache
        match: ``tails[b] = prompt[starts[b]:]`` (``starts[b] == 0`` — the
        whole prompt — when nothing matched).  Admitted rows are zeroed
        (stale recurrent state / ring buffers from the previous occupant;
        shared paged pools are never touched), bookkeeping is reset to the
        engine invariant (cache holds prompt[:-1], pending = prompt[-1],
        the matched prefix already living in spliced pages below
        ``starts``), and the unmatched tail is teacher-forced through all
        three models via the regular commit path with ``row_live`` masking.

        ``live`` (B,) marks which admitted rows hold their *whole* prompt:
        those come up decoding (done=False).  A chunked-prefill admission
        passes ``live=False`` — the row stays device-done (inert under the
        decode masks) until :meth:`extend` commits its final chunk, so live
        neighbours keep decoding while the long prompt trickles in.
        """
        caches = reset_cache_rows(state["caches"], admit_mask)
        new = {
            "caches": caches,
            "pending": jnp.where(admit_mask, tails[:, 0],
                                 state["pending"]),
            "pos": jnp.where(admit_mask, starts, state["pos"]),
            "done": jnp.where(admit_mask, ~live, state["done"]),
        }
        if "pt" in state:
            new["pt"], new["scratch"] = state["pt"], state["scratch"]
            new["gen"] = state["gen"]
        return self._commit(params, new, tails[:, 1:], row_live=admit_mask)

    def _extend(self, params, state, mask, chunks, live):
        """Commit continuation prefill ``chunks`` (B,W; PAD-padded) into
        mid-prefill slots where ``mask`` is True (chunked prefill).

        Each masked row's chunk is the next run of its prompt tokens: the
        regular commit path teacher-forces ``pending`` + ``chunks[:, :-1]``
        and leaves the chunk's last token pending — after the final chunk
        the row satisfies the same invariant a one-shot admit establishes
        (cache holds prompt[:-1], pending == prompt[-1], pos == len-1).
        ``live`` flips rows whose final chunk this is to done=False; rows
        mid-prefill stay device-done and inert under the decode masks.
        """
        new = self._commit(params, state, chunks, row_live=mask)
        new["done"] = jnp.where(mask, ~live, state["done"])
        return new

    def _branch(self, cache, n, state):
        """n scratch branches of a committed cache: dense n-way copy, or
        paged copy-on-write aliasing.  Returns (cache, branch_pt)."""
        if not self.paged:
            return repeat_cache(cache, n), None
        scr = state["scratch"][:, :n]
        bpt = branch_pages(state["pt"], state["pos"], scr, self.page_size)
        return branch_cache(cache, n, state["pt"], state["pos"], scr,
                            self.page_size), bpt

    def _draft_phase(self, params, state, rng):
        """Sample n draft candidates; score with target + PRM."""
        g = self.gcfg
        n = g.n
        ps, pb, pp = params
        k1, k2 = jax.random.split(rng)
        pend = expand_requests(state["pending"], n)
        pos = expand_requests(state["pos"], n)
        done = expand_requests(state["done"], n)

        scratch_s, bpt = self._branch(state["caches"]["S"], n, state)
        steps = sample_steps(
            self.draft, ps, scratch_s, pend, pos, k1,
            max_tokens=g.max_step_tokens, sep_token=g.sep_token_id,
            eos_token=g.eos_token_id, temperature=g.temperature,
            top_p=g.top_p, already_done=done, pt=bpt)

        cands = fold_candidates(steps.tokens, n)             # (B,n,L)
        # PRM rewards (always needed)
        if self.shared_scoring:
            from repro.models.scoring import score_candidates
            cache_p = state["caches"]["P"]
            if self.paged:
                cache_p = paged_view(cache_p, state["pt"])
            _, rewards = score_candidates(
                self.prm, pp, cache_p, state["pending"],
                state["pos"], cands, return_rewards=True)
        else:
            scratch_p, _ = self._branch(state["caches"]["P"], n, state)
            _, _, _, rewards_flat = score_and_append(
                self.prm, pp, scratch_p, pend, pos, steps.tokens,
                return_rewards=True, pt=bpt)
            rewards = fold_candidates(rewards_flat, n)

        out = {
            "cands": cands,
            "logp_S": fold_candidates(steps.logprob, n),     # (B,n)
            "rewards": rewards,
            "rng": k2,
        }
        if self.mode in ("gsi", "gsi_norej"):
            if self.shared_scoring:
                from repro.models.scoring import score_candidates
                cache_b = state["caches"]["B"]
                if self.paged:
                    cache_b = paged_view(cache_b, state["pt"])
                out["logp_B"] = score_candidates(
                    self.target, pb, cache_b,
                    state["pending"], state["pos"], cands)
            else:
                scratch_b, _ = self._branch(state["caches"]["B"], n, state)
                logp_B, _, _ = score_and_append(
                    self.target, pb, scratch_b, pend, pos, steps.tokens,
                    pt=bpt)
                out["logp_B"] = fold_candidates(logp_B, n)
            dec = gsi_select(k2, out["rewards"], out["logp_B"],
                             out["logp_S"], beta=g.beta,
                             threshold_u=g.threshold_u)
            accept = dec.accept if (self.mode == "gsi" and g.use_rejection) \
                else jnp.ones_like(dec.accept)
            out.update(index=dec.index, accept=accept,
                       selected=dec.selected_tilted, tilted=dec.tilted)
        elif self.mode == "rsd":
            dec = rsd_select(k2, out["rewards"], beta=g.beta,
                             threshold=self.rsd_threshold)
            out.update(index=dec.index, accept=dec.accept,
                       selected=dec.selected_reward, tilted=out["rewards"])
        else:  # sbon_s: always accept the soft-BoN choice
            idx = soft_bon_select(k2, out["rewards"], g.beta)
            out.update(index=idx, accept=jnp.ones((idx.shape[0],), bool),
                       selected=take_per_request(out["rewards"], idx),
                       tilted=out["rewards"])
        out["chosen"] = take_candidates(out["cands"], out["index"])
        out["max_reward"] = jnp.max(out["rewards"], axis=-1)
        return out

    def _target_phase(self, params, state, rng):
        """S-BoN with the target model (rejection fallback / sbon_b)."""
        g = self.gcfg
        n = g.n_target or g.n
        _, pb, pp = params
        k1, k2 = jax.random.split(rng)
        pend = expand_requests(state["pending"], n)
        pos = expand_requests(state["pos"], n)
        done = expand_requests(state["done"], n)

        scratch_b, bpt = self._branch(state["caches"]["B"], n, state)
        steps = sample_steps(
            self.target, pb, scratch_b, pend, pos, k1,
            max_tokens=g.max_step_tokens, sep_token=g.sep_token_id,
            eos_token=g.eos_token_id, temperature=g.temperature,
            top_p=g.top_p, already_done=done, pt=bpt)
        scratch_p, _ = self._branch(state["caches"]["P"], n, state)
        _, _, _, rewards = score_and_append(
            self.prm, pp, scratch_p, pend, pos, steps.tokens,
            return_rewards=True, pt=bpt)
        cands = fold_candidates(steps.tokens, n)
        r = fold_candidates(rewards, n)
        idx = soft_bon_select(k2, r, g.beta)
        return {"chosen": take_candidates(cands, idx), "cands": cands,
                "rewards": r, "selected": take_per_request(r, idx)}

    # ------------------------------------------------------------------
    # Host loop
    # ------------------------------------------------------------------
    def _decode_core(self, params, state, rng, rng_target):
        """One whole engine step as a single traced computation.

        Draft phase, the rejection-fallback target phase under a
        device-side ``lax.cond`` (it runs iff any live slot rejected —
        exactly when the host-checked path used to run it, and
        ``jnp.where`` selection makes the all-accept case bit-identical
        to skipping it), commit, and the EOS / B.2 done fold.  Returns
        ``(new_state, StepTicket)`` — everything a pipelined caller needs
        without a host round-trip.
        """
        g = self.gcfg
        if self.mode == "sbon_b":
            tp = self._target_phase(params, state, rng)
            chosen = tp["chosen"]
            accept = jnp.ones_like(state["done"])
            max_r = jnp.max(tp["rewards"], axis=-1)
            draft_count = jnp.zeros((), jnp.int32)
            target_count = jnp.sum(tp["cands"] != PAD).astype(jnp.int32)
            rewards = tilted = ratio = None
        else:
            dp = self._draft_phase(params, state, rng)
            accept = dp["accept"]
            max_r = dp["max_reward"]
            draft_count = jnp.sum(dp["cands"] != PAD).astype(jnp.int32)
            rewards = dp["rewards"]
            tilted = dp["tilted"] if "logp_B" in dp else None
            ratio = (dp["logp_B"] - dp["logp_S"]) if "logp_B" in dp \
                else None

            def fallback(_):
                tp = self._target_phase(params, state, rng_target)
                return (tp["chosen"],
                        jnp.sum(tp["cands"] != PAD).astype(jnp.int32))

            def no_fallback(_):
                return (jnp.zeros_like(dp["chosen"]),
                        jnp.zeros((), jnp.int32))

            tp_chosen, target_count = jax.lax.cond(
                jnp.all(accept), no_fallback, fallback, None)
            chosen = jnp.where(accept[:, None], dp["chosen"], tp_chosen)
        done_prev = state["done"]
        # early stop (paper B.2): all draft rewards below min threshold
        failed = max_r < g.min_step_reward
        new_state = self._commit(params, state, chosen)
        eos = jnp.any(chosen == g.eos_token_id, axis=1)
        new_done = done_prev | eos | (failed & ~done_prev)
        new_state["done"] = new_done
        ticket = StepTicket(
            chosen=chosen, done_prev=done_prev, eos=eos, failed=failed,
            accept=accept, done=new_done, pos=new_state["pos"],
            draft_tokens=draft_count, target_tokens=target_count,
            rewards=rewards, tilted=tilted, logp_ratio=ratio)
        return new_state, ticket

    def dispatch_decode(self, state, rng, rng_target=None):
        """Enqueue one engine step; returns ``(state, StepTicket)``.

        Non-blocking: page assignment reads the host-side position
        mirrors, the jitted step is dispatched asynchronously, and no
        device value is fetched — the host is free to overlap admission
        and harvest work with the step's device execution.  Pair with
        :meth:`materialize`; ``step_decode`` is the synchronous
        composition of the two.
        """
        g = self.gcfg
        if rng_target is None:
            rng, rng_target = jax.random.split(rng)
        if self.paged:
            self._check_gen(state)
            # page in the blocks every in-flight step may write: one
            # max_step_tokens of look-ahead per dispatched-unharvested step
            ahead = (self._inflight_steps + 1) * g.max_step_tokens
            state = self._assign_pages(state, ahead)
        new_state, ticket = self._jit_step(state, rng, rng_target)
        new_state = self._with_gen(new_state, state)
        self._inflight_steps += 1
        return new_state, ticket

    def materialize(self, ticket: StepTicket) -> StepResult:
        """Transfer a dispatched step's whole outcome to the host.

        One batched ``device_get`` over every ticket array (blocking only
        until the step's device execution completes), refreshing the
        host-side ``pos``/``done`` mirrors the next dispatch assigns
        pages from.  Stats folding is split out (:meth:`fold_step_stats`)
        so a pipelined scheduler can defer it off the dispatch path.
        """
        host = jax.device_get(
            {n: v for n, v in zip(StepTicket._fields, ticket)
             if v is not None})
        kw = {n: host.get(n) for n in StepTicket._fields}
        kw["draft_tokens"] = int(kw["draft_tokens"])
        kw["target_tokens"] = int(kw["target_tokens"])
        self._known_pos = np.array(kw["pos"], np.int64)
        self._known_done = np.array(kw["done"], bool)
        self._inflight_steps = max(0, self._inflight_steps - 1)
        return StepResult(**kw)

    def fold_step_stats(self, res: StepResult, stats: EngineStats,
                        collect_stats: bool = False) -> None:
        """Fold one materialized step into ``stats``.

        Exactly the accounting the synchronous ``step_decode`` always
        did, factored out so the pipelined scheduler can run it while the
        next step executes on device.
        """
        if self.mode == "sbon_b":
            stats.bump(steps=1, target_tokens=res.target_tokens)
            return
        live = ~res.done_prev
        stats.bump(steps=1, draft_tokens=res.draft_tokens,
                   target_tokens=res.target_tokens,
                   decisions=int(live.sum()),
                   accepted=int((res.accept & live).sum()))
        if collect_stats:
            stats.record_trace("raw_rewards", res.rewards)
            if res.logp_ratio is not None:
                stats.record_trace("logp_ratio", res.logp_ratio)
                stats.record_trace("tilted_rewards", res.tilted)

    def step_decode(self, state, rng, rng_target=None, *,
                    stats: Optional[EngineStats] = None,
                    collect_stats: bool = False):
        """One engine step over the whole (fixed-size) batch.

        Runs the mode's phase(s) on every live slot (done slots are masked
        and stay inert), commits the chosen step to the three caches, and
        folds EOS / B.2 early-stop into ``state["done"]``.  Returns
        ``(state, StepResult)``; the caller (``run`` or the
        continuous-batching scheduler) owns response assembly.  This is
        ``dispatch_decode`` + ``materialize`` back-to-back — the
        synchronous and pipelined schedulers run the same compiled step.
        """
        state, ticket = self.dispatch_decode(state, rng, rng_target)
        res = self.materialize(ticket)
        if stats is not None:
            self.fold_step_stats(res, stats, collect_stats)
        return state, res

    def admit(self, state, admit_mask: np.ndarray, prompts: np.ndarray,
              starts=None, live=None):
        """Scheduler API: prefill ``prompts`` (B,Lp) into masked slots.

        ``starts`` (B,) gives each admitted slot's prefix-cache match
        length (a multiple of ``page_size``; 0 = no match).  Matched blocks
        are spliced into the slot's table from the pages its claim was
        seeded with, only the tail ``prompt[start:]`` is prefilled, and the
        prompt's full committed pages are published to the radix index
        *after* the prefill commit is ordered on the device stream — a
        request admitted on the same step can never match pages whose
        content is still being written.

        ``live`` (B,) bool (default all-True) marks rows admitted with
        their whole prompt.  Chunked prefill admits a *truncated* prompt
        with ``live=False``: the row stays device-done (inert) and the
        scheduler streams the rest in with :meth:`extend`.  The caller's
        page claim must cover the full prompt either way (``claim_slot``
        with the real prompt length).
        """
        admit_mask = np.asarray(admit_mask, bool)
        prompts = np.asarray(prompts, np.int32)
        B = prompts.shape[0]
        live_np = np.ones((B,), bool) if live is None \
            else np.asarray(live, bool)
        starts_np = np.zeros((B,), np.int32) if starts is None \
            else np.asarray(starts, np.int32).copy()
        publish = []
        if self.paged:
            self._check_gen(state)
            state = self._flush_released(state)
            lengths = (prompts != PAD).sum(axis=1)
            wants = {}
            rows, cols, vals = [], [], []
            for slot in np.nonzero(admit_mask)[0]:
                slot = int(slot)
                if slot not in self.pager.assigned:
                    # direct engine use (no scheduler claim): worst case
                    starts_np[slot] = 0
                    self.claim_slot(slot, int(lengths[slot]),
                                    self.gcfg.max_steps)
                nshared = int(starts_np[slot]) // self.page_size
                if nshared:
                    # splice matched pages in as table blocks 0..nshared-1
                    for blk, page in enumerate(
                            self.pager.assigned[slot][:nshared]):
                        rows.append(slot)
                        cols.append(blk)
                        vals.append(page)
                # tail prefill writes positions start .. Lp-1
                wants[slot] = min(self.nblk,
                                  pages_for(max(int(lengths[slot]), 1),
                                            self.page_size))
                full = max(int(lengths[slot]) - 1, 0) // self.page_size
                if self.prefix_cache and full:
                    publish.append(
                        (prompts[slot, :full * self.page_size], slot, full))
            state = self._ensure_blocks(state, wants,
                                        splice=(rows, cols, vals))
        elif starts_np.any():
            raise ValueError("prefix-cache starts require a paged engine")
        tails = pack_tails(prompts, starts_np)
        out = self._with_gen(
            self._jit_admit(state, jnp.asarray(admit_mask),
                            jnp.asarray(tails), jnp.asarray(starts_np),
                            jnp.asarray(live_np)),
            state)
        for tokens, slot, full in publish:
            self.pager.publish(tokens, self.pager.assigned[slot][:full])
        # refresh the host mirrors: an admitted slot ends the prefill at
        # pos == len(prompt) - 1 with pending == prompt[-1]; it is live
        # unless this was a partial (chunked) admission
        lengths = (prompts != PAD).sum(axis=1)
        admitted = np.nonzero(admit_mask)[0]
        self._known_pos[admitted] = np.maximum(lengths[admitted] - 1, 0)
        self._known_done[admitted] = ~live_np[admitted]
        return out

    def extend(self, state, mask: np.ndarray, chunks: np.ndarray,
               live: np.ndarray):
        """Scheduler API: commit continuation prefill chunks (chunked
        prefill) into mid-prefill slots.

        ``chunks`` (B,W; PAD-padded) holds each masked slot's next run of
        prompt tokens; ``live`` marks the rows whose final chunk this is
        (they come up decoding).  Pages for the chunk's positions are
        drawn lazily from the slot's admission claim, and the host
        ``pos``/``done`` mirrors advance so a pipelined dispatch keeps
        assigning pages without touching device state.  Publication of
        the prompt's full pages stays the *scheduler's* job (via
        :meth:`publish_prefix` after the final chunk): mid-prefill pages
        become matchable only once their content commit is ordered.
        """
        mask = np.asarray(mask, bool)
        chunks = np.asarray(chunks, np.int32)
        live_np = np.asarray(live, bool)
        lengths = (chunks != PAD).sum(axis=1)
        if self.paged:
            self._check_gen(state)
            state = self._flush_released(state)
            wants = {}
            for slot in np.nonzero(mask)[0]:
                slot = int(slot)
                # the chunk commits positions pos .. pos+len-1 plus the
                # benign garbage write at the new pos
                need = int(self._known_pos[slot]) + int(lengths[slot]) + 1
                wants[slot] = min(self.nblk,
                                  self.pager.max_blocks(slot),
                                  pages_for(need, self.page_size))
            state = self._ensure_blocks(state, wants)
        out = self._with_gen(
            self._jit_extend(state, jnp.asarray(mask),
                             jnp.asarray(chunks), jnp.asarray(live_np)),
            state)
        sel = np.nonzero(mask)[0]
        self._known_pos[sel] = self._known_pos[sel] + lengths[sel]
        self._known_done[sel] = ~live_np[sel]
        return out

    def publish_prefix(self, slot: int, tokens) -> int:
        """Publish ``slot``'s full committed pages of ``tokens`` to the
        radix index; returns the pages newly retained.

        ``tokens`` is the slot's committed context (prompt, or prompt +
        generated steps at preemption); per the engine invariant its last
        token is pending, so exactly ``(len - 1) // page_size`` pages are
        full and content-complete.  No-op on dense engines or with the
        prefix cache off.
        """
        if not self.prefix_cache or self.pager is None \
                or slot not in self.pager.assigned:
            return 0
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        full = min(max(tokens.size - 1, 0) // self.page_size,
                   len(self.pager.assigned[slot]))
        if not full:
            return 0
        return self.pager.publish(tokens[:full * self.page_size],
                                  self.pager.assigned[slot][:full])

    def preempt_slot(self, slot: int, tokens) -> int:
        """Pause a live slot: publish its full committed pages (so a later
        re-admission splices them back via the regular prefix match) and
        release the slot's pages/claim.  Returns the pages published.

        Publication must precede release — ``publish`` requires the
        caller to hold a reference to every published page.  The caller
        owns the rest of the lifecycle: force-done the row, free the
        scheduler slot and requeue ``tokens`` as the resume prompt.
        """
        published = self.publish_prefix(slot, tokens)
        self.release_slot(slot)
        return published

    def save_cache(self, state, path=None, *, roots=None) -> dict:
        """Snapshot the hot (refcount-free cached) radix subtrees of the
        live ``state``: token chunk keys, LRU clocks and the cached
        pages' KV rows — scale rows included for quantized pools.

        Returns the host-side snapshot dict (``serving.snapshot``
        format) and, when ``path`` is given, also writes it to disk as
        a single ``.npz``.  ``roots`` restricts the snapshot to the
        given preamble-group chunks (cache migration pushes one group);
        ``None`` snapshots everything cached.  No-op (empty snapshot)
        on dense engines or with the prefix cache off.
        """
        from repro.serving.snapshot import save_snapshot, snapshot_state
        snap = snapshot_state(self, state, roots=roots)
        if path is not None:
            save_snapshot(snap, path)
        return snap

    def load_cache(self, state, snapshot):
        """Splice a snapshot (dict or ``.npz`` path) into the live
        ``state``'s prefix cache; returns the new state.

        Page ids are remapped through the page pool's free list —
        restoring never overwrites pages currently referenced by live
        slots — and when the pool has fewer free pages than the
        snapshot has records only the coldest subtrees are dropped.
        The conservation ledger and ``scale_slots`` lockstep hold after
        every restore; restoring an empty snapshot is the identity.
        """
        from repro.serving.snapshot import load_snapshot, restore_state
        if isinstance(snapshot, (str, bytes)) or hasattr(snapshot,
                                                         "__fspath__"):
            snapshot = load_snapshot(snapshot)
        return restore_state(self, state, snapshot)

    def run(self, prompts: np.ndarray, rng, *,
            collect_stats: bool = True):
        """Fixed-batch run-to-completion: generate until EOS/max_steps.

        Returns (responses, stats); responses is a list of B lists of
        step-token arrays.  Kept as the simple batch API — the
        continuous-batching path lives in ``repro.serving.scheduler``.
        """
        g = self.gcfg
        B = prompts.shape[0]
        state = self.init_state(prompts)
        stats = EngineStats()
        responses = [[] for _ in range(B)]

        res = None
        for it in range(g.max_steps):
            rng, k1, k2 = jax.random.split(rng, 3)
            state, res = self.step_decode(state, k1, k2, stats=stats,
                                          collect_stats=collect_stats)
            for b in range(B):
                if not res.done_prev[b]:
                    toks = res.chosen[b][res.chosen[b] != PAD]
                    responses[b].append(toks)
            if res.done.all():
                break
        stats.requests_finished = 0 if res is None else int(res.done.sum())
        return responses, stats
