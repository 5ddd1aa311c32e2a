"""The unified Model API over all architecture families.

``Model`` wraps a :class:`repro.config.ModelConfig` and exposes:

  * ``param_specs()`` / ``init(rng)``    — parameter tree (spec / arrays)
  * ``forward(params, tokens, source)``  — full-sequence logits (training)
  * ``prefill(params, tokens, ...)``     — last-token logits + KV cache
  * ``decode_step(params, cache, tok, pos)`` — one-token serving step
  * ``extend(params, cache, tokens, start, live)`` — append a block of
    tokens to the cache in one pass (serving's prompt prefill)
  * ``init_cache(batch, max_seq)``       — zeroed cache pytree
  * ``score(params, tokens, ...)``       — log p(tokens) per position (GSI)
  * ``reward(params, tokens, ...)``      — PRM head scores per position

Layers are grouped into *pattern blocks* and scanned with ``jax.lax.scan``
(HLO size O(|pattern|), see DESIGN.md §5); the remainder layers (pattern
prefix) are applied unscanned at the end of the stack.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import blocks as B
from repro.models.common import (adtype, embed_specs, embed_tokens,
                                 init_params, norm_spec, rms_norm, spec,
                                 stack_specs, unembed)


def effective_pattern(cfg: ModelConfig):
    if cfg.family == "ssm":
        # pattern length controls the scan-body size (all blocks are rwkv);
        # the dry-run uses a 2-long body for its two-point cost accounting.
        return ("rwkv",) * len(cfg.layer_pattern)
    return tuple(cfg.layer_pattern)


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.pattern = effective_pattern(cfg)
        n = len(self.pattern)
        self.repeats = cfg.num_layers // n if cfg.scan_layers else 0
        rem = cfg.num_layers - self.repeats * n
        self.remainder = self.pattern[:rem] if cfg.scan_layers else \
            tuple(self.pattern * ((cfg.num_layers + n - 1) // n))[:cfg.num_layers]

    # ------------------------------------------------------------------
    # Parameters
    # ------------------------------------------------------------------
    def param_specs(self):
        cfg = self.cfg
        specs = {"embed": embed_specs(cfg), "final_ln": norm_spec(cfg.d_model)}
        if self.repeats:
            specs["blocks"] = {
                f"p{i}": stack_specs(B.block_specs(cfg, kind), self.repeats)
                for i, kind in enumerate(self.pattern)}
        if self.remainder:
            specs["rem"] = {
                f"r{i}": B.block_specs(cfg, kind)
                for i, kind in enumerate(self.remainder)}
        if cfg.encoder_layers:
            specs["encoder"] = {
                "blocks": stack_specs(B.block_specs(cfg, "enc"),
                                      cfg.encoder_layers),
                "final_ln": norm_spec(cfg.d_model),
            }
        if cfg.reward_head:
            specs["reward_head"] = {
                "w": spec((cfg.d_model, 1), ("embed", None)),
                "b": spec((1,), (None,), "zeros"),
            }
        return specs

    def init(self, rng):
        return init_params(self.param_specs(), rng, jnp.dtype(
            self.cfg.param_dtype))

    # ------------------------------------------------------------------
    # Encoder (audio family): frames (B, enc_seq, d) -> source embeddings
    # ------------------------------------------------------------------
    def encode(self, params, frames):
        cfg = self.cfg
        x = frames.astype(adtype(cfg))
        positions = jnp.arange(x.shape[1])

        def body(carry, bp):
            y, _, _ = B.block_apply(cfg, "enc", bp, carry, mode="train",
                                    positions=positions)
            return y, None

        x, _ = jax.lax.scan(body, x, params["encoder"]["blocks"])
        return rms_norm(x, params["encoder"]["final_ln"], cfg.norm_eps)

    # ------------------------------------------------------------------
    # Core stack
    # ------------------------------------------------------------------
    def _run_stack(self, params, x, *, mode, positions, cache=None,
                   source=None, max_seq=0, window_override=0, live=None,
                   pt=None, ctx=0):
        cfg = self.cfg
        aux_total = 0.0
        new_cache = {"blocks": None, "rem": None}

        apply = functools.partial(
            B.block_apply, cfg, mode=mode, positions=positions,
            source=source, max_seq=max_seq, window_override=window_override,
            live=live, pt=pt, ctx=ctx)

        if self.repeats:
            def body(carry, xs):
                h = carry
                bp, csl = xs
                out_slices, aux = {}, 0.0
                for i, kind in enumerate(self.pattern):
                    key = f"p{i}"
                    c = None if csl is None else csl[key]
                    h, nc, a = apply(kind, bp[key], h, cache=c)
                    out_slices[key] = nc
                    aux = aux + a
                return h, (out_slices, aux)

            cache_xs = None if cache is None else cache["blocks"]
            x, (stacked_cache, auxs) = jax.lax.scan(
                body, x, (params["blocks"], cache_xs))
            new_cache["blocks"] = stacked_cache
            aux_total = aux_total + jnp.sum(auxs) if self._has_aux() else 0.0

        if self.remainder:
            rem_cache = {}
            for i, kind in enumerate(self.remainder):
                key = f"r{i}"
                c = None if cache is None else cache["rem"][key]
                x, nc, a = apply(kind, params["rem"][key], x, cache=c)
                rem_cache[key] = nc
                aux_total = aux_total + a
            new_cache["rem"] = rem_cache

        x = rms_norm(x, params["final_ln"], cfg.norm_eps)
        return x, new_cache, aux_total

    def _has_aux(self):
        return bool(self.cfg.num_experts)

    # ------------------------------------------------------------------
    # Public entry points
    # ------------------------------------------------------------------
    def forward(self, params, tokens, *, source=None):
        """Training forward: (B,S) tokens -> (logits (B,S,V), aux_loss)."""
        cfg = self.cfg
        if cfg.encoder_layers:
            source = self.encode(params, source)
        x = embed_tokens(cfg, params["embed"], tokens)
        positions = jnp.arange(tokens.shape[1])
        x, _, aux = self._run_stack(params, x, mode="train",
                                    positions=positions, source=source)
        return unembed(cfg, params["embed"], x), aux

    def hidden(self, params, tokens, *, source=None):
        """Final hidden states (B,S,d) — used by score() and reward()."""
        cfg = self.cfg
        if cfg.encoder_layers:
            source = self.encode(params, source)
        x = embed_tokens(cfg, params["embed"], tokens)
        positions = jnp.arange(tokens.shape[1])
        x, _, _ = self._run_stack(params, x, mode="train",
                                  positions=positions, source=source)
        return x

    def prefill(self, params, tokens, *, source=None, max_seq=0):
        """(B,S) tokens -> (last-token logits (B,V), cache)."""
        cfg = self.cfg
        if cfg.encoder_layers:
            source = self.encode(params, source)
        x = embed_tokens(cfg, params["embed"], tokens)
        positions = jnp.arange(tokens.shape[1])
        x, cache, _ = self._run_stack(
            params, x, mode="prefill", positions=positions, source=source,
            max_seq=max_seq or tokens.shape[1],
            window_override=cfg.serve_window_override)
        logits = unembed(cfg, params["embed"], x[:, -1:])[:, 0]
        return logits, cache

    def decode_step(self, params, cache, tokens, positions, live=None,
                    return_hidden: bool = False, pt=None):
        """One serving step: tokens (B,1), positions (B,) -> (logits, cache).

        ``live`` (B,) bool freezes recurrent state for finished requests.
        ``return_hidden`` additionally returns the final hidden state (B,d)
        (used by the PRM reward head in the serving engine).  ``pt`` (B,
        nblk) routes attention layers through the paged KV-cache path.
        """
        cfg = self.cfg
        x = embed_tokens(cfg, params["embed"], tokens)
        x, new_cache, _ = self._run_stack(
            params, x, mode="decode", positions=positions, cache=cache,
            window_override=cfg.serve_window_override, live=live, pt=pt)
        logits = unembed(cfg, params["embed"], x)[:, 0]
        if return_hidden:
            return logits, new_cache, x[:, 0]
        return logits, new_cache

    def extend(self, params, cache, tokens, start, live, *, ctx: int,
               pt=None):
        """Append a block of tokens to the cache in one pass.

        tokens (B, W); start (B,) the position of each row's first token;
        live (B, W) the tokens to append: live token t of row b lands at
        ``start[b]`` plus the number of live tokens before it, and the
        others are skipped.  ``ctx`` (static, a multiple of the page size
        on the paged layout) is an upper bound on every position the
        block reaches.  Returns the new cache: no LM head, no reward head.
        Defined for stacks of full and local attention layers.
        """
        cfg = self.cfg
        n = live.astype(jnp.int32)
        positions = start[:, None] + jnp.cumsum(n, axis=1) - n
        x = embed_tokens(cfg, params["embed"], tokens)
        _, new_cache, _ = self._run_stack(
            params, x, mode="extend", positions=positions, cache=cache,
            window_override=cfg.serve_window_override, live=live, pt=pt,
            ctx=ctx)
        return new_cache

    def reward_from_hidden(self, params, h):
        """PRM head on a hidden state (..., d) -> reward in [0,1]."""
        rh = params["reward_head"]
        logit = (h.astype(jnp.float32) @ rh["w"].astype(jnp.float32)
                 )[..., 0] + rh["b"].astype(jnp.float32)
        return jax.nn.sigmoid(logit)

    def init_cache(self, batch: int, max_seq: int, *, pages: int = 0,
                   page_size: int = 0, kv_dtype=None):
        """Zeroed cache pytree; ``pages > 0`` selects the paged layout
        (attention leaves become shared page pools, see serving/pages.py).
        ``kv_dtype`` selects the page-pool storage format (int8/fp8 add
        per-page scale tensors alongside the pools)."""
        cfg = self.cfg
        cache = {"blocks": None, "rem": None}
        kw = dict(pages=pages, page_size=page_size, kv_dtype=kv_dtype)
        if self.repeats:
            def stack_zero(kind):
                one = B.init_block_cache(cfg, kind, batch, max_seq, **kw)
                return jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a[None], (self.repeats,) + a.shape), one)
            cache["blocks"] = {f"p{i}": stack_zero(k)
                               for i, k in enumerate(self.pattern)}
        if self.remainder:
            cache["rem"] = {f"r{i}": B.init_block_cache(cfg, k, batch,
                                                        max_seq, **kw)
                            for i, k in enumerate(self.remainder)}
        return cache

    def score(self, params, tokens, *, source=None):
        """log pi(tokens[t] | tokens[<t]) for t>=1 -> (B, S-1).

        The GSI target-scoring pass: one parallel forward, no generation.
        Dispatches to the fused logprob-gather kernel when enabled.
        """
        h = self.hidden(params, tokens[:, :-1], source=source)
        labels = tokens[:, 1:]
        from repro.kernels import ops
        w = params["embed"].get("unembed")
        if w is None:
            w = params["embed"]["embedding"].T
        return ops.logprob_gather(h, w, labels, self.cfg.vocab_size)

    def reward(self, params, tokens, *, source=None):
        """PRM: per-position reward in [0,1] -> (B,S)."""
        assert self.cfg.reward_head, "reward() needs cfg.reward_head"
        h = self.hidden(params, tokens, source=source)
        rh = params["reward_head"]
        logit = (h.astype(jnp.float32) @ rh["w"].astype(jnp.float32)
                 )[..., 0] + rh["b"].astype(jnp.float32)
        return jax.nn.sigmoid(logit)


@functools.lru_cache(maxsize=64)
def _cached_model(cfg: ModelConfig) -> Model:
    return Model(cfg)


def build_model(cfg: ModelConfig) -> Model:
    return _cached_model(cfg)
