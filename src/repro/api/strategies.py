"""Pluggable decode strategies — adapters from the jittable step functions
(`repro.core.engine`, the kernels-of-record) to the canonical ``StepResult``.

A strategy owns everything mode-specific: how the state is initialized (the
tree mode reserves scratch cache slots), how wide a step's emit can be, and
which engine step runs per tick. Exit-gate backend selection
(``ModelFlags.exit_gate_kernel``) resolves INSIDE the engine entry points via
``exit_gate.ops.impl_for_flags`` — callers of this API never touch it.

``strategy.step`` is pure and jit-compatible: ``DecodeSession`` jits it once;
``launch/dryrun.py`` lowers it against the production mesh as-is.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple, Union

import jax.numpy as jnp

from repro.core import engine as eng
from repro.core.tree import TreeSpec
from repro.models.model import Model

from repro.api.types import StepResult


def _no_done(B: int):
    return jnp.zeros((B,), bool)


def _single_token_result(token, info: eng.StepInfo) -> StepResult:
    """Pack a 1-token-per-tick engine emit (dense / AR) as a StepResult."""
    B = token.shape[0]
    return StepResult(tokens=token[:, None],
                      counts=jnp.ones((B,), jnp.int32),
                      done=_no_done(B),
                      exit_layer=info.exit_point,
                      accept_len=jnp.zeros((B,), jnp.int32),
                      exited=info.exited,
                      units_run=info.units_run,
                      head_passes=info.head_passes)


@dataclass(frozen=True)
class DecodeStrategy:
    """Base: one decode mode behind the Engine/DecodeSession surface."""
    name = "base"
    requires_sw = True

    def emit_width(self, model: Model) -> int:
        return 1

    def cache_seq_len(self, model: Model, max_seq: int) -> int:
        """State slots to allocate for a ``max_seq`` session (tree mode
        reserves its node-scratch region on top)."""
        return max_seq

    def validate(self, model: Model, sw) -> None:
        if self.requires_sw and sw is None:
            raise ValueError(f"{type(self).__name__} needs SpecEE weights "
                             "(draft + predictors); pass sw=")

    def init_state(self, model: Model, params, sw,
                   batch: Dict[str, jnp.ndarray], max_seq: int, prng=None
                   ) -> Tuple[jnp.ndarray, eng.DecodeState]:
        """Prefill → (first greedy token (B,), state). ``max_seq`` is the
        SESSION length; strategies add their own scratch internally."""
        return eng.init_decode_state(model, params, sw, batch,
                                     self.cache_seq_len(model, max_seq),
                                     prng=prng)

    def empty_state(self, model: Model, sw, batch: int, max_seq: int,
                    prng=None, cache=None) -> eng.DecodeState:
        """``cache``: a pre-built cache pytree from the session's
        ``KVCacheManager`` (dense or paged layout); None keeps the dense
        allocation. The strategy step functions read the layout off the
        state itself (``cache["page_table"]``), so one jitted step serves
        both."""
        return eng.empty_decode_state(model, sw, batch,
                                      self.cache_seq_len(model, max_seq),
                                      prng=prng, cache=cache)

    def step(self, model: Model, params, sw, state: eng.DecodeState,
             qw=None, shard=None) -> Tuple[StepResult, eng.DecodeState]:
        """``qw``: optional quantized-weight bundle
        (``repro.quant.quantize_params``) threaded into the engine step —
        a parallel pytree; the original ``params`` stay untouched.
        ``shard``: optional ``repro.sharding.ctx.ShardCtx`` — the engine
        runs its full-LM-head reductions as per-shard partials (DESIGN.md
        §9); threaded statically from ``Engine`` (it keys the jit cache)."""
        raise NotImplementedError

    def megatick(self, model: Model, params, sw, state: eng.DecodeState,
                 limits, num_ticks: int, qw=None, shard=None):
        """Fuse ``num_ticks`` strategy steps into one device-resident
        ``lax.while_loop`` (``engine.megatick_decode``): per-row budgets, EOS
        cut-off, and the done mask ride in the jitted carry, so host sync
        happens once per megatick instead of once per tick. Works unchanged
        for every strategy — the adapter below is the only mode-specific
        glue. Returns ``(out dict, new_state, new_limits)``."""
        def tick(st):
            res, new_st = self.step(model, params, sw, st, qw=qw,
                                    shard=shard)
            return eng.TickEmit(tokens=res.tokens, counts=res.counts,
                                exit_layer=res.exit_layer,
                                accept_len=res.accept_len,
                                exited=res.exited,
                                units_run=res.units_run,
                                head_passes=res.head_passes), new_st
        return eng.megatick_decode(tick, state, limits, num_ticks,
                                   self.emit_width(model),
                                   model.num_exit_points)


@dataclass(frozen=True)
class DenseStrategy(DecodeStrategy):
    """Full-depth baseline. Greedy by default; ``temperature > 0`` samples
    from the full logits, consuming the session's PRNG stream (seeded via
    ``Engine.new_session(prng_seed=...)`` / ``ServingEngine(prng_seed=...)``).
    """
    temperature: float = 0.0
    top_k: Optional[int] = None
    name = "dense"
    requires_sw = False

    def step(self, model, params, sw, state, qw=None, shard=None):
        token, new_state, info = eng.dense_decode_step(
            model, params, sw, state, temperature=self.temperature,
            top_k=self.top_k, qw=qw, shard=shard)
        return _single_token_result(token, info), new_state


@dataclass(frozen=True)
class SpecEEStrategy(DecodeStrategy):
    """Autoregressive speculative early exiting (paper T1+T2).

    ``threshold=None`` takes ``run.specee.exit_threshold``; a threshold > 1
    disables exits (bit-identical to dense greedy — the property the
    session-level parity tests pin).
    """
    threshold: Optional[float] = None
    name = "specee"

    def step(self, model, params, sw, state, qw=None, shard=None):
        token, new_state, info = eng.ar_decode_step(
            model, params, sw, state, threshold=self.threshold, qw=qw,
            shard=shard)
        return _single_token_result(token, info), new_state


@dataclass(frozen=True)
class TreeStrategy(DecodeStrategy):
    """T3: tree speculative decoding with the hyper-token merged mapping.

    Emits up to ``tree.depth + 1`` tokens per tick (accepted chain + bonus).
    ``tree=None`` builds the TreeSpec from ``run.specee.tree_depth/_branch``.
    """
    tree: Optional[TreeSpec] = None
    threshold: Optional[float] = None
    name = "tree"

    def tree_for(self, model: Model) -> TreeSpec:
        if self.tree is not None:
            return self.tree
        spec = model.run.specee
        return TreeSpec(depth=spec.tree_depth, branch=spec.tree_branch)

    def emit_width(self, model):
        return self.tree_for(model).depth + 1

    def cache_seq_len(self, model, max_seq):
        return max_seq + self.tree_for(model).num_nodes

    def validate(self, model, sw):
        super().validate(model, sw)
        if not model.supports_tree():
            raise ValueError(
                "tree strategy requires a pure-attention stack (DESIGN.md "
                f"§4); {model.cfg.name} is {model.cfg.family}")
        if model.flags.kv_quant:
            raise ValueError(
                "tree strategy does not support kv_quant: tree scratch "
                "writes are full-precision (the node K/V is re-read within "
                "the same step, where int8 round-tripping would corrupt "
                "verification); decode with the AR engine instead "
                "(DESIGN.md §4)")

    def step(self, model, params, sw, state, qw=None, shard=None):
        out, n_emit, new_state, info = eng.tree_decode_step(
            model, params, sw, state, self.tree_for(model),
            threshold=self.threshold, qw=qw, shard=shard)
        B = out.shape[0]
        res = StepResult(tokens=out,
                         counts=n_emit.astype(jnp.int32),
                         done=_no_done(B),
                         exit_layer=info.exit_point,
                         accept_len=info.accepted_len,
                         exited=info.exited,
                         units_run=info.units_run,
                         head_passes=info.head_passes)
        return res, new_state


_BY_NAME = {
    "dense": DenseStrategy,
    "specee": SpecEEStrategy,
    "ar": SpecEEStrategy,
    "tree": TreeStrategy,
}


def get_strategy(spec: Union[str, DecodeStrategy, None]) -> DecodeStrategy:
    """Resolve a strategy name or pass an instance through.

    Names: "dense" | "specee" (alias "ar") | "tree".
    """
    if spec is None:
        return SpecEEStrategy()
    if isinstance(spec, DecodeStrategy):
        return spec
    try:
        return _BY_NAME[spec]()
    except KeyError:
        raise ValueError(
            f"unknown strategy {spec!r}; expected one of {sorted(_BY_NAME)} "
            "or a DecodeStrategy instance") from None
