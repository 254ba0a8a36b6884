"""Engine / DecodeSession — the one inference surface over every decode mode.

    engine = Engine.create(model, params, sw, strategy="tree")
    session = engine.new_session()
    first = session.prefill({"tokens": prompts}, max_new_tokens=64)
    while not session.all_done():
        res = session.step()            # canonical StepResult, any strategy

``Engine`` binds (model, params, SpecEE weights, strategy) and jits the
strategy step exactly once; sessions share the compiled step. A session owns
one batched ``DecodeState`` plus per-row token budgets, EOS cut-off, and the
``done`` mask of the canonical ``StepResult``. For single steps that
bookkeeping runs host-side (the historical path, bit-preserved); for
``step(num_ticks=K)`` it moves INTO the jit as a device-resident carry so K
ticks run as one fused ``lax.while_loop`` ("megatick") with a single host
sync at the end — see DESIGN.md §6. The step/extend jits donate the decode
state (KV cache included, paged pools and page table too), so XLA updates
the cache in place instead of reallocating it every token; callers must not
read a state reference retained from before a step (donation deletes the
buffers loudly rather than corrupting them).

``step_async`` is the serving engine's pipelined variant: it dispatches a
megatick and returns a handle without blocking, keeping the budget/EOS/done
carry device-resident across megaticks so the NEXT megatick can dispatch
before the previous one's results are read (``finish_step`` syncs results +
host mirrors; admission/retirement between finish and the next dispatch
mirror their row edits onto the in-flight carry).

Session memory is owned by a ``KVCacheManager`` (``repro.api.cache``):
``new_session(..., cache="paged")`` swaps the slot-masked dense layout for
paged pools + a page table with zero changes to the step loop, and
``retire_row`` compacts a finished row (frees its pages, zeroes its length)
so idle slots stop paying attention span.

Two session styles:
  * whole-batch: ``prefill(prompts)`` then ``step()`` — examples, benchmarks;
  * slot-based (continuous batching): ``new_session(batch=B, max_seq=S)``
    pre-allocates empty rows; admission is either one-shot
    (``prefill_row(slot, prompt)``) or chunked Sarathi-style
    (``begin_admission`` + ``prefill_chunk``), which splits the prompt
    forward into fixed-token chunks so the serving loop can interleave them
    with decode ticks instead of stalling on long prompts.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as eng
from repro.core import draft as draft_lib
from repro.core import scheduler as sched_lib
from repro.models.model import Model
from repro.runtime import faultinject
from repro.runtime.telemetry import spanned

from repro.api.cache import (CacheSpec, KVCacheManager, insert_row_pytree,
                             make_cache_manager)
from repro.api.strategies import DecodeStrategy, get_strategy
from repro.api.types import StepResult

_NO_BUDGET = np.iinfo(np.int64).max
_DEV_NO_BUDGET = np.iinfo(np.int32).max     # device-carry budget cap


@dataclass
class MegatickHandle:
    """One dispatched-but-unread megatick (``DecodeSession.step_async``).

    ``out``/``carry`` hold device arrays that are still being computed;
    ``finish_step`` blocks on them. The carry captured here is the megatick's
    OUTPUT limits — the exact arrays the next megatick consumes as input.
    ``dirty`` collects rows whose HOST bookkeeping advanced after this
    dispatch (retire / re-admit mirror edits): for those rows the captured
    carry is stale, so ``finish_step`` keeps the host values instead of
    syncing from it.
    """
    out: Any
    carry: Any
    num_ticks: int
    dirty: set = field(default_factory=set)


class Engine:
    """Binds a model + weights to a decode strategy; factory for sessions."""

    def __init__(self, model: Model, params, sw=None,
                 strategy: Union[str, DecodeStrategy, None] = None,
                 quant=None, mesh=None, policy: str = "tp_dp"):
        self.model = model
        self.params = params
        self.sw = sw
        self.strategy = get_strategy(strategy)
        self.strategy.validate(model, sw)
        # tensor-parallel serving (DESIGN.md §9): a 2-D ("data","model") mesh
        # pins the weights with the Megatron-role specs and threads a static
        # ShardCtx into the jitted steps (sharded exit-gate verify). A mesh
        # whose 'model' extent is 1 runs the unsharded step, but its weights
        # and state are still pinned to that mesh's device — that is what
        # puts each one-chip replica of a ReplicaPool on its own chip.
        self.mesh = mesh
        self.policy = policy
        self.shard = None
        if mesh is not None:
            from repro.sharding.ctx import ShardCtx
            self.shard = ShardCtx.from_mesh(mesh)
        # weight-only quantization (repro.quant): ``quant`` is a QuantSpec /
        # "int8" / "int4" / None. The quantized bundle is a PARALLEL pytree —
        # ``self.params`` stays untouched (paper: early exiting "without
        # affecting the model original parameters") and rides into the jitted
        # step as an extra argument so the kernels see the int tiles.
        from repro import quant as quant_lib
        self.quant_spec = quant_lib.QuantSpec.resolve(quant)
        self.qw = quant_lib.quantize_params(params, sw, self.quant_spec)
        if mesh is not None:
            from repro.sharding import serving as shard_serving
            ps, ss, qs = shard_serving.engine_shardings(
                model, mesh, policy, self.params, self.sw, self.qw)
            self.params = jax.device_put(self.params, ps)
            if self.sw is not None:
                self.sw = jax.device_put(self.sw, ss)
            if self.qw is not None:
                self.qw = jax.device_put(self.qw, qs)
        self._prefill_view = None
        strat = self.strategy
        shard = self.shard
        # the decode state (KV cache pytree included — paged pools + page
        # table too) is DONATED: XLA updates the cache in place every tick
        # instead of reallocating it, and stale state references fail loudly.
        # Named functions name the programs (``jit_decode_step`` ...) in a
        # profiler trace.
        def decode_step(p, s, st, qw):
            return strat.step(model, p, s, st, qw=qw, shard=shard)

        def prefill_extend(p, toks, cache, n):
            return model.prefill_extend(p, toks, cache, n)

        self._step_jit = jax.jit(decode_step, donate_argnums=(2,))
        self._extend_jit = jax.jit(prefill_extend, donate_argnums=(2,))
        self._mega_jits = {}

    def megatick_jit(self, num_ticks: int):
        """The jitted K-tick fused step (compiled once per K). The state —
        where the KV cache lives — is donated; the (B,)-sized limits carry is
        NOT: the async pipeline passes megatick N's output limits straight
        into megatick N+1 while N's handle still holds them for the deferred
        host sync, so donating them would delete buffers the finish path
        reads."""
        fn = self._mega_jits.get(num_ticks)
        if fn is None:
            strat, model, shard = self.strategy, self.model, self.shard

            def decode_megatick(p, s, st, limits, qw):
                return strat.megatick(model, p, s, st, limits, num_ticks,
                                      qw=qw, shard=shard)
            fn = jax.jit(decode_megatick, donate_argnums=(2,))
            self._mega_jits[num_ticks] = fn
        return fn

    @classmethod
    def create(cls, model: Model, params, sw=None,
               strategy: Union[str, DecodeStrategy, None] = None,
               quant=None, mesh=None, policy: str = "tp_dp") -> "Engine":
        """The canonical constructor: ``Engine.create(model, params, sw,
        strategy="dense"|"specee"|"tree"|DecodeStrategy(...),
        quant=None|"int8"|"int4"|QuantSpec(...),
        mesh=None|jax.sharding.Mesh)``. A mesh with a 'model' axis of
        extent > 1 turns on tensor-parallel decode (DESIGN.md §9)."""
        return cls(model, params, sw=sw, strategy=strategy, quant=quant,
                   mesh=mesh, policy=policy)

    @property
    def emit_width(self) -> int:
        return self.strategy.emit_width(self.model)

    def shard_state(self, state, cache_mgr=None):
        """Pin a ``DecodeState`` to the engine's mesh layout (no-op without
        a mesh). Sessions call this wherever a state is (re)built from
        host values — empty-state alloc, whole-batch prefill, row insert,
        restore — so the jitted step always sees one stable input layout
        (drifting shardings would fork the jit cache per layout)."""
        if self.mesh is None:
            return state
        from repro.sharding import policies as pol
        from repro.sharding import serving as shard_serving
        specs = shard_serving.decode_state_specs(
            self.model, self.mesh, self.policy, state, cache_mgr=cache_mgr)
        return jax.device_put(state, pol.named(self.mesh, specs))

    def empty_state(self, cache_mgr: KVCacheManager, batch: int,
                    max_seq: int, prng):
        """An all-empty ``batch``-slot decode state in the engine's layout.
        Under a mesh it is allocated directly in its shards: a full-size
        paged pool would not fit one device on its way to being split."""
        def build():
            return self.strategy.empty_state(self.model, self.sw, batch,
                                             max_seq, prng=prng,
                                             cache=cache_mgr.empty_cache())
        if self.mesh is None:
            return build()
        from repro.sharding import policies as pol
        from repro.sharding import serving as shard_serving
        specs = shard_serving.decode_state_specs(
            self.model, self.mesh, self.policy, jax.eval_shape(build),
            cache_mgr=cache_mgr)
        return jax.jit(build, out_shardings=pol.named(self.mesh, specs))()

    def prefill_weights(self):
        """(params, sw) the prefill/admission paths consume.

        Under weight-only quantization the DECODE step sees the int tiles
        (dequant fused into the kernels); prefill must see the numerically
        identical dequantized weights, or the prefill-written KV cache and
        first token would come from the fp originals and diverge from what
        the quantized decode loop would have produced (visible at int4,
        where the quantization error is large enough to flip argmax). The
        dequantized view is materialized once and cached — prefill is the
        compute-bound cold path; the decode hot loop still runs on the
        compressed tiles."""
        if self.qw is None:
            return self.params, self.sw
        if self._prefill_view is None:
            from repro import quant as quant_lib
            self._prefill_view = quant_lib.dequantized_reference(
                self.params, self.sw, self.qw)
        return self._prefill_view

    def new_session(self, batch: Optional[int] = None,
                    max_seq: Optional[int] = None,
                    prng_seed: int = 0,
                    cache: Union[None, str, CacheSpec] = None
                    ) -> "DecodeSession":
        """``batch=None``: empty shell, populated by ``prefill(prompts)``.
        ``batch=B``: pre-allocated empty rows for slot-based serving
        (``max_seq`` defaults to the run's ``serve.max_seq_len``).
        ``cache``: "dense" (default) | "paged" | a ``CacheSpec`` — the
        KVCacheManager layout session memory lives in."""
        return DecodeSession(self, batch=batch, max_seq=max_seq,
                             prng_seed=prng_seed, cache=cache)


@dataclass
class Admission:
    """One in-flight chunked prefill (host-side handle).

    Created by ``DecodeSession.begin_admission``; each ``prefill_chunk`` call
    advances ``consumed`` by at most one chunk of prompt tokens. When the
    prompt is exhausted the session inserts the finished batch-1 state into
    ``row`` and ``first_token`` is set.
    """
    row: int
    tokens: np.ndarray
    max_new_tokens: Optional[int] = None
    eos_token: Optional[int] = None
    consumed: int = 0
    cache: Any = None               # batch-1 dense extend cache
    h_parts: List[Any] = field(default_factory=list)
    first_token: Optional[int] = None

    @property
    def prompt_len(self) -> int:
        return int(self.tokens.shape[0])

    @property
    def complete(self) -> bool:
        return self.first_token is not None

    @property
    def remaining(self) -> int:
        return self.prompt_len - self.consumed


class DecodeSession:
    def __init__(self, engine: Engine, batch: Optional[int] = None,
                 max_seq: Optional[int] = None, prng_seed: int = 0,
                 cache: Union[None, str, CacheSpec] = None):
        self.engine = engine
        self._prng_seed = prng_seed
        self._max_seq = max_seq
        self._cache_spec = CacheSpec.resolve(cache, engine.model.run.serve)
        self._state: Optional[eng.DecodeState] = None
        self.cache_mgr: Optional[KVCacheManager] = None
        self.batch: Optional[int] = None
        # device-resident decode limits (budget/emitted/eos/done/retired):
        # None = host bookkeeping is authoritative, rebuilt lazily at the
        # next megatick dispatch; non-None = carried device arrays threading
        # megatick→megatick (admission/retire mirror row edits onto them)
        self._dev_carry: Optional[dict] = None
        # dispatched-but-unread megaticks, oldest first (the async pipeline
        # dispatches N+1 before finishing N, so two can be outstanding)
        self._async_handles: List[MegatickHandle] = []
        if batch is not None:
            if max_seq is None:
                max_seq = engine.model.run.serve.max_seq_len
                self._max_seq = max_seq
            self.cache_mgr = self._make_manager(batch, max_seq)
            self._state = engine.empty_state(self.cache_mgr, batch, max_seq,
                                             jax.random.PRNGKey(prng_seed))
            self._alloc_bookkeeping(batch, live=False)

    def _make_manager(self, batch: int, max_seq: int) -> KVCacheManager:
        e = self.engine
        seq = e.strategy.cache_seq_len(e.model, max_seq)
        return make_cache_manager(e.model, batch, seq, self._cache_spec)

    # ----- host-side bookkeeping -----
    def _alloc_bookkeeping(self, batch: int, live: bool) -> None:
        self.batch = batch
        self._emitted = np.zeros(batch, np.int64)
        self._budget = np.full(batch, _NO_BUDGET, np.int64)
        self._eos: List[Optional[int]] = [None] * batch
        # empty slots count as done until a request is admitted
        self._done = np.full(batch, not live, bool)
        # rows compacted by retire_row: their logical length is pinned to 0
        # after every tick (the batched step advances len uniformly).
        # Never-admitted slots start retired too — without the pin their
        # cache["len"] creeps up every tick until it saturates the row's
        # paged capacity, and the degenerate attention at saturation
        # perturbs live rows through the batch-shared kernels (which breaks
        # the row-local determinism that eviction replay relies on)
        self._retired: set = set() if live else set(range(batch))
        self._dev_carry = None

    # ----- device-side decode-limit carry (megatick path) -----
    def _carry_from_host(self) -> dict:
        """Materialize the device-side limits from the host bookkeeping
        (dispatch-time lazy rebuild; 5 small (B,) transfers)."""
        B = self.batch
        retired = np.zeros(B, bool)
        if self._retired:
            retired[sorted(self._retired)] = True
        return {
            "budget": jnp.asarray(np.minimum(self._budget, _DEV_NO_BUDGET)
                                  .astype(np.int32)),
            "emitted": jnp.asarray(
                np.minimum(self._emitted, _DEV_NO_BUDGET).astype(np.int32)),
            "eos": jnp.asarray(np.asarray(
                [-1 if e is None else int(e) for e in self._eos], np.int32)),
            "done": jnp.asarray(self._done),
            "retired": jnp.asarray(retired),
        }

    def _mirror_row_to_dev(self, row: int) -> None:
        """Apply one row's host bookkeeping onto the in-flight device carry
        (enqueued .at ops, no sync) — admission/retirement between a megatick
        dispatch and the next must edit the carried arrays, not just the
        host mirrors the carry will overwrite at the next finish."""
        c = self._dev_carry
        if c is None:
            return
        eos = self._eos[row]
        self._dev_carry = {
            "budget": c["budget"].at[row].set(
                int(min(self._budget[row], _DEV_NO_BUDGET))),
            "emitted": c["emitted"].at[row].set(
                int(min(self._emitted[row], _DEV_NO_BUDGET))),
            "eos": c["eos"].at[row].set(-1 if eos is None else int(eos)),
            "done": c["done"].at[row].set(bool(self._done[row])),
            "retired": c["retired"].at[row].set(row in self._retired),
        }
        # outstanding megaticks were dispatched with a carry that predates
        # this edit: their finish must not roll the row's host mirrors back
        for h in self._async_handles:
            h.dirty.add(row)

    def _set_row_limits(self, row: int, max_new_tokens: Optional[int],
                        eos_token: Optional[int]) -> None:
        self._emitted[row] = 0
        self._budget[row] = (_NO_BUDGET if max_new_tokens is None
                             else max_new_tokens)
        self._eos[row] = eos_token
        self._done[row] = False

    def _account_row(self, row: int, toks: np.ndarray, count: int) -> int:
        """Apply budget + EOS to one row's raw emit; returns the kept count
        and updates ``done``/``emitted``."""
        if self._done[row]:
            return 0
        count = int(min(count, self._budget[row] - self._emitted[row]))
        eos = self._eos[row]
        if eos is not None:
            hits = np.nonzero(toks[:count] == eos)[0]
            if hits.size:
                count = int(hits[0]) + 1
                self._done[row] = True
        self._emitted[row] += count
        if self._emitted[row] >= self._budget[row]:
            self._done[row] = True
        return count

    def _wrap(self, raw: StepResult) -> StepResult:
        """Device → host + per-row budget/EOS accounting → canonical result.

        The single-tick path: accounting runs host-side, so any carried
        device limits are stale afterwards — drop them (the next megatick
        rebuilds from the host, which is authoritative here)."""
        self._dev_carry = None
        tokens = np.asarray(raw.tokens)
        counts = np.asarray(raw.counts).copy()
        for row in range(tokens.shape[0]):
            counts[row] = self._account_row(row, tokens[row], counts[row])
        return StepResult(tokens=tokens, counts=counts,
                          done=self._done.copy(),
                          exit_layer=np.asarray(raw.exit_layer),
                          accept_len=np.asarray(raw.accept_len),
                          exited=np.asarray(raw.exited),
                          units_run=np.asarray(raw.units_run),
                          head_passes=np.asarray(raw.head_passes))

    def all_done(self) -> bool:
        return self._state is None or bool(self._done.all())

    def row_done(self, row: int) -> bool:
        return bool(self._done[row])

    def live_rows(self) -> np.ndarray:
        return ~self._done

    # ----- cache management -----
    def can_admit(self, prompt_len: int = 0) -> bool:
        """Admission control: does the cache manager have room for one more
        request (paged: a full row reservation of free pages)?"""
        return self.cache_mgr is None or self.cache_mgr.can_admit(prompt_len)

    def retire_row(self, row: int) -> None:
        """Per-row compaction: release the finished row's cache footprint so
        the idle slot stops paying attention span (paged: pages return to
        the free list; dense: the logical length drops to zero).

        Safe under an in-flight megatick: the cache edits are functional ops
        enqueued on the in-flight output state (device ordering serializes
        them after the megatick's writes), and the row's done/retired bits
        are mirrored onto the carried limits so the NEXT megatick skips it."""
        assert self._state is not None and self.cache_mgr is not None
        self._done[row] = True
        self._retired.add(row)
        self._state = self._state._replace(
            cache=self.cache_mgr.retire_row(self._state.cache, row))
        self._mirror_row_to_dev(row)

    def row_span(self, row: int) -> int:
        """Attention span the row currently pays (tests/benchmarks)."""
        assert self._state is not None and self.cache_mgr is not None
        return self.cache_mgr.row_span(self._state.cache, row)

    # ----- checkpoint / restore / fault recovery (DESIGN.md §7) -----
    @property
    def in_flight(self) -> int:
        """Dispatched-but-unread async megaticks outstanding."""
        return len(self._async_handles)

    def abort_async(self) -> None:
        """Forget every dispatched-but-unread megatick (watchdog recovery).

        The host mirrors stay at their last *synced* values — which are
        authoritative precisely because the aborted megaticks' results were
        never read — and the device-limit carry is dropped, so the next
        dispatch rebuilds it from the host. ``self._state`` keeps pointing
        at the (still materializing) output buffers of the last dispatch;
        callers that suspect those values are poisoned must evict the
        affected rows, whose recompute replay rebuilds them from scratch.
        """
        self._async_handles.clear()
        self._dev_carry = None

    def snapshot(self) -> tuple:
        """-> ``(state_tree, meta)``: the full decode state of this session.

        ``state_tree`` is the device ``DecodeState`` pytree (KV pools + page
        table + draft cache + scheduler state + PRNG — everything the jitted
        step consumes); ``meta`` is a JSON-serializable dict of the host-side
        bookkeeping (budgets/emitted/eos/done/retired mirrors plus the cache
        manager's allocator state). Together they are sufficient for
        ``restore`` to resume decode token-identically. The caller must
        finish (or abort) outstanding async megaticks first — a snapshot
        straddling an unread dispatch would capture host mirrors that trail
        the device state.
        """
        assert self._state is not None and self.batch is not None, \
            "nothing to snapshot: session has no state"
        assert not self._async_handles, \
            "finish_step()/abort_async() outstanding megaticks before " \
            "snapshot()"
        meta = {
            "batch": int(self.batch),
            "max_seq": int(self._max_seq),
            "strategy": self.engine.strategy.name,
            "emitted": [int(x) for x in self._emitted],
            "budget": [None if int(b) >= _NO_BUDGET else int(b)
                       for b in self._budget],
            "eos": [None if e is None else int(e) for e in self._eos],
            "done": [bool(d) for d in self._done],
            "retired": sorted(int(r) for r in self._retired),
            "cache": self.cache_mgr.export_state(),
        }
        return self._state, meta

    def restore(self, state_tree, meta: dict) -> None:
        """Adopt a ``snapshot`` into THIS pre-allocated session.

        The session must have been built the same way as the one that
        snapshotted (same batch / max_seq / strategy / cache layout) —
        validated here before anything is touched. After restore the next
        ``step``/``step_async`` continues exactly where the saved session
        stopped (decode is deterministic: greedy argmax, and sampling keys
        derive from state that travels in the snapshot).
        """
        assert self._state is not None and self.batch is not None, \
            "restore needs a pre-allocated session (new_session(batch=B))"
        for key, have in (("batch", self.batch), ("max_seq", self._max_seq),
                          ("strategy", self.engine.strategy.name)):
            if meta[key] != have:
                raise ValueError(
                    f"snapshot {key}={meta[key]!r} does not match this "
                    f"session's {key}={have!r}")
        self.cache_mgr.import_state(meta["cache"])
        self._state = self.engine.shard_state(
            jax.tree_util.tree_map(jnp.asarray, state_tree), self.cache_mgr)
        self._emitted = np.asarray(meta["emitted"], np.int64)
        self._budget = np.asarray(
            [_NO_BUDGET if b is None else int(b) for b in meta["budget"]],
            np.int64)
        self._eos = [None if e is None else int(e) for e in meta["eos"]]
        self._done = np.asarray(meta["done"], bool)
        self._retired = set(int(r) for r in meta["retired"])
        self._dev_carry = None
        self._async_handles = []

    # ----- whole-batch entry -----
    def prefill(self, prompts, max_new_tokens: Optional[int] = None,
                eos_token: Optional[int] = None,
                max_seq: Optional[int] = None) -> StepResult:
        """Prefill the whole batch. ``prompts``: (B, T) int tokens or a
        ``{"tokens": ...}`` batch dict. Returns the first-token StepResult
        (the prefill's greedy argmax counts against the budget)."""
        e = self.engine
        batch = (dict(prompts) if isinstance(prompts, dict)
                 else {"tokens": jnp.asarray(prompts, jnp.int32)})
        B, T = batch["tokens"].shape
        if max_seq is None:
            max_seq = self._max_seq
        if max_seq is None:
            new = (max_new_tokens if max_new_tokens is not None
                   else e.model.run.serve.max_new_tokens)
            max_seq = T + new + e.emit_width + 1
        self._max_seq = max_seq
        pparams, psw = e.prefill_weights()
        first, self._state = e.strategy.init_state(
            e.model, pparams, psw, batch, max_seq,
            prng=jax.random.PRNGKey(self._prng_seed))
        self.cache_mgr = self._make_manager(B, max_seq)
        self._state = e.shard_state(
            self._state._replace(
                cache=self.cache_mgr.from_prefill(self._state.cache)),
            self.cache_mgr)
        self._alloc_bookkeeping(B, live=True)
        # the KV cache has max_seq slots: the budget is always bounded by the
        # remaining capacity so a budgetless session still terminates instead
        # of silently clobbering the last cache position
        cap = max(max_seq - T - 1, 1)
        budget = cap if max_new_tokens is None else min(max_new_tokens, cap)
        for row in range(B):
            self._set_row_limits(row, budget, eos_token)
        W, E = e.emit_width, e.model.num_exit_points
        raw = StepResult(
            tokens=jnp.pad(first[:, None], ((0, 0), (0, W - 1))),
            counts=jnp.ones((B,), jnp.int32),
            done=jnp.zeros((B,), bool),
            exit_layer=jnp.full((B,), E, jnp.int32),
            accept_len=jnp.zeros((B,), jnp.int32),
            exited=jnp.zeros((B,), bool),
            units_run=jnp.int32(0),
            head_passes=jnp.int32(0))
        return self._wrap(raw)

    # ----- slot-based admission (continuous batching) -----
    def _insert_state1(self, row: int, st1: eng.DecodeState, prompt_len: int,
                       max_new_tokens: Optional[int],
                       eos_token: Optional[int]) -> int:
        """Insert a finished batch-1 state into slot ``row`` (cache through
        the manager, the rest leaf-wise) + budget/EOS accounting. Returns the
        first token."""
        st = self._state
        self._retired.discard(row)
        cache = self.cache_mgr.insert_row(st.cache, row, st1.cache)
        B = self.batch
        self._state = eng.DecodeState(
            cache=cache,
            draft_cache=insert_row_pytree(st.draft_cache, st1.draft_cache,
                                          row, B),
            sched=insert_row_pytree(st.sched, st1.sched, row, B),
            last_token=insert_row_pytree(st.last_token, st1.last_token,
                                         row, B),
            h_last=insert_row_pytree(st.h_last, st1.h_last, row, B),
            prng=st.prng,
        )
        self._state = self.engine.shard_state(self._state, self.cache_mgr)
        cap = max(self._max_seq - prompt_len - 1, 1)
        budget = cap if max_new_tokens is None else min(max_new_tokens, cap)
        self._set_row_limits(row, budget, eos_token)
        tok = int(np.asarray(st1.last_token)[0])
        n = self._account_row(row, np.asarray([tok]), 1)
        assert n <= 1
        self._mirror_row_to_dev(row)
        return tok

    def prefill_row(self, row: int, prompt,
                    max_new_tokens: Optional[int] = None,
                    eos_token: Optional[int] = None) -> int:
        """Admit one request into slot ``row``: blocking batch-1 prefill,
        insert the resulting rows into the batched state. Returns the first
        token. (Chunked admission: ``begin_admission``/``prefill_chunk``.)"""
        assert self._state is not None and self.batch is not None, \
            "prefill_row needs a pre-allocated session (new_session(batch=B))"
        e = self.engine
        tokens = jnp.asarray(prompt, jnp.int32)[None, :]
        pparams, psw = e.prefill_weights()
        first, st1 = e.strategy.init_state(e.model, pparams, psw,
                                           {"tokens": tokens}, self._max_seq)
        return self._insert_state1(row, st1, tokens.shape[1],
                                   max_new_tokens, eos_token)

    # ----- chunked admission (Sarathi-style) -----
    def begin_admission(self, row: int, prompt,
                        max_new_tokens: Optional[int] = None,
                        eos_token: Optional[int] = None) -> Admission:
        """Start admitting one request into slot ``row``. The returned handle
        is advanced by ``prefill_chunk`` — the prompt forward happens there,
        a chunk per call, so the caller can interleave decode ticks."""
        assert self._state is not None and self.batch is not None, \
            "begin_admission needs a pre-allocated session"
        return Admission(row=row, tokens=np.asarray(prompt, np.int64),
                         max_new_tokens=max_new_tokens, eos_token=eos_token)

    @spanned("serve.admit.chunk")
    def prefill_chunk(self, adm: Admission,
                      max_tokens: Optional[int] = None) -> int:
        """Run at most ``max_tokens`` prompt tokens of ``adm``'s prefill.

        ``max_tokens=None`` (or an architecture without chunked-prefill
        support — recurrent/SSD/frontend stacks, DESIGN.md §4) falls back to
        the blocking one-shot path and completes the admission in one call.
        Returns the number of prompt tokens processed; when the prompt is
        exhausted the row is inserted and ``adm.first_token`` is set.
        """
        if adm.complete:
            return 0
        e = self.engine
        T = adm.prompt_len
        if max_tokens is None or not e.model.supports_chunked_prefill():
            assert adm.consumed == 0, \
                "cannot fall back to blocking admission mid-chunk"
            first = self.prefill_row(adm.row, adm.tokens,
                                     max_new_tokens=adm.max_new_tokens,
                                     eos_token=adm.eos_token)
            adm.consumed = T
            adm.first_token = first
            return T
        # chunked path: fixed-width padded chunk through the jitted extend
        C = int(max_tokens)
        if adm.cache is None:
            seq = e.strategy.cache_seq_len(e.model, self._max_seq)
            adm.cache = e.model.empty_cache(1, seq)
        n = min(C, adm.remaining)
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :n] = adm.tokens[adm.consumed:adm.consumed + n]
        pparams, _ = e.prefill_weights()
        h, adm.cache = e._extend_jit(pparams, jnp.asarray(chunk), adm.cache,
                                     jnp.int32(n))
        adm.h_parts.append(h[:, :n])
        adm.consumed += n
        if adm.remaining == 0:
            self._finish_admission(adm)
        return n

    @spanned("serve.admit.finish")
    def _finish_admission(self, adm: Admission) -> None:
        """Last chunk done: first token, draft prefill over the accumulated
        hiddens, batch-1 state assembly, row insert."""
        e = self.engine
        model = e.model
        params, sw = e.prefill_weights()
        tokens = jnp.asarray(adm.tokens, jnp.int32)[None, :]
        h_all = jnp.concatenate(adm.h_parts, axis=1)         # (1, T, D)
        logits = model.logits(params, h_all[:, -1, :])
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if sw is not None:
            seq = e.strategy.cache_seq_len(model, self._max_seq)
            embeds = model.embed(params, tokens)
            dcache = draft_lib.draft_prefill(model.cfg, sw.draft, embeds,
                                             h_all, seq)
        else:
            dcache = {}
        st1 = eng.DecodeState(
            cache=adm.cache,
            draft_cache=dcache,
            sched=sched_lib.init_state(1, model.run.specee),
            last_token=first,
            h_last=h_all[:, -1, :],
            prng=self._state.prng,
        )
        adm.first_token = self._insert_state1(
            adm.row, st1, adm.prompt_len, adm.max_new_tokens, adm.eos_token)
        adm.cache = None
        adm.h_parts = []

    # ----- decode tick -----
    def step(self, num_ticks: Optional[int] = None) -> StepResult:
        """Batched decode through the strategy's jitted step.

        ``num_ticks=None``/``1``: one tick, host-side budget/EOS accounting —
        the historical path, bit-preserved. ``num_ticks=K > 1``: one fused
        device-resident megatick (K ticks in one ``lax.while_loop`` with the
        accounting in the jitted carry and ONE host sync at the end) —
        token-identical to K single steps; the StepResult widens to the
        (B, K·W) megatick contract (see ``repro.api.types``).
        """
        assert self._state is not None, "prefill first"
        assert not self._async_handles, \
            "async megaticks are in flight; finish_step() them first"
        if num_ticks is None or int(num_ticks) == 1:
            e = self.engine
            # fault-injection site: fires BEFORE the donating jit call, so
            # the decode state is untouched and the caller may retry
            faultinject.check("dispatch")
            raw, self._state = e._step_jit(e.params, e.sw, self._state,
                                           e.qw)
            if self._retired:
                # compaction is sticky: the uniform len advance of the
                # batched step must not regrow a retired row's span
                cache = self._state.cache
                rows = jnp.asarray(sorted(self._retired), jnp.int32)
                self._state = self._state._replace(
                    cache=dict(cache, len=cache["len"].at[rows].set(0)))
            return self._wrap(raw)
        return self.finish_step(self.step_async(num_ticks))

    def step_async(self, num_ticks: int = 1) -> MegatickHandle:
        """Dispatch one megatick WITHOUT blocking on its results.

        The budget/EOS/done carry stays device-resident across async
        megaticks, so the caller may dispatch megatick N+1 before reading
        megatick N's results (the serving engine's pipeline) — correctness
        holds because the done mask travels in the carry, not on the host.
        Outstanding handles retire in dispatch order via ``finish_step``.
        """
        assert self._state is not None, "prefill first"
        K = int(num_ticks)
        assert K >= 1, f"num_ticks must be >= 1, got {K}"
        e = self.engine
        # fault-injection site: fires BEFORE the donating jit call, so the
        # decode state is untouched and the caller may retry the dispatch
        faultinject.check("dispatch")
        carry = (self._dev_carry if self._dev_carry is not None
                 else self._carry_from_host())
        out, self._state, carry = e.megatick_jit(K)(e.params, e.sw,
                                                    self._state, carry, e.qw)
        self._dev_carry = carry
        handle = MegatickHandle(out=out, carry=carry, num_ticks=K)
        self._async_handles.append(handle)
        return handle

    def finish_step(self, handle: MegatickHandle) -> StepResult:
        """Block on a dispatched megatick, sync host mirrors from its carry,
        and wrap the canonical (widened) StepResult. Handles finish oldest
        first (host mirrors advance monotonically through the pipeline), and
        finishing must precede any admission/retirement that reacts to the
        megatick's results."""
        assert self._async_handles and self._async_handles[0] is handle, \
            "megaticks finish in dispatch order (oldest handle first)"
        self._async_handles.pop(0)
        out = handle.out
        done = np.asarray(out["done"]).copy()
        emitted = np.asarray(handle.carry["emitted"]).astype(np.int64)
        # rows retired / re-admitted after this megatick's dispatch: the
        # host bookkeeping advanced past the dispatch-time carry — keep it
        # (the edit was mirrored onto the NEXT megatick's input, whose
        # finish will sync it back coherently)
        for row in handle.dirty:
            done[row] = self._done[row]
            emitted[row] = self._emitted[row]
        self._done = done
        self._emitted = emitted
        return StepResult(
            tokens=np.asarray(out["tokens"]),
            counts=np.asarray(out["counts"]),
            # the result's mask is the megatick's own dispatch-coherent view
            # (what _collect attributes to the dispatch-time slot snapshot),
            # not the merged host view — they differ only on dirty rows
            done=np.asarray(out["done"]),
            exit_layer=np.asarray(out["exit_layer"]),
            accept_len=np.asarray(out["accept_len"]),
            exited=np.asarray(out["exited"]),
            units_run=np.asarray(out["units_run"]),
            head_passes=np.asarray(out["head_passes"]),
            ticks=int(np.asarray(out["ticks"])),
            tick_counts=np.asarray(out["tick_counts"]),
            tick_live=np.asarray(out["tick_live"]))
