"""Continuous-batching serving engine — a slot loop over ``DecodeSession``
with managed cache memory, scheduled admission, and fault tolerance.

vLLM-style slot model adapted to JAX's static shapes:
  * ``max_batch`` slots share one batched ``DecodeSession`` whose memory is
    owned by a ``KVCacheManager`` (``repro.api.cache``): **paged KV** by
    default (``ServeConfig.page_size`` pages + per-row page tables, free-page
    admission control), with the slot-masked dense layout available as the
    bit-identical reference (``cache="dense"``);
  * admission runs through the ``ChunkedPrefillScheduler``
    (``repro.api.scheduler``): prompts split into ``ServeConfig.
    prefill_chunk``-token chunks interleaved with decode ticks
    (Sarathi-style) — a live batch is never stalled more than one chunk
    budget per tick. ``prefill_chunk=0`` restores blocking whole-prompt
    admission;
  * every engine tick runs ONE batched strategy step for all live slots —
    dense, AR-SpecEE, or tree speculative decoding behind the same
    ``StepResult`` surface; finished rows retire *and compact*
    (``session.retire_row``): their pages return to the pool and their
    logical length drops to zero, so long-idle slots stop paying attention
    span — exactly the iteration-level scheduling of Orca/vLLM;
  * inactive slots are masked; their compute is wasted but bounded (the
    standard TPU static-batch trade-off; see DESIGN.md §3), and after
    compaction an idle slot's attention span is ~zero rather than its stale
    context length.

Serve-path adoption (ROADMAP): the engine defaults the fused exit-gate
pipeline ON (``ModelFlags.exit_gate_kernel``) — pass ``fused_gate=False`` to
pin the reference path. Sampling modes come from ``run.serve`` (greedy /
temperature) on the dense strategy; ``prng_seed`` seeds the session's PRNG
stream so sampled runs are reproducible per seed.

Device-resident multi-tick decode (PR 5, DESIGN.md §6): ``megatick=K`` folds
K decode ticks into one fused ``lax.while_loop`` dispatch (budget/EOS/done
accounting in the jitted carry — host sync once per K tokens instead of once
per token), and ``async_ticks`` (default ON when K > 1) pipelines the loop:
``step()`` dispatches megatick N+1 BEFORE blocking on megatick N's results,
so host-side detokenization, retirement, and chunked admission overlap
device compute. The pipeline is correct because the done mask rides in the
device carry (a megatick dispatched against rows that just finished runs
zero device ticks), at the cost of results and admissions lagging one
``step()`` call — ``run_to_completion`` drains the in-flight handle.

Fault tolerance (this PR, DESIGN.md §7) composes four mechanisms:
  * **checkpoint/restore** — ``checkpoint_now()`` drains the in-flight
    megatick, snapshots the session (device state + host mirrors + page
    allocator) plus the engine's request/queue/slot bookkeeping through
    ``repro.checkpoint``, and a fresh engine's ``restore_checkpoint()``
    resumes token-identically. Wired to SIGTERM via ``PreemptionGuard``:
    the next ``step()`` after the signal checkpoints and raises
    ``Preempted``.
  * **pool-pressure eviction** — when the scheduler's queue head sits
    blocked on ``can_admit`` for ``evict_patience`` consecutive ticks with
    a slot free, ``VictimPolicy`` picks a live row to evict: its pages are
    freed and the request requeues with its ORIGINAL prompt. After
    readmission the row deterministically re-emits its recorded tokens,
    which the engine *verifies* against the recorded output instead of
    re-appending (the recompute-prefix invariant) — divergence surfaces as
    ``ServingFault(site="replay")``.
  * **watchdog + backoff** — megatick dispatch retries through ``Backoff``
    before surfacing ``ServingFault(site="dispatch")``; a wedged or
    poisoned finish (``finish_timeout`` / ``nan_logits`` fault-injection
    sites, out-of-vocab token validation) aborts the async pipeline,
    evicts the affected rows (replay regenerates the lost tokens), and
    falls back to the synchronous tick path for ``cooldown_ticks``.
  * **fault log** — every recovery action lands in ``fault_log`` so tests
    assert the intended degradation path actually ran.

This engine is the PC/cloud *logic* deliverable; the multi-pod path lowers
the same strategy step through pjit (launch/serve.py, launch/dryrun.py).
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple, Union

import jax
import numpy as np

from repro.api import (CacheSpec, DecodeStrategy, DenseStrategy, Engine,
                       get_strategy)
from repro.api.scheduler import ChunkedPrefillScheduler
from repro.checkpoint import CheckpointManager
from repro.models.model import Model, build_model
from repro.runtime import faultinject
from repro.runtime.fault import PreemptionGuard, plan_replica_remesh
from repro.runtime.telemetry import Counters, span, spanned
from repro.serving.resilience import (Backoff, FaultEvent, FaultLog,
                                      Preempted, ServingFault, VictimInfo,
                                      VictimPolicy)


@dataclass
class Request:
    uid: int
    prompt: np.ndarray                  # (T,) int32
    max_new_tokens: int = 32
    eos_token: Optional[int] = None
    # filled by the engine
    output: List[int] = field(default_factory=list)
    exit_points: List[int] = field(default_factory=list)
    accept_lens: List[int] = field(default_factory=list)
    done: bool = False
    # eviction/recompute bookkeeping: after an eviction the first
    # ``replay_total`` tokens the re-admitted row emits are VERIFIED against
    # ``output`` (already recorded) rather than appended; ``replayed`` is the
    # verification cursor and ``evictions`` feeds VictimPolicy's protection
    replay_total: int = 0
    replayed: int = 0
    evictions: int = 0

    @property
    def replaying(self) -> bool:
        return self.replayed < self.replay_total


class ServingEngine:
    def __init__(self, model: Model, params, sw=None, specee: bool = True,
                 strategy: Union[str, DecodeStrategy, None] = None,
                 prng_seed: int = 0, fused_gate: bool = True,
                 cache: Union[None, str, CacheSpec] = "paged",
                 page_size: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 megatick: int = 1,
                 async_ticks: Optional[bool] = None,
                 checkpoint_dir: Optional[str] = None,
                 guard: Optional[PreemptionGuard] = None,
                 victim: Optional[VictimPolicy] = None,
                 evict_patience: int = 2,
                 watchdog_s: Optional[float] = None,
                 backoff: Optional[Backoff] = None,
                 cooldown_ticks: int = 8,
                 quant=None,
                 mesh=None, policy: str = "tp_dp",
                 fault_log_cap: int = 256):
        spec = CacheSpec.resolve(cache, model.run.serve)
        if page_size is not None:
            # the override obeys the same rule ServeConfig validates at
            # construction (pages tile the cache exactly)
            if page_size <= 0 or model.run.serve.max_seq_len % page_size:
                raise ValueError(
                    f"page_size ({page_size}) must be > 0 and divide "
                    f"max_seq_len ({model.run.serve.max_seq_len})")
            spec = dataclasses.replace(spec, page_size=page_size)
        self.cache_spec = spec
        flags = model.flags
        if bool(fused_gate) != getattr(flags, "exit_gate_kernel", False):
            flags = dataclasses.replace(flags,
                                        exit_gate_kernel=bool(fused_gate))
        # paged serving pairs with the page-table-aware decode kernel on real
        # hardware; off-TPU the kernel would run in interpret mode, so the
        # XLA gather path stays (same tokens — the kernel is a perf variant)
        if (spec.kind == "paged" and not flags.decode_kernel
                and jax.default_backend() == "tpu"):
            flags = dataclasses.replace(flags, decode_kernel=True)
        if flags is not model.flags:
            model = build_model(model.run, flags)
        self.model = model
        self.serve_cfg = model.run.serve
        if strategy is None:
            if specee and model.run.specee.enabled:
                strategy = "specee"
            elif self.serve_cfg.greedy:
                strategy = "dense"
            else:
                strategy = DenseStrategy(
                    temperature=self.serve_cfg.temperature)
        self.strategy = get_strategy(strategy)
        # ``quant``: None | "int8" | "int4" | QuantSpec — weight-only
        # compression applied once at engine build (parallel pytree; the
        # fp params are untouched and stay the checkpoint of record)
        # ``mesh``: a 2-D ("data","model") jax Mesh turns on tensor-parallel
        # decode for THIS engine (DESIGN.md §9); data parallelism lives one
        # level up in ``repro.serving.replica.ReplicaPool``
        self.engine = Engine.create(model, params, sw=sw,
                                    strategy=self.strategy, quant=quant,
                                    mesh=mesh, policy=policy)
        # remesh sources (DESIGN.md §10): ``Engine.create`` pins sharded
        # copies under the mesh's specs but never mutates the host pytrees,
        # so these references are all a device-loss rebuild needs — no
        # checkpoint round-trip
        self._src_params, self._src_sw = params, sw
        self._src_quant, self._src_policy = quant, policy
        self._src_seed = prng_seed
        B = self.serve_cfg.max_batch
        S = self.serve_cfg.max_seq_len
        self.B, self.S = B, S
        self.session = self.engine.new_session(batch=B, max_seq=S,
                                               prng_seed=prng_seed,
                                               cache=self.cache_spec)
        chunk = (self.serve_cfg.prefill_chunk if prefill_chunk is None
                 else prefill_chunk)
        self.scheduler = ChunkedPrefillScheduler(
            self.session, chunk_tokens=chunk or None)
        self.slots: List[Optional[Request]] = [None] * B
        self._inflight: Dict[int, Request] = {}
        self._next_uid = 0
        if megatick < 1:
            raise ValueError(f"megatick must be >= 1, got {megatick}")
        self.megatick = int(megatick)
        # pipelined ticks default ON whenever megaticks are on: the whole
        # point of folding K ticks into one dispatch is to overlap the
        # host work with device compute
        self.async_ticks = (self.megatick > 1 if async_ticks is None
                            else bool(async_ticks))
        self._handle: Optional[Tuple] = None   # in-flight async megatick
        # ----- fault tolerance (DESIGN.md §7) -----
        self.checkpoint_dir = checkpoint_dir
        # sync saves: a preemption checkpoint must be durable before the
        # process exits, and serving snapshots are small
        self.ckpt = (CheckpointManager(checkpoint_dir, keep=2,
                                       async_save=False)
                     if checkpoint_dir else None)
        self._own_guard = guard is None and checkpoint_dir is not None
        self.guard = (guard if guard is not None
                      else (PreemptionGuard() if checkpoint_dir else None))
        if self._own_guard and self.guard is not None:
            self.guard.install()
        self.victim = victim if victim is not None else VictimPolicy()
        self.evict_patience = int(evict_patience)
        self.watchdog_s = watchdog_s
        self.backoff = backoff if backoff is not None else Backoff()
        self.cooldown_ticks = int(cooldown_ticks)
        self._sync_cooldown = 0         # ticks left on the sync fallback path
        self._tick = 0
        self.fault_log = FaultLog(cap=fault_log_cap)
        self.completed: List[Request] = []   # finish order, survives restore
        self.counters = Counters()      # work run, for the engine's life

    @property
    def tp_degree(self) -> int:
        """Current tensor-parallel degree (1 = unsharded; drops on remesh)."""
        shard = self.engine.shard
        return shard.degree if shard is not None else 1

    # ----- request intake -----
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 32,
               eos_token: Optional[int] = None) -> Request:
        req = Request(uid=self._next_uid,
                      prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=max_new_tokens, eos_token=eos_token)
        self._next_uid += 1
        self._inflight[req.uid] = req
        self.scheduler.submit(req.uid, req.prompt,
                              max_new_tokens=req.max_new_tokens,
                              eos_token=req.eos_token)
        return req

    def adopt(self, prompt: np.ndarray, max_new_tokens: int = 32,
              eos_token: Optional[int] = None, recorded=(),
              stats=None) -> Request:
        """Admit a request that already emitted ``recorded`` tokens on
        ANOTHER engine (replica failover, DESIGN.md §9). The request
        re-prefills here and its first ``len(recorded)`` tokens run as
        verified replay — the PR-6 recompute invariant, which holds across
        replicas because they share weights and decode is deterministic —
        before new tokens append. ``stats`` optionally seeds the
        (exit_points, accept_lens) recorded so far, so the finished request's
        stats match an uninterrupted run. Empty ``recorded`` behaves exactly
        like ``submit``."""
        req = Request(uid=self._next_uid,
                      prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=max_new_tokens, eos_token=eos_token,
                      output=[int(t) for t in recorded],
                      replay_total=len(recorded), replayed=0)
        if stats is not None:
            req.exit_points = [int(x) for x in stats[0]]
            req.accept_lens = [int(x) for x in stats[1]]
        self._next_uid += 1
        self._inflight[req.uid] = req
        self.scheduler.submit(req.uid, req.prompt,
                              max_new_tokens=req.max_new_tokens,
                              eos_token=req.eos_token)
        return req

    @property
    def pending(self) -> List[Request]:
        """Requests not yet slotted: queued + the in-flight chunked
        admission (back-compat view — pre-PR3 a request stayed in
        ``pending`` until it occupied a slot)."""
        return [self._inflight[uid] for uid in
                self.scheduler.admitting + self.scheduler.queued]

    def _retire(self, row: int, req: Request,
                finished: List[Request]) -> None:
        req.done = True
        finished.append(req)
        self.slots[row] = None
        self.session.retire_row(row)    # compaction: free pages, zero span

    # ----- token accounting (replay-aware) -----
    def _admit_token(self, req: Request, tok: int) -> None:
        """Record a request's first token (admission). A re-admitted evicted
        request is in replay: the token is verified, not re-appended."""
        if req.replaying:
            want = int(req.output[req.replayed])
            if int(tok) != want:
                raise ServingFault(
                    "replay", f"uid={req.uid} diverged at token "
                    f"{req.replayed}: re-admission produced {int(tok)}, "
                    f"recorded {want}")
            req.replayed += 1
        else:
            req.output.append(int(tok))

    def _fold_tick(self, req: Request, toks: List[int], exit_point: int,
                   accept_len: int) -> None:
        """Fold one live device tick of one row into the request.

        Replay ticks (tokens the request emitted before an eviction) verify
        against the recorded output and contribute NO stats — their stats
        were recorded the first time, so the final exit_points/accept_lens
        match an uninterrupted run exactly. A tick that straddles the replay
        boundary verifies its prefix and appends the remainder (cannot
        happen when eviction sits on a tick boundary, which it always does —
        the engine drains the in-flight megatick before evicting — but the
        fold is tolerant)."""
        i = 0
        if req.replaying:
            n = min(len(toks), req.replay_total - req.replayed)
            want = [int(t) for t in req.output[req.replayed:req.replayed + n]]
            got = [int(t) for t in toks[:n]]
            if got != want:
                raise ServingFault(
                    "replay", f"uid={req.uid} diverged at token "
                    f"{req.replayed}: replay produced {got}, recorded {want}")
            req.replayed += n
            i = n
            if req.replaying or i == len(toks):
                return                  # fully-replayed tick: stats recorded
        req.output.extend(int(t) for t in toks[i:])
        req.exit_points.append(int(exit_point))
        req.accept_lens.append(int(accept_len))

    def _collect(self, res, slots: List[Optional[Request]],
                 finished: List[Request]) -> None:
        """Count one result's device work in ``counters`` (replayed ticks
        too: they are device work) and fold it into its requests, inside a
        ``serve.collect`` span that carries what it counted, so a trace of
        any window can sum it."""
        added = self.counters.add(ticks=int(res.ticks),
                                  units_run=int(res.units_run),
                                  head_passes=int(res.head_passes),
                                  decoded_tokens=int(np.sum(res.counts)))
        with span("serve.collect", **added):
            self._fold_result(res, slots, finished)

    def _fold_result(self, res, slots: List[Optional[Request]],
                     finished: List[Request]) -> None:
        """Fold one (possibly multi-tick) StepResult into the requests that
        occupied the slots WHEN THE MEGATICK WAS DISPATCHED: detokenize,
        per-tick exit stats, retire + compact. The snapshot matters in the
        async pipeline — a slot can be re-admitted between a megatick's
        dispatch and its finish, and the old result must not be attributed
        to (or retire) the new occupant. A request already retired by an
        earlier finish is skipped (later megaticks report it done again but
        emit nothing for it — the device done-mask guarantees counts 0)."""
        for slot in range(self.B):
            req = slots[slot]
            if req is None or req.done:
                continue
            toks = res.row_tokens(slot)
            if res.is_megatick:
                # (B, K·W) tokens are packed left-aligned in tick order, so
                # tick_counts slices them back into per-tick runs
                off = 0
                for t in range(int(res.ticks)):
                    if not bool(res.tick_live[slot, t]):
                        continue
                    n = int(res.tick_counts[slot, t])
                    self._fold_tick(req, toks[off:off + n],
                                    int(res.exit_layer[slot, t]),
                                    int(res.accept_len[slot, t]))
                    off += n
            else:
                self._fold_tick(req, toks, int(res.exit_layer[slot]),
                                int(res.accept_len[slot]))
            if res.done[slot]:
                # req not done => its slot has not been re-admitted (slots
                # only free at retirement), so slots[slot] is still req
                self._retire(slot, req, finished)

    # ----- dispatch / finish with recovery -----
    def _attempt(self, site: str, fn):
        """Run ``fn`` with the engine's backoff schedule; exhausting the
        retries surfaces a structured ``ServingFault`` carrying the site,
        the attempt count, and the last underlying error."""
        delays = list(self.backoff.delays())
        last: Optional[BaseException] = None
        for i in range(len(delays) + 1):
            try:
                return fn()
            except (ServingFault, KeyboardInterrupt):
                raise
            except Exception as err:
                last = err
                retrying = i < len(delays)
                self.fault_log.append(FaultEvent(
                    site=site, tick=self._tick,
                    action="retry" if retrying else "give_up",
                    detail=repr(err)))
                if retrying:
                    self.backoff.sleep(delays[i])
        raise ServingFault(site,
                           f"failed after {len(delays) + 1} attempts: "
                           f"{last!r}",
                           attempts=len(delays) + 1, cause=last) from last

    def _dispatch(self) -> Optional[Tuple]:
        """Dispatch one megatick (plus the slot snapshot its results will be
        attributed to) if any row may still be live. The host view can trail
        the device by one in-flight megatick, but only toward liveness (rows
        never un-finish between admissions), so a stale dispatch at worst
        runs zero device ticks. Dispatch failures retry through the backoff
        schedule — the fault-injection ``dispatch`` site (and any real error
        raised before the jit call donates the state) leaves the session
        intact, so a retry is safe."""
        if np.any(self.session.live_rows()):
            with span("serve.dispatch"):
                handle = self._attempt(
                    "dispatch", lambda: self.session.step_async(self.megatick))
            return handle, list(self.slots)
        return None

    def _checked(self, res) -> Tuple[object, bool]:
        """Validate a step result's tokens against the vocab range (the
        cheap host-side canary for device corruption — a NaN'd logits bank
        argmaxes/samples into garbage ids). The ``nan_logits`` injection
        site poisons the result here to exercise the recovery path."""
        tokens = np.asarray(res.tokens)
        if faultinject.fire("nan_logits"):
            tokens = np.full_like(tokens, -(1 << 30))
            res = res._replace(tokens=tokens)
        V = self.model.run.model.vocab_size
        counts = np.asarray(res.counts)
        for row in range(tokens.shape[0]):
            n = int(counts[row])
            if n and (np.any(tokens[row, :n] < 0)
                      or np.any(tokens[row, :n] >= V)):
                return res, False
        return res, True

    def _recover_lost(self, site: str, detail: str) -> None:
        """A megatick's results are lost or untrustworthy (wedged finish,
        poisoned tokens): abort the async pipeline and evict every live
        slotted request. The evictions requeue them with their original
        prompts; deterministic replay regenerates the lost tokens, so the
        recovery costs recompute but never output. Then cool down on the
        synchronous tick path."""
        self.session.abort_async()
        self._handle = None
        evicted = 0
        for row in range(self.B):
            req = self.slots[row]
            if req is not None and not req.done:
                self._evict(row, req, reason=site)
                evicted += 1
        self._sync_cooldown = self.cooldown_ticks
        self.fault_log.append(FaultEvent(
            site=site, tick=self._tick, action="recover",
            detail=f"{detail}; evicted={evicted} rows, sync cooldown "
                   f"{self.cooldown_ticks} ticks"))

    def _finish_handle(self, prev: Tuple, finished: List[Request]) -> None:
        """Block on a dispatched megatick and fold its results in, guarding
        the three failure modes: an injected wedge (``finish_timeout`` —
        results never arrive), poisoned tokens (``nan_logits`` / vocab-range
        validation), and a *slow but successful* finish (wall-clock over
        ``watchdog_s`` — results are kept, but the engine falls back to the
        sync path for ``cooldown_ticks`` so a degraded device stops
        accumulating in-flight work)."""
        handle, slots_at_dispatch = prev
        if faultinject.fire("finish_timeout"):
            self._recover_lost("finish_timeout",
                               "megatick finish wedged past watchdog")
            return
        t0 = time.monotonic()
        with span("serve.finish"):
            res = self.session.finish_step(handle)
        dt = time.monotonic() - t0
        res, ok = self._checked(res)
        if not ok:
            self._recover_lost("nan_logits",
                               "out-of-vocab tokens in megatick result")
            return
        if self.watchdog_s is not None and dt > self.watchdog_s:
            self._sync_cooldown = self.cooldown_ticks
            self.fault_log.append(FaultEvent(
                site="watchdog", tick=self._tick, action="sync_fallback",
                detail=f"finish blocked {dt * 1e3:.1f}ms > "
                       f"{self.watchdog_s * 1e3:.1f}ms"))
        self._collect(res, slots_at_dispatch, finished)

    def _drain(self, finished: List[Request]) -> None:
        """Finish the in-flight async megatick, if any, without dispatching
        a replacement (checkpoint / eviction barrier)."""
        prev, self._handle = self._handle, None
        if prev is not None:
            self._finish_handle(prev, finished)

    def _sync_step(self, finished: List[Request]) -> None:
        with span("serve.dispatch"):
            res = self._attempt(
                "dispatch",
                lambda: self.session.step(num_ticks=self.megatick))
        res, ok = self._checked(res)
        if not ok:
            self._recover_lost("nan_logits",
                               "out-of-vocab tokens in step result")
            return
        self._collect(res, self.slots, finished)

    # ----- pool-pressure eviction -----
    def _evict(self, row: int, req: Request, reason: str) -> None:
        """Evict a live row: free its pages, requeue the request with its
        ORIGINAL prompt. Deterministic replay re-emits (and the engine
        verifies) the already-recorded tokens after re-admission."""
        req.evictions += 1
        req.replay_total = len(req.output)
        req.replayed = 0
        self.slots[row] = None
        self.session.retire_row(row)    # pages back to the pool
        self._inflight[req.uid] = req
        self.scheduler.submit(req.uid, req.prompt,
                              max_new_tokens=req.max_new_tokens,
                              eos_token=req.eos_token)
        self.fault_log.append(FaultEvent(
            site=reason, tick=self._tick, action="evict",
            detail=f"uid={req.uid} row={row} progress={len(req.output)} "
                   f"evictions={req.evictions}"))

    def _maybe_evict(self, finished: List[Request]) -> None:
        """Pool-pressure graceful degradation: the queue head has been
        blocked on ``can_admit`` for ``evict_patience`` consecutive ticks
        while a slot sat free — evict the policy's victim so admission can
        proceed. The in-flight megatick drains FIRST so its tokens land in
        the victim's record before ``replay_total`` freezes (otherwise the
        late finish would append tokens the replay then duplicates)."""
        if self.scheduler.deferred_ticks < self.evict_patience:
            return
        self._drain(finished)
        cands = []
        for row in range(self.B):
            req = self.slots[row]
            if req is None or req.done:
                continue
            cands.append(VictimInfo(row=row, progress=len(req.output),
                                    pages=self.session.row_span(row),
                                    evictions=req.evictions))
        row = self.victim.select(cands)
        if row is None:
            return                      # every candidate is protected
        self._evict(row, self.slots[row], reason="pool_pressure")
        self.scheduler.deferred_ticks = 0

    # ----- elastic remesh on device loss (DESIGN.md §10) -----
    def remesh(self, mesh, site: str = "device_lost",
               detail: str = "") -> None:
        """Rebuild the decode stack on ``mesh`` (None = unsharded) and
        re-admit every unfinished request with verified replay.

        Order matters: the in-flight megatick drains FIRST so its tokens
        land in each request's record before ``replay_total`` freezes (the
        eviction invariant); then the chunked admission aborts back to the
        queue; then a fresh ``Engine`` re-``device_put``s the HOST
        params/spec-weights under the new mesh's Megatron specs, a fresh
        session re-shards the paged pools (``shard_state`` at alloc) and
        re-traces step/megatick for the new ``ShardCtx``. Re-admitted
        requests replay-verify their recorded tokens (PR 6/9: decode is
        deterministic and sharded ≡ unsharded, so the degraded engine is
        token-identical to the healthy run); stats recorded pre-remesh stay
        on the request, and replay ticks contribute none — the finished
        stats match an uninterrupted run exactly."""
        finished: List[Request] = []
        self._drain(finished)
        self.completed.extend(finished)
        self.scheduler.abort_active()
        chunk = self.scheduler.chunk_tokens
        pending: List[Request] = [
            req for req in self.slots if req is not None and not req.done]
        pending.extend(self._inflight[uid] for uid in self.scheduler.queued)
        # admission order on the rebuilt engine is uid order — deterministic
        # regardless of which rows happened to be slotted at the loss
        pending.sort(key=lambda r: r.uid)
        old_tp = self.tp_degree
        self.engine = Engine.create(self.model, self._src_params,
                                    sw=self._src_sw, strategy=self.strategy,
                                    quant=self._src_quant, mesh=mesh,
                                    policy=self._src_policy)
        self.session = self.engine.new_session(batch=self.B, max_seq=self.S,
                                               prng_seed=self._src_seed,
                                               cache=self.cache_spec)
        self.scheduler = ChunkedPrefillScheduler(self.session,
                                                 chunk_tokens=chunk)
        self.slots = [None] * self.B
        self._inflight = {}
        self._handle = None
        for req in pending:
            req.replay_total = len(req.output)
            req.replayed = 0
            self._inflight[req.uid] = req
            self.scheduler.submit(req.uid, req.prompt,
                                  max_new_tokens=req.max_new_tokens,
                                  eos_token=req.eos_token)
        self.fault_log.append(FaultEvent(
            site=site, tick=self._tick, action="remesh",
            detail=f"tp {old_tp}->{self.tp_degree} "
                   f"readmitted={len(pending)}"
                   + (f"; {detail}" if detail else "")))

    def _maybe_device_loss(self) -> None:
        """The ``device_lost`` injection site: deterministically drop the
        HIGHEST device from this engine's mesh between ticks. With a valid
        factorization over the survivors (``plan_replica_remesh``) the
        engine rebuilds in place at the lower TP degree; with none (already
        unsharded, or no device left) it drains what it can and surfaces
        ``ServingFault(site="device_lost")`` — standalone that's terminal,
        under a ``ReplicaPool`` it's the kill-and-requeue fallback."""
        if not faultinject.fire("device_lost"):
            return
        mesh = self.engine.mesh
        devices = (list(mesh.devices.flat)
                   if mesh is not None and self.engine.shard is not None
                   else [])
        lost = devices[-1] if devices else None
        surviving = devices[:-1]
        new_tp = plan_replica_remesh(len(surviving), self.tp_degree)
        if new_tp is None:
            self.drain()
            self.fault_log.append(FaultEvent(
                site="device_lost", tick=self._tick, action="give_up",
                detail=f"no factorization over {len(surviving)} surviving "
                       f"devices (tp={self.tp_degree})"))
            raise ServingFault(
                "device_lost",
                f"device lost with no valid remesh (tp={self.tp_degree}, "
                f"surviving={len(surviving)})")
        # a (1, 1) mesh at TP 1 keeps the engine on its surviving device
        new_mesh = jax.make_mesh(
            (1, new_tp), ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2,
            devices=surviving[:new_tp])
        self.remesh(new_mesh, detail=f"lost={lost}")

    def cancel(self, uid: int) -> bool:
        """Withdraw an unfinished request (deadline shedding): drop it from
        the queue/admission, or free its slot and pages. The in-flight
        megatick drains first so a slotted cancel retires a coherent row —
        if that drain FINISHES the request, it stays finished (it made the
        deadline after all). Returns True when the uid was found live."""
        if uid in self._inflight:
            if uid in self.scheduler.admitting:
                self.scheduler.abort_active()
            self.scheduler.remove(uid)
            del self._inflight[uid]
            return True
        for row in range(self.B):
            req = self.slots[row]
            if req is not None and req.uid == uid and not req.done:
                self.drain()
                if self.slots[row] is req and not req.done:
                    self.slots[row] = None
                    self.session.retire_row(row)
                return True
        return False

    # ----- checkpoint / restore (SIGTERM preemption) -----
    def _req_meta(self, req: Request) -> dict:
        return {"uid": int(req.uid),
                "prompt": [int(t) for t in req.prompt],
                "max_new": int(req.max_new_tokens),
                "eos": (None if req.eos_token is None
                        else int(req.eos_token)),
                "output": [int(t) for t in req.output],
                "exit_points": [int(x) for x in req.exit_points],
                "accept_lens": [int(x) for x in req.accept_lens],
                "done": bool(req.done),
                "replay_total": int(req.replay_total),
                "replayed": int(req.replayed),
                "evictions": int(req.evictions)}

    def _all_requests(self) -> Dict[int, Request]:
        reqs: Dict[int, Request] = {r.uid: r for r in self.completed}
        for r in self.slots:
            if r is not None:
                reqs[r.uid] = r
        reqs.update(self._inflight)
        return reqs

    def checkpoint_now(self) -> int:
        """Drain the in-flight megatick, snapshot the session + engine
        bookkeeping, write a step-atomic checkpoint. Returns the tick the
        checkpoint captures. The in-flight chunked admission is aborted back
        to the queue front (no pages are held until its final chunk, so the
        restore run simply re-prefills it)."""
        assert self.ckpt is not None, \
            "checkpoint_now() needs checkpoint_dir"
        self.drain()
        self.scheduler.abort_active()
        state, session_meta = self.session.snapshot()
        meta = {
            "session": session_meta,
            "serve": {
                "tick": int(self._tick),
                "uid_next": int(self._next_uid),
                "requests": [self._req_meta(r)
                             for r in self._all_requests().values()],
                "completed": [int(r.uid) for r in self.completed],
                "slots": [None if r is None else int(r.uid)
                          for r in self.slots],
                "queue": [int(u) for u in self.scheduler.queued],
            },
        }
        self.ckpt.save(self._tick, {"state": state}, extra=meta)
        self.fault_log.append(FaultEvent(
            site="sigterm", tick=self._tick, action="checkpoint",
            detail=f"saved tick {self._tick} to {self.ckpt.root}"))
        return self._tick

    def restore_checkpoint(self) -> bool:
        """Adopt the latest checkpoint into this freshly-built engine (same
        config). Returns False if the directory holds no committed
        checkpoint (first boot) — the engine then starts clean. After a
        True return the next ``step()`` continues the saved run
        token-identically."""
        assert self.ckpt is not None, \
            "restore_checkpoint() needs checkpoint_dir"
        hit = self.ckpt.restore_latest(like={"state": self.session._state})
        if hit is None:
            return False
        step, tree, extra = hit
        self.session.restore(tree["state"], extra["session"])
        sv = extra["serve"]
        self._tick = int(sv["tick"])
        self._next_uid = int(sv["uid_next"])
        reqs: Dict[int, Request] = {}
        for rm in sv["requests"]:
            reqs[int(rm["uid"])] = Request(
                uid=int(rm["uid"]),
                prompt=np.asarray(rm["prompt"], np.int32),
                max_new_tokens=int(rm["max_new"]),
                eos_token=(None if rm["eos"] is None else int(rm["eos"])),
                output=[int(t) for t in rm["output"]],
                exit_points=[int(x) for x in rm["exit_points"]],
                accept_lens=[int(x) for x in rm["accept_lens"]],
                done=bool(rm["done"]),
                replay_total=int(rm["replay_total"]),
                replayed=int(rm["replayed"]),
                evictions=int(rm["evictions"]))
        self.completed = [reqs[int(u)] for u in sv["completed"]]
        self.slots = [None if u is None else reqs[int(u)]
                      for u in sv["slots"]]
        self._inflight = {int(u): reqs[int(u)] for u in sv["queue"]}
        for uid in sv["queue"]:
            req = reqs[int(uid)]
            self.scheduler.submit(req.uid, req.prompt,
                                  max_new_tokens=req.max_new_tokens,
                                  eos_token=req.eos_token)
        self._handle = None
        self.fault_log.append(FaultEvent(
            site="sigterm", tick=self._tick, action="restore",
            detail=f"resumed from tick {step} in {self.ckpt.root}"))
        return True

    def _maybe_preempt(self) -> None:
        """SIGTERM (real, via ``PreemptionGuard``, or the ``sigterm``
        injection site) between ticks: drain, checkpoint if configured, and
        surface ``Preempted`` — the clean-shutdown signal for the launcher
        to exit and be restarted with ``--restore``."""
        hit = faultinject.fire("sigterm")
        if self.guard is not None and self.guard.should_save():
            hit = True
        if not hit:
            return
        if self.ckpt is not None:
            step = self.checkpoint_now()
            raise Preempted(step=step, path=self.ckpt.root)
        self.drain()
        raise Preempted(step=self._tick, path="")

    def close(self) -> None:
        """Release process-global hooks (the SIGTERM handler, if this engine
        installed its own guard)."""
        if self._own_guard and self.guard is not None:
            self.guard.uninstall()

    # ----- one batched engine tick -----
    @spanned("serve.step")
    def step(self) -> List[Request]:
        """Scheduled admission (≤ one prefill chunk while decode is live),
        one strategy megatick for all live slots, retire + compact finished.
        Returns the list of requests completed this call.

        With ``async_ticks`` the call is one pipeline stage: megatick N+1 is
        dispatched BEFORE megatick N's results are read, so the host work
        below (detokenization, retirement, chunked admission) overlaps device
        compute; results consequently arrive one call later than they did on
        the blocking path. During a recovery cooldown the pipeline is
        suspended and ticks run synchronously."""
        self._maybe_preempt()
        self._maybe_device_loss()
        self._tick += 1
        finished: List[Request] = []
        async_enabled = self.async_ticks and self._sync_cooldown == 0
        if self._sync_cooldown > 0:
            self._sync_cooldown -= 1
        prev, self._handle = self._handle, None
        if prev is not None:
            if async_enabled:
                # overlap: next megatick goes out before we block on this one
                self._handle = self._dispatch()
            self._finish_handle(prev, finished)
        live = bool(np.any(self.session.live_rows()))
        free = [s for s in range(self.B) if self.slots[s] is None]
        with span("serve.admit"):
            admitted = self.scheduler.tick(free, live_decode=live)
        self.counters.add(prefill_chunks=self.scheduler.last_tick_chunks,
                          prefill_tokens=self.scheduler.last_tick_tokens)
        for ev in admitted:
            req = self._inflight.pop(ev.uid)
            if req.max_new_tokens > 0:
                self._admit_token(req, ev.first_token)
            if self.session.row_done(ev.row):
                self._retire(ev.row, req, finished)
            else:
                self.slots[ev.row] = req
        self._maybe_evict(finished)
        if self._handle is None and np.any(self.session.live_rows()):
            if async_enabled:
                self._handle = self._dispatch()
            else:
                self._sync_step(finished)
        self.completed.extend(finished)
        return finished

    @property
    def in_flight(self) -> bool:
        """An async megatick is dispatched but its results are unread."""
        return self._handle is not None

    @property
    def busy(self) -> bool:
        """Work outstanding: queued/in-flight admission, live decode rows,
        or an in-flight async megatick awaiting its results."""
        return (self._handle is not None or self.scheduler.has_work()
                or bool(np.any(self.session.live_rows())))

    def drain(self) -> List[Request]:
        """Finish (without replacing) the in-flight async megatick; any
        requests it completes land in ``completed`` as usual."""
        finished: List[Request] = []
        self._drain(finished)
        self.completed.extend(finished)
        return finished

    def run_to_completion(self, max_ticks: int = 10_000) -> List[Request]:
        done: List[Request] = []
        for _ in range(max_ticks):
            done.extend(self.step())
            if not self.busy:
                return done
        raise ServingFault(
            "stall",
            f"still busy after {max_ticks} ticks: "
            f"queued={len(self.scheduler.queued)} "
            f"admitting={len(self.scheduler.admitting)} "
            f"live={int(np.sum(self.session.live_rows()))} "
            f"in_flight={self.in_flight}")
