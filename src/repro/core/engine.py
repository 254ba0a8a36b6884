"""SpecEE decode engines (paper Fig. 3 dataflow) — the jittable
kernels-of-record beneath the unified decode API.

Application code decodes through ``repro.api`` (Engine / DecodeSession /
StepResult with pluggable strategies — see docs/api.md); the step functions
here are the pure computations those strategies adapt, and the only
sanctioned direct callers are ``repro/api`` and the tests.

``ar_decode_step``  — autoregressive decoding with speculative early exiting:
    draft k speculative tokens → layer-by-layer ``lax.while_loop`` with the
    T1 predictor at T2-scheduled exit points → verification (full LM head at
    the candidate exit layer; exit iff global argmax ∈ speculative set) →
    KV/state propagation for skipped layers.

``tree_decode_step`` — T3: EAGLE-style tree speculative decoding with the
    context-aware merged (hyper-token) mapping; one predictor evaluation per
    root→leaf path, exit at the rearmost (Cannikin) layer, acceptance by
    greedy path matching at the exit layer.

Per-exit-point decisions flow through the fused exit-gate entry points
(``repro.kernels.exit_gate.ops``), selected by ``ModelFlags.exit_gate_kernel``:
the AR gate (spec-head features + predictor) runs as ONE Pallas kernel and
verification/emit streams the LM head (running argmax, no (B, V) logits).
The tree gate fuses its three stages piecewise — spec-head feature kernel,
banked predictor-MLP kernel, streaming node verify — because the hyper-token
min-merge sits between features and predictor (folding the merge into the
gate kernel is a ROADMAP follow-on). With the flag off the same entry points
pin the historical four-op reference sequence bit-for-bit.

Semantics guarantees (property-tested in tests/):
  * with the predictor disabled (threshold > 1) the emitted tokens are
    bit-identical to dense greedy decoding. Caveat: the fused verify
    accumulates logits in fp32; with bf16 weights on TPU a near-exact tie in
    the top-2 logits may therefore resolve differently than the bf16 dense
    matmul (a numerics improvement, exercised only when the fused flag is on);
  * when a row exits, its emitted token equals argmax of the FULL LM head at
    the exit layer (verification), and is a member of the speculative set.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import RunConfig, SpecEEConfig
from repro.core import draft as draft_lib
from repro.core import features as feat_lib
from repro.core import predictor as pred_lib
from repro.core import scheduler as sched_lib
from repro.kernels.exit_gate import ops as gate_lib
from repro.models import common
from repro.models.common import Params, lm_head_weight
from repro.models.model import Model


def _apply_qw(params: Params, sw: Optional["SpecEEWeights"], qw):
    """Resolve one step's weight views under an optional quantized bundle
    (the ``repro.quant.quantize_params`` output, threaded down from the API
    layer as an extra jit argument).

    Returns ``(params', lm_w, predictors)``: ``params'`` has quantized
    projection leaves replaced by dequantized views (weight-only — XLA fuses
    the dequant into the consuming matmul), ``lm_w`` is the ``QTensor`` LM
    head when quantized (the exit-gate / spec-head ops dispatch on the type
    and keep the int tiles resident) else the fp ``lm_head_weight``, and
    ``predictors`` the quantized bank when present. The original ``params``
    and ``sw`` pytrees are never touched — the bundle is a parallel tree.
    """
    predictors = sw.predictors if sw is not None else None
    if not qw:
        return params, lm_head_weight(params), predictors
    from repro import quant as quant_lib
    if qw.get("proj") is not None:
        params = quant_lib.merge_dequant(params, qw["proj"])
    lm_w = qw.get("lm_head")
    if lm_w is None:
        lm_w = lm_head_weight(params)
    if qw.get("predictors") is not None:
        predictors = qw["predictors"]
    return params, lm_w, predictors


def _gate_impls(model: Model) -> Tuple[str, bool]:
    """Exit-gate backend selection for a model's flags.

    Returns (impl for ``gate_lib.exit_gate``/``verify_argmax``, fused?).
    With ``exit_gate_kernel`` off the engine still flows through the same
    entry points, pinned to the "ref" impl — the historical four-op sequence,
    bit-for-bit (this is the numerics reference the fused path is property-
    tested against). Resolution lives in ``gate_lib.impl_for_flags`` so the
    decode strategies (repro.api) share the exact same selection.
    """
    fused = getattr(model.flags, "exit_gate_kernel", False)
    return gate_lib.impl_for_flags(model.flags), fused


class SpecEEWeights(NamedTuple):
    """Everything SpecEE adds next to the frozen target model."""
    draft: Params
    predictors: Params          # stacked over exit points
    offline_mask: jnp.ndarray   # (E,) bool — T2 offline schedule


class DecodeState(NamedTuple):
    cache: Any                  # target model cache (segments + len)
    draft_cache: Any
    sched: Dict[str, jnp.ndarray]
    last_token: jnp.ndarray     # (B,)
    h_last: jnp.ndarray         # (B, D) final hidden at the last position
    prng: jnp.ndarray


class StepInfo(NamedTuple):
    exit_point: jnp.ndarray     # (B,) unit index at exit (E if ran full depth)
    exited: jnp.ndarray         # (B,) bool — predictor-driven exit happened
    units_run: jnp.ndarray      # () int32 — units the while loops executed
    head_passes: jnp.ndarray    # () int32 — full LM-head passes the step ran


def init_specee(model: Model, key) -> SpecEEWeights:
    """Seeded SpecEE weights: the draft layer in ``cfg.dtype`` (drawn and
    cast in one jit, like ``Model.init``), the predictor bank in fp32."""
    spec = model.run.specee
    dt = common.dtype_of(model.cfg.dtype)

    def draw(key):
        k1, k2 = jax.random.split(key)
        return SpecEEWeights(
            draft=common.cast_tree(draft_lib.init_draft(model.cfg, k1), dt),
            predictors=pred_lib.init_predictors(spec, model.num_exit_points,
                                                k2),
            offline_mask=jnp.ones((model.num_exit_points,), bool),
        )
    return jax.jit(draw)(key)


def init_decode_state(model: Model, params: Params,
                      sw: Optional[SpecEEWeights],
                      batch: Dict[str, jnp.ndarray], max_seq: int,
                      prng=None) -> Tuple[jnp.ndarray, DecodeState]:
    """Prefill the target + draft and build the decode state.

    ``sw=None`` builds a dense-only state (no draft cache) — only
    ``dense_decode_step`` may consume it. Returns (first greedy token (B,),
    state)."""
    spec = model.run.specee
    logits, cache, extras = model.prefill(params, batch, max_seq=max_seq)
    h_all = extras["h_final"]                              # (B, S, D)
    if sw is not None:
        embeds = model.embed(params, batch["tokens"])
        dcache = draft_lib.draft_prefill(model.cfg, sw.draft, embeds, h_all,
                                         max_seq)
    else:
        dcache = {}
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    state = DecodeState(
        cache=cache,
        draft_cache=dcache,
        sched=sched_lib.init_state(h_all.shape[0], spec),
        last_token=first,
        h_last=h_all[:, -1, :],
        prng=prng if prng is not None else jax.random.PRNGKey(0),
    )
    return first, state


def empty_decode_state(model: Model, sw: Optional[SpecEEWeights], batch: int,
                       max_seq: int, prng=None, cache=None) -> DecodeState:
    """All-zeros batched state with ``batch`` empty slots — the serving
    engine's starting point: rows are later populated by inserting batch-1
    ``init_decode_state`` results (continuous batching).

    ``cache``: a pre-built cache pytree from a ``KVCacheManager``
    (``repro.api.cache``) — e.g. the paged pool + page table layout; None
    keeps the historical dense allocation."""
    dtype = common.dtype_of(model.cfg.dtype)
    return DecodeState(
        cache=cache if cache is not None else model.empty_cache(batch,
                                                                max_seq),
        draft_cache=(draft_lib.draft_cache(model.cfg, batch, max_seq, dtype)
                     if sw is not None else {}),
        sched=sched_lib.init_state(batch, model.run.specee),
        last_token=jnp.zeros((batch,), jnp.int32),
        h_last=jnp.zeros((batch, model.cfg.d_model), dtype),
        prng=prng if prng is not None else jax.random.PRNGKey(0),
    )


# ---------------------------------------------------------------------------
# autoregressive SpecEE step
# ---------------------------------------------------------------------------
def ar_decode_step(model: Model, params: Params, sw: SpecEEWeights,
                   state: DecodeState,
                   threshold: Optional[float] = None,
                   spec_ids_override: Optional[jnp.ndarray] = None,
                   qw=None, shard=None
                   ) -> Tuple[jnp.ndarray, DecodeState, StepInfo]:
    """Decode one token for every row with speculative early exiting.

    spec_ids_override: (B, k) — oracle speculative set for tests/upper-bound
    benchmarks (bypasses the draft proposal, draft cache still maintained).
    qw: optional quantized-weight bundle (``repro.quant.quantize_params``).
    shard: optional ShardCtx — routes every full-LM-head reduction (the
    draft proposal's top-k, the exit verify, the final emit) through the
    per-shard partial paths (DESIGN.md §9); the predictor-MLP/spec-head
    gates run replicated per shard.
    """
    spec = model.run.specee
    thresh = spec.exit_threshold if threshold is None else threshold
    E = model.num_exit_points
    params, lm_w, predictors = _apply_qw(params, sw, qw)
    pos = state.cache["len"]
    B = state.last_token.shape[0]
    k = spec.num_speculative
    gate_impl, _ = _gate_impls(model)
    sh_kernel = getattr(model.flags, "spec_head_kernel", False)
    pages = state.cache.get("page_table")       # paged KV: table indirection

    # ---- 1. speculate: draft proposes k candidate tokens ----
    emb = model.embed(params, state.last_token[:, None])[:, 0, :]
    h_draft, draft_cache = draft_lib.draft_step(
        model.cfg, sw.draft, emb, state.h_last, state.draft_cache, pos)
    if spec_ids_override is None:
        spec_ids, _ = draft_lib.propose_topk(model, params, h_draft, k,
                                             lm_w=lm_w, shard=shard)
        head_passes = jnp.int32(1)
    else:
        spec_ids, head_passes = spec_ids_override, jnp.int32(0)

    # ---- 2. T2 scheduling: which exit points run a predictor ----
    active = sched_lib.active_mask(state.sched, sw.offline_mask, spec, E)

    # ---- 3. layer loop with early exit ----
    h = emb
    exited = jnp.zeros((B,), bool)
    exit_token = jnp.zeros((B,), jnp.int32)
    exit_pt = jnp.full((B,), E, jnp.int32)
    prev_probs = jnp.full((B, k), 1.0 / k, jnp.float32)
    units_run = jnp.int32(0)
    new_segs = []
    ep_base = 0
    for seg, (unit, reps) in enumerate(model.segments):
        seg_cache = state.cache["segments"][seg]

        def cond(c):
            u = c[0]
            return (u < reps) & ~jnp.all(c[3])

        def body(c):
            (u, h, seg_cache, exited, exit_token, exit_pt, prev_probs, nrun,
             npass) = c
            live = ~exited
            h_new, seg_cache = model.run_unit(params, seg, u, h, seg_cache,
                                              pos, live_mask=live,
                                              pages=pages, shard=shard)
            h = jnp.where(exited[:, None], h, h_new)
            ep = ep_base + u                                   # global exit pt

            act = jnp.take(active, ep, axis=1) & live          # (B,)

            def with_predictor(args):
                h, prev_probs, exited, exit_token, exit_pt = args
                hn = model.final_norm(params, h)
                # single exit-gate entry point: spec-head features +
                # predictor fused ("kernel"/"xla") or the four-op reference
                p_exit, probs, _ = gate_lib.exit_gate(
                    hn, lm_w, spec_ids, prev_probs, predictors, ep,
                    impl=gate_impl, spec_head_kernel=sh_kernel, shard=shard)
                would = act & (p_exit > thresh)

                def verify(args2):
                    exited, exit_token, exit_pt = args2
                    gtok, _ = gate_lib.verify_argmax(hn, lm_w,
                                                     impl=gate_impl,
                                                     shard=shard)
                    confirmed = jnp.any(gtok[:, None] == spec_ids, axis=1)
                    newly = would & confirmed
                    exit_token = jnp.where(newly, gtok, exit_token)
                    exit_pt = jnp.where(newly, ep, exit_pt)
                    return exited | newly, exit_token, exit_pt

                fire = jnp.any(would)
                exited, exit_token, exit_pt = jax.lax.cond(
                    fire, verify, lambda a: a,
                    (exited, exit_token, exit_pt))
                prev_probs = jnp.where(act[:, None], probs, prev_probs)
                return (prev_probs, exited, exit_token, exit_pt,
                        fire.astype(jnp.int32))

            def without_predictor(args):
                h, prev_probs, exited, exit_token, exit_pt = args
                return prev_probs, exited, exit_token, exit_pt, jnp.int32(0)

            prev_probs, exited, exit_token, exit_pt, verified = jax.lax.cond(
                jnp.any(act), with_predictor, without_predictor,
                (h, prev_probs, exited, exit_token, exit_pt))
            return (u + 1, h, seg_cache, exited, exit_token, exit_pt,
                    prev_probs, nrun + 1, npass + verified)

        carry = (jnp.int32(0), h, seg_cache, exited, exit_token, exit_pt,
                 prev_probs, units_run, head_passes)
        u_end, h, seg_cache, exited, exit_token, exit_pt, prev_probs, \
            units_run, head_passes = jax.lax.while_loop(cond, body, carry)

        # ---- 4. KV/state propagation for units the loop never reached ----
        def pcond(c):
            return c[0] < reps

        def pbody(c):
            u, seg_cache = c
            seg_cache = model.propagate_unit(params, seg, u, h, seg_cache,
                                             pos, pages=pages)
            return u + 1, seg_cache

        _, seg_cache = jax.lax.while_loop(pcond, pbody, (u_end, seg_cache))
        new_segs.append(seg_cache)
        ep_base += reps

    # ---- 5. emit: exited rows use the verified token, others the full head
    # (streamed through the verify kernel when fused — one LM-head pass) ----
    final_tok, _ = gate_lib.verify_argmax(model.final_norm(params, h), lm_w,
                                          impl=gate_impl, shard=shard)
    token = jnp.where(exited, exit_token, final_tok)

    # ---- 6. bookkeeping ----
    sched = sched_lib.update(state.sched,
                             jnp.minimum(exit_pt, E - 1))
    new_state = DecodeState(
        cache=dict(state.cache, segments=new_segs, len=pos + 1),
        draft_cache=draft_cache,
        sched=sched,
        last_token=token,
        h_last=h,
        prng=state.prng,
    )
    info = StepInfo(exit_point=exit_pt, exited=exited, units_run=units_run,
                    head_passes=head_passes + 1)        # + the final emit
    return token, new_state, info


# ---------------------------------------------------------------------------
# T3: tree speculative decoding with hyper-token merged early exit
# ---------------------------------------------------------------------------
class TreeStepInfo(NamedTuple):
    accepted_len: jnp.ndarray   # (B,) matched draft tokens (excl. bonus)
    exit_point: jnp.ndarray     # (B,) unit index at exit
    exited: jnp.ndarray         # (B,)
    units_run: jnp.ndarray      # ()
    head_passes: jnp.ndarray    # () — full LM-head passes the step ran


def build_tree(model: Model, params: Params, sw: SpecEEWeights,
               state: DecodeState, tree) -> Tuple[jnp.ndarray, jnp.ndarray, Any]:
    """Draft-expand the static tree. Returns (node_tokens (B, N) int32,
    node_parent_hidden (B, N, D) draft hiddens, new draft cache)."""
    cfg = model.cfg
    B = state.last_token.shape[0]
    pos0 = state.cache["len"]
    b = tree.branch
    # root draft step (writes the trunk cache at pos0)
    emb = model.embed(params, state.last_token[:, None])[:, 0, :]
    h_root, draft_cache = draft_lib.draft_step(
        cfg, sw.draft, emb, state.h_last, state.draft_cache, pos0)

    node_tokens = jnp.zeros((B, tree.num_nodes), jnp.int32)
    node_tokens = node_tokens.at[:, 0].set(state.last_token)
    h_nodes = jnp.zeros((B, tree.num_nodes) + h_root.shape[-1:], h_root.dtype)
    h_nodes = h_nodes.at[:, 0].set(h_root)

    level_off = tree.level_offsets
    for lvl in range(1, tree.depth + 1):
        p_off, p_size = level_off[lvl - 1], tree.level_sizes[lvl - 1]
        off, size = level_off[lvl], tree.level_sizes[lvl]
        # children tokens = top-b of each parent's draft logits
        hp = h_nodes[:, p_off:p_off + p_size].reshape(B * p_size, -1)
        logits = model.logits(params, hp)
        _, topb = jax.lax.top_k(logits, b)
        toks = topb.astype(jnp.int32).reshape(B, p_size * b)
        node_tokens = jax.lax.dynamic_update_slice_in_dim(
            node_tokens, toks, off, axis=1)
        if lvl < tree.depth:  # need hiddens to expand further
            emb_c = model.embed(params, toks.reshape(B * size, 1))[:, 0, :]
            hp_rep = jnp.repeat(hp.reshape(B, p_size, -1), b, axis=1
                                ).reshape(B * size, -1)
            h_c = draft_lib.draft_step_readonly(
                cfg, sw.draft, emb_c, hp_rep, draft_cache, pos0 + lvl,
                pos0 + 1)
            h_nodes = jax.lax.dynamic_update_slice_in_dim(
                h_nodes, h_c.reshape(B, size, -1), off, axis=1)
    return node_tokens, h_nodes, draft_cache


def tree_decode_step(model: Model, params: Params, sw: SpecEEWeights,
                     state: DecodeState, tree,
                     threshold: Optional[float] = None,
                     node_tokens_override: Optional[jnp.ndarray] = None,
                     qw=None, shard=None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, DecodeState,
                                TreeStepInfo]:
    """One tree-speculative step with hyper-token merged early exit.

    Returns (tokens (B, depth+1) emitted left-aligned, num_emitted (B,),
    new state, info). Cache must have ``tree.num_nodes`` scratch slots beyond
    ``max_seq`` (see ``init_tree_decode_state``).
    """
    assert model.supports_tree(), \
        "T3 tree mode requires a pure-attention stack (DESIGN.md §4)"
    spec = model.run.specee
    thresh = spec.exit_threshold if threshold is None else threshold
    E = model.num_exit_points
    params, lm_w, predictors = _apply_qw(params, sw, qw)
    B = state.last_token.shape[0]
    N = tree.num_nodes
    k = spec.num_speculative
    pos0 = state.cache["len"]
    gate_impl, fused = _gate_impls(model)
    sh_kernel = getattr(model.flags, "spec_head_kernel", False)
    # the tree gate's predictor stage goes through the Pallas wrapper only
    # when the fused backend actually resolves to the kernel path
    pred_kernel = fused and gate_lib.resolve_impl(gate_impl) == "kernel"
    # static scratch offset = logical capacity minus N; with a paged cache
    # the capacity is pages_per_row * page_size (table width × pool page dim)
    pages = state.cache.get("page_table")
    any_k = jax.tree_util.tree_leaves(state.cache["segments"][0])[0]
    if pages is None:
        capacity = any_k.shape[2]
    else:
        capacity = pages.shape[1] * any_k.shape[2]
    scratch_off = capacity - N

    node_tokens, h_nodes_draft, draft_cache = build_tree(
        model, params, sw, state, tree)
    if node_tokens_override is not None:  # oracle mode for tests/benchmarks
        node_tokens = node_tokens_override.at[:, 0].set(state.last_token)

    # children token matrix per node, padded to k for the predictor features
    children = jnp.asarray(tree.children)                   # (N, b)
    safe_children = jnp.maximum(children, 0)
    child_toks = node_tokens[:, safe_children]              # (B, N, b)
    if tree.branch < k:
        pad = jnp.repeat(child_toks[:, :, :1], k - tree.branch, axis=2)
        child_toks = jnp.concatenate([child_toks, pad], axis=2)
    else:
        child_toks = child_toks[:, :, :k]

    # ---- layer loop with hyper-token early exit ----
    mask = tree.attention_mask(pos0, scratch_off)           # (B|1,1,N,S+N)
    positions = jnp.broadcast_to(tree.positions(pos0), (B, N))
    h = model.embed(params, node_tokens)                    # (B, N, D)
    exited = jnp.zeros((B,), bool)
    exit_pt = jnp.full((B,), E, jnp.int32)
    prev_probs = jnp.full((B, N, k), 1.0 / k, jnp.float32)
    units_run = jnp.int32(0)
    active = sched_lib.active_mask(state.sched, sw.offline_mask, spec, E)
    path_nodes = jnp.asarray(tree.path_nodes)               # (P, depth+1)
    new_segs = []
    ep_base = 0
    for seg, (unit, reps) in enumerate(model.segments):
        seg_cache = state.cache["segments"][seg]

        def cond(c):
            return (c[0] < reps) & ~jnp.all(c[3])

        def body(c):
            u, h, seg_cache, exited, exit_pt, prev_probs, nrun = c
            live = ~exited
            h_new, seg_cache = model.run_unit_tree(
                params, seg, u, h, seg_cache, mask, positions, scratch_off,
                pages=pages)
            h = jnp.where(exited[:, None, None], h, h_new)
            ep = ep_base + u
            act = jnp.take(active, ep, axis=1) & live

            def with_predictor(args):
                h, prev_probs, exited, exit_pt = args
                hn = model.final_norm(params, h).reshape(B * N, -1)
                feats, probs = feat_lib.extract_features(
                    hn, lm_w, child_toks.reshape(B * N, k),
                    prev_probs.reshape(B * N, k), use_kernel=sh_kernel,
                    shard=shard)
                feats = feats.reshape(B, N, -1)
                probs = probs.reshape(B, N, k)
                # hyper-token merge: one predictor eval per root→leaf path
                pf, _ = feat_lib.merge_path_features(
                    feats, probs, path_nodes,
                    jnp.full((path_nodes.shape[0],), path_nodes.shape[1]))
                p_exit = pred_lib.apply_predictor_banked(
                    predictors, ep, pf,
                    use_kernel=pred_kernel, shard=shard)       # (B, P)
                fire = jnp.max(p_exit, axis=1) > thresh     # best path fires
                newly = act & fire
                exit_pt = jnp.where(newly, ep, exit_pt)
                prev_probs = jnp.where(act[:, None, None], probs, prev_probs)
                return prev_probs, exited | newly, exit_pt

            prev_probs, exited, exit_pt = jax.lax.cond(
                jnp.any(act), with_predictor,
                lambda a: (a[1], a[2], a[3]),
                (h, prev_probs, exited, exit_pt))
            return u + 1, h, seg_cache, exited, exit_pt, prev_probs, nrun + 1

        carry = (jnp.int32(0), h, seg_cache, exited, exit_pt, prev_probs,
                 units_run)
        u_end, h, seg_cache, exited, exit_pt, prev_probs, units_run = \
            jax.lax.while_loop(cond, body, carry)

        def pcond(c):
            return c[0] < reps

        def pbody(c):
            u, sc = c
            sc = model.propagate_unit_tree(params, seg, u, h, sc, positions,
                                           scratch_off, pages=pages)
            return u + 1, sc

        _, seg_cache = jax.lax.while_loop(pcond, pbody, (u_end, seg_cache))
        new_segs.append(seg_cache)
        ep_base += reps

    # ---- acceptance walk on global logits at the (per-row) exit layer ----
    # B·N node rows stream through the verify kernel when fused: one LM-head
    # pass, never a (B, N, V) logits tensor
    hn_nodes = model.final_norm(params, h).reshape(B * N, -1)
    gtok = gate_lib.verify_argmax(hn_nodes, lm_w, impl=gate_impl,
                                  shard=shard)[0].reshape(B, N)

    rows = jnp.arange(B)
    cur = jnp.zeros((B,), jnp.int32)                        # root
    acc_nodes = jnp.full((B, tree.depth + 1), -1, jnp.int32)
    acc_nodes = acc_nodes.at[:, 0].set(0)
    acc_len = jnp.ones((B,), jnp.int32)                     # root always in
    out_tokens = jnp.zeros((B, tree.depth + 1), jnp.int32)
    n_emit = jnp.zeros((B,), jnp.int32)
    alive = jnp.ones((B,), bool)
    for d in range(1, tree.depth + 1):
        target = gtok[rows, cur]                            # (B,)
        ch = safe_children[cur]                             # (B, b)
        ch_tok = node_tokens[rows[:, None], ch]             # (B, b)
        match = (ch_tok == target[:, None]) & (children[cur] >= 0)
        hit = jnp.any(match, axis=1) & alive
        nxt = ch[rows, jnp.argmax(match, axis=1)]
        out_tokens = out_tokens.at[:, d - 1].set(
            jnp.where(hit, target, out_tokens[:, d - 1]))
        n_emit = n_emit + hit.astype(jnp.int32)
        acc_nodes = acc_nodes.at[:, d].set(jnp.where(hit, nxt, -1))
        acc_len = acc_len + hit.astype(jnp.int32)
        cur = jnp.where(hit, nxt, cur)
        alive = hit
    # bonus token: TLM greedy at the last accepted node
    bonus = gtok[rows, cur]
    out_tokens = out_tokens.at[rows, n_emit].set(bonus)
    n_emit = n_emit + 1

    # ---- commit: copy accepted K/V into real cache positions ----
    cache = dict(state.cache, segments=new_segs, len=pos0)
    cache = model.accept_tree_kv(cache, acc_nodes, acc_len, pos0, scratch_off)
    cache["len"] = pos0 + acc_len                           # root + matched

    # ---- draft cache catch-up for accepted tokens beyond the root ----
    h_last = h[rows, cur]                                   # (B, D) exit hidden
    for d in range(1, tree.depth + 1):
        valid = d < acc_len
        tok_d = out_tokens[:, d - 1]                        # accepted token d
        emb_d = model.embed(params, tok_d[:, None])[:, 0, :]
        parent_h = h[rows, jnp.maximum(acc_nodes[:, d - 1], 0)]
        h_d, dc_new = draft_lib.draft_step(
            model.cfg, sw.draft, emb_d, parent_h, draft_cache, pos0 + d)
        draft_cache = jax.tree_util.tree_map(
            lambda new, old: jnp.where(
                valid[:, None, None, None], new, old), dc_new, draft_cache)

    sched = sched_lib.update(state.sched, jnp.minimum(exit_pt, E - 1))
    new_state = DecodeState(cache=cache, draft_cache=draft_cache, sched=sched,
                            last_token=bonus, h_last=h_last, prng=state.prng)
    # one draft-logits pass per tree level, one verify over the nodes
    info = TreeStepInfo(accepted_len=acc_len - 1, exit_point=exit_pt,
                        exited=exited, units_run=units_run,
                        head_passes=jnp.int32(tree.depth + 1))
    return out_tokens, n_emit, new_state, info


# ---------------------------------------------------------------------------
# device-resident multi-tick decode ("megatick")
# ---------------------------------------------------------------------------
class TickEmit(NamedTuple):
    """Raw per-tick emit of one strategy step, as the megatick loop sees it."""
    tokens: jnp.ndarray         # (B, W) int32 — left-aligned emitted tokens
    counts: jnp.ndarray         # (B,) int32 — valid tokens this tick
    exit_layer: jnp.ndarray     # (B,) int32
    accept_len: jnp.ndarray     # (B,) int32
    exited: jnp.ndarray         # (B,) bool
    units_run: jnp.ndarray      # () int32
    head_passes: jnp.ndarray    # () int32


def megatick_decode(tick_fn, state: DecodeState, limits: Dict[str, jnp.ndarray],
                    num_ticks: int, emit_width: int, num_exit_points: int
                    ) -> Tuple[Dict[str, jnp.ndarray], DecodeState,
                               Dict[str, jnp.ndarray]]:
    """Fuse ``num_ticks`` strategy steps into one ``lax.while_loop``.

    ``tick_fn(state) -> (TickEmit, new_state)`` is one batched strategy step
    (any decode mode). The per-row token budgets, EOS cut-off, and done mask —
    historically host-side Python in ``DecodeSession`` — live in the jitted
    carry, so the whole megatick runs device-resident: emits accumulate into a
    ``(B, K*W)`` buffer at per-row offsets, per-tick exit-layer/accept-length
    stats land in ``(B, K)`` columns, and the loop exits early once every row
    is done. Rows retired mid-flight (``limits["retired"]``) have their
    logical cache length re-pinned to zero after every tick, preserving the
    session's sticky-compaction invariant without a host sync.

    Accounting is tick-for-tick identical to ``DecodeSession._account_row``:
    budget clip first, EOS scan within the clipped window, ``done`` on EOS hit
    or budget exhaustion; rows already done keep stepping (their emits are
    dropped — exactly what K single host-accounted steps do) so device state
    stays bit-identical to the unfused loop.

    Returns ``(out, final_state, new_limits)`` where ``out`` holds tokens
    (B, K·W), counts (B,), per-tick stat planes (B, K), ``ticks`` actually
    run, ``units_run`` and ``head_passes`` summed over them, and the final
    done mask; ``new_limits`` is the advanced carry for
    the next megatick (device-resident across calls — no host round-trip).
    """
    K, W = int(num_ticks), int(emit_width)
    B = state.last_token.shape[0]
    buf_len = K * W
    budget = limits["budget"]
    eos = limits["eos"]
    retired = limits["retired"]
    lanes = jnp.arange(W)

    def write_rows(buf, off, toks, kept):
        # per-row scatter at the row's running offset; lanes >= kept map out
        # of range and drop (the buffer is exactly K*W: a row that keeps j
        # tokens per tick never writes past its own accumulated count)
        idx = jnp.where(lanes[None, :] < kept[:, None],
                        off[:, None] + lanes[None, :], buf_len)
        return jax.vmap(lambda b, i, t: b.at[i].set(t, mode="drop"))(
            buf, idx, toks)

    def cond(c):
        return (c["t"] < K) & ~jnp.all(c["done"])

    def body(c):
        t, done, emitted = c["t"], c["done"], c["emitted"]
        em, st = tick_fn(c["state"])
        live = ~done
        # budget clip, then EOS scan within the clipped window (the exact
        # order of the host-side _account_row)
        kept = jnp.maximum(jnp.minimum(em.counts, budget - emitted), 0)
        is_eos = ((em.tokens == eos[:, None]) & (eos >= 0)[:, None]
                  & (lanes[None, :] < kept[:, None]))
        has_eos = jnp.any(is_eos, axis=1)
        kept = jnp.where(has_eos,
                         jnp.argmax(is_eos, axis=1).astype(jnp.int32) + 1,
                         kept)
        kept = jnp.where(live, kept, 0)
        emitted = emitted + kept
        done = done | (live & (has_eos | (emitted >= budget)))
        buf = write_rows(c["buf"], c["counts"], em.tokens, kept)
        # sticky compaction: the batched tick advances len uniformly; a
        # retired row's span must stay pinned to zero
        cache = st.cache
        st = st._replace(cache=dict(cache,
                                    len=jnp.where(retired, 0, cache["len"])))
        return dict(
            state=st, t=t + 1, done=done, emitted=emitted, buf=buf,
            counts=c["counts"] + kept,
            exit_layer=c["exit_layer"].at[:, t].set(em.exit_layer),
            accept_len=c["accept_len"].at[:, t].set(em.accept_len),
            exited=c["exited"].at[:, t].set(em.exited),
            tick_counts=c["tick_counts"].at[:, t].set(kept),
            tick_live=c["tick_live"].at[:, t].set(live),
            units=c["units"] + em.units_run,
            passes=c["passes"] + em.head_passes,
        )

    init = dict(
        state=state, t=jnp.int32(0), done=limits["done"],
        emitted=limits["emitted"],
        buf=jnp.zeros((B, buf_len), jnp.int32),
        counts=jnp.zeros((B,), jnp.int32),
        exit_layer=jnp.full((B, K), num_exit_points, jnp.int32),
        accept_len=jnp.zeros((B, K), jnp.int32),
        exited=jnp.zeros((B, K), bool),
        tick_counts=jnp.zeros((B, K), jnp.int32),
        tick_live=jnp.zeros((B, K), bool),
        units=jnp.int32(0),
        passes=jnp.int32(0),
    )
    fin = jax.lax.while_loop(cond, body, init)
    out = {"tokens": fin["buf"], "counts": fin["counts"],
           "exit_layer": fin["exit_layer"], "accept_len": fin["accept_len"],
           "exited": fin["exited"], "tick_counts": fin["tick_counts"],
           "tick_live": fin["tick_live"], "ticks": fin["t"],
           "units_run": fin["units"], "head_passes": fin["passes"],
           "done": fin["done"]}
    new_limits = {"budget": budget, "emitted": fin["emitted"], "eos": eos,
                  "done": fin["done"], "retired": retired}
    return out, fin["state"], new_limits


def init_tree_decode_state(model: Model, params: Params, sw: SpecEEWeights,
                           batch: Dict[str, jnp.ndarray], max_seq: int,
                           tree) -> Tuple[jnp.ndarray, DecodeState]:
    """Like ``init_decode_state`` but reserves N scratch slots in the cache
    (cache lengths are per-row throughout — rows accept ragged counts)."""
    return init_decode_state(model, params, sw, batch,
                             max_seq + tree.num_nodes)


# ---------------------------------------------------------------------------
# dense baseline step sharing the same state plumbing (for A/B benchmarks
# and the serving engine's non-SpecEE mode)
# ---------------------------------------------------------------------------
def dense_decode_step(model: Model, params: Params,
                      sw: Optional[SpecEEWeights], state: DecodeState,
                      temperature: float = 0.0, top_k: Optional[int] = None,
                      qw=None, shard=None
                      ) -> Tuple[jnp.ndarray, DecodeState, StepInfo]:
    """One dense (full-depth) decode step.

    Greedy (``temperature<=0``) emits through ``gate_lib.verify_argmax`` —
    the LM head streams with the exit-gate impl the model's flags select, so
    the fused path stops materializing (B, V) logits here too ("ref" keeps
    the historical materialized argmax bit-for-bit). ``temperature>0``
    samples from the full logits (sampling needs the distribution) with a
    per-row key derived from (session key, row position, previous token) —
    ``sampler.row_keys`` — so a row's samples are a pure function of its own
    decode history: batch- and slot-independent, megatick-invariant, and
    exactly reproducible when an evicted row replays its prefix through the
    fault-recovery path (DESIGN.md §7). ``state.prng`` stays constant.

    With a quantized bundle (``qw``) the greedy path verifies against the
    quantized head; the sampling path keeps the fp LM head (the distribution
    is the product, not just its argmax) while still using dequantized
    projections.
    """
    params, lm_w, _ = _apply_qw(params, sw, qw)
    pos_before = state.cache["len"]
    h, cache = model.decode_step_hidden(params, state.last_token, state.cache,
                                        shard=shard)
    if temperature > 0.0:
        from repro.serving.sampler import row_keys, sample_rows
        keys = row_keys(state.prng, pos_before, state.last_token)
        logits = model.logits(params, h)
        token = sample_rows(logits, keys, temperature=temperature,
                            top_k=top_k)
        prng = state.prng
    else:
        prng = state.prng
        gate_impl, _ = _gate_impls(model)
        token, _ = gate_lib.verify_argmax(model.final_norm(params, h),
                                          lm_w, impl=gate_impl, shard=shard)
    B = token.shape[0]
    E = model.num_exit_points
    new_state = DecodeState(cache=cache, draft_cache=state.draft_cache,
                            sched=state.sched, last_token=token,
                            h_last=h, prng=prng)
    info = StepInfo(exit_point=jnp.full((B,), E, jnp.int32),
                    exited=jnp.zeros((B,), bool),
                    units_run=jnp.int32(E),
                    head_passes=jnp.int32(1))
    return token, new_state, info
