"""Public jit'd wrappers for the fused exit gate.

``exit_gate()`` / ``verify_argmax()`` are the decode engine's SINGLE entry
points for the per-exit-point decision; an ``impl`` switch selects the
backend:

  "kernel" — the Pallas chain (interpret mode off-TPU): gate = one fused
             kernel, verify = the streaming argmax kernel.
  "xla"    — the same fused dataflow as one XLA computation: gate is the
             jnp chain under a single jit; verify streams vocab tiles with a
             ``lax.scan`` running (max, argmax) — still never materializes
             the (B, V) logits.
  "ref"    — the engine's historical unfused op sequence, bit-for-bit
             (verification matmuls in ``hn.dtype``). The numerics reference.
  None / "auto" — "kernel" on TPU; off-TPU the gate takes "xla" and the
             verify takes "ref" (on CPU one BLAS GEMM beats any streaming
             formulation — the logits-round-trip saving is an HBM property).

The stacked predictor bank is routed THROUGH the wrapper: ``exit_gate``
takes the full ``(E, ...)`` bank plus the exit-point index and performs the
``dynamic_index_in_dim`` inside the same jit as the kernel launch, so the
per-step weight slice fuses with the gate instead of bouncing through HBM.
Predictor banks that are not 2-layer (DSE sweeps) fall back from "kernel"
to the jnp chain automatically.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.exit_gate import ref as gate_ref
from repro.kernels.exit_gate import tuning
from repro.kernels.exit_gate.exit_gate import (_pick_vocab_block,
                                               argmax_verify_fused,
                                               exit_gate_fused,
                                               topk_verify_fused)
from repro.quant import QTensor, unpack_int4

IMPLS = (None, "auto", "kernel", "xla", "ref")

_I32_MAX = 2**31 - 1


def resolve_impl(impl: Optional[str], cpu_default: str = "xla") -> str:
    """Backend an ``impl`` request resolves to on the current platform."""
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    if impl in (None, "auto"):
        return "kernel" if on_tpu() else cpu_default
    return impl


_resolve = resolve_impl


def impl_for_flags(flags) -> str:
    """Exit-gate backend a ``ModelFlags`` bundle selects.

    This is THE single resolution point for ``ModelFlags.exit_gate_kernel``:
    the decode strategies (repro.api), the engine step functions, and the
    draft proposal all call it instead of re-reading the flags at every call
    site. With the flag off every entry point pins the historical "ref"
    numerics bit-for-bit.
    """
    fused = getattr(flags, "exit_gate_kernel", False)
    return (getattr(flags, "exit_gate_impl", "auto") or "auto") if fused \
        else "ref"


def _index_bank(predictors, ep):
    """Slice one predictor out of the stacked (E, ...) bank."""
    from repro.core.predictor import predictor_at
    return predictor_at(predictors, ep)


@partial(jax.jit,
         static_argnames=("impl", "spec_head_kernel", "block_d", "shard"))
def exit_gate(hn: jnp.ndarray, lm_head, spec_ids: jnp.ndarray,
              prev_probs: jnp.ndarray, predictors, ep: jnp.ndarray,
              impl: Optional[str] = None, spec_head_kernel: bool = False,
              block_d: int = 512, shard=None
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused exit decision for one exit point.

    hn: (B, D) final-normed hidden; lm_head: (D, V); spec_ids: (B, k) int32;
    prev_probs: (B, k); predictors: stacked bank (every leaf (E, ...));
    ep: scalar int32 exit-point index. ``shard``: optional ShardCtx — the
    gate's kernels then run whole on every device (the gate reads k columns
    per row; it is replicated work under tensor parallelism).

    Returns (p_exit (B,), local_probs (B, k), logits (B, k)), all fp32.
    """
    impl = _resolve(impl)
    pp = _index_bank(predictors, ep)
    layers = pp["layers"]
    quantized = (isinstance(lm_head, QTensor)
                 or any(isinstance(l.get("w"), QTensor) for l in layers))
    if impl == "kernel" and len(layers) == 2 and quantized:
        # piecewise fusion for quantized weights (mirrors the tree gate):
        # the quantized spec-head gather kernel + the quantized fused MLP —
        # features still make exactly one VMEM round-trip each
        from repro.kernels.predictor_mlp import ops as pm_ops
        from repro.kernels.spec_head import ops as sh_ops
        logits, probs = sh_ops.spec_head(hn, lm_head, spec_ids,
                                         block_d=block_d, shard=shard)
        feats = jnp.concatenate(
            [logits, probs, probs - prev_probs.astype(jnp.float32)], axis=-1)
        return pm_ops.predictor_mlp(feats, pp, shard=shard), probs, logits
    if impl == "kernel" and len(layers) == 2:
        return exit_gate_fused(hn, lm_head, spec_ids, prev_probs,
                               layers[0]["w"], layers[0]["b"],
                               layers[1]["w"], layers[1]["b"],
                               block_d=block_d, shard=shard)
    if impl == "ref" and spec_head_kernel:
        # historical path with the spec_head Pallas kernel selected
        from repro.kernels.spec_head import ops as sh_ops
        logits, probs = sh_ops.spec_head(hn, lm_head, spec_ids, shard=shard)
        feats = jnp.concatenate(
            [logits, probs, probs - prev_probs.astype(jnp.float32)], axis=-1)
        return gate_ref.mlp_ref(feats, pp), probs, logits
    # "xla" and "ref" share the jnp dataflow — under this jit XLA fuses it
    # into one computation either way; "ref" exists so callers can pin the
    # historical numerics explicitly.
    return gate_ref.exit_gate_ref(hn, lm_head, spec_ids, prev_probs, pp)


def _verify_streaming_xla(hn: jnp.ndarray, lm_head: jnp.ndarray,
                          block_v: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """lax.scan over vocab tiles with a running (max, argmax) carry."""
    B, D = hn.shape
    V = lm_head.shape[1]
    # same no-copy preference as the kernel: only pad for vocabs where no
    # reasonable block divides V
    block_v, pad_v = _pick_vocab_block(V, block_v)
    wp = jnp.pad(lm_head, ((0, 0), (0, pad_v))) if pad_v else lm_head
    nv = (V + pad_v) // block_v
    hf = hn.astype(jnp.float32)
    lanes = jnp.arange(block_v)

    def body(carry, v):
        best, barg = carry
        w = jax.lax.dynamic_slice_in_dim(wp, v * block_v, block_v, axis=1)
        tile = hf @ w.astype(jnp.float32)                      # (B, Vt)
        col = v * block_v + lanes
        tile = jnp.where(col[None, :] < V, tile, -jnp.inf)
        tmax = jnp.max(tile, axis=-1)
        targ = (v * block_v + jnp.argmax(tile, axis=-1)).astype(jnp.int32)
        better = tmax > best
        return (jnp.where(better, tmax, best),
                jnp.where(better, targ, barg)), None

    init = (jnp.full((B,), -jnp.inf, jnp.float32),
            jnp.zeros((B,), jnp.int32))
    (best, barg), _ = jax.lax.scan(body, init, jnp.arange(nv))
    return barg, best


def _q_stream_plan(hn: jnp.ndarray, qt: QTensor, block_v: int):
    """Shared tile geometry + per-tile dequantized-logits fn for the
    quantized streaming-XLA paths. Mirrors the quantized kernels: integer
    codes + per-column scales stream per tile; the scale folds in after
    the dot (exact — scales are column-constant)."""
    V = qt.q.shape[-1]
    block_v, pad_v = _pick_vocab_block(V, block_v)
    q = qt.q
    scale = qt.scale
    if pad_v:
        q = jnp.pad(q, ((0, 0), (0, pad_v)))
        scale = jnp.pad(scale, (0, pad_v))
    nv = (V + pad_v) // block_v
    hf = hn.astype(jnp.float32)
    half = q.shape[0]        # = D/2 for the packed int4 plane layout
    bits = qt.bits

    def tile_logits(v):
        qt_tile = jax.lax.dynamic_slice_in_dim(q, v * block_v, block_v,
                                               axis=1)
        s_tile = jax.lax.dynamic_slice_in_dim(scale, v * block_v, block_v,
                                              axis=0)
        if bits == 4:
            lo, hi = unpack_int4(qt_tile)
            part = (hf[:, :half] @ lo.astype(jnp.float32)
                    + hf[:, half:] @ hi.astype(jnp.float32))
        else:
            part = hf @ qt_tile.astype(jnp.float32)
        return part * s_tile[None, :]                   # (B, Vt)

    return tile_logits, block_v, nv, V


def _verify_streaming_xla_q(hn: jnp.ndarray, qt: QTensor,
                            block_v: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantized sibling of ``_verify_streaming_xla``."""
    B = hn.shape[0]
    tile_logits, block_v, nv, V = _q_stream_plan(hn, qt, block_v)
    lanes = jnp.arange(block_v)

    def body(carry, v):
        best, barg = carry
        col = v * block_v + lanes
        tile = jnp.where(col[None, :] < V, tile_logits(v), -jnp.inf)
        tmax = jnp.max(tile, axis=-1)
        targ = (v * block_v + jnp.argmax(tile, axis=-1)).astype(jnp.int32)
        better = tmax > best
        return (jnp.where(better, tmax, best),
                jnp.where(better, targ, barg)), None

    init = (jnp.full((B,), -jnp.inf, jnp.float32),
            jnp.zeros((B,), jnp.int32))
    (best, barg), _ = jax.lax.scan(body, init, jnp.arange(nv))
    return barg, best


def _topk_streaming_xla_q(hn: jnp.ndarray, qt: QTensor, k: int,
                          block_v: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantized sibling of ``_topk_streaming_xla`` (same tie contract)."""
    B = hn.shape[0]
    tile_logits, block_v, nv, V = _q_stream_plan(hn, qt, block_v)
    lanes = jnp.arange(block_v)

    def body(carry, v):
        cvals, cids = carry
        col = v * block_v + lanes
        tile = jnp.where(col[None, :] < V, tile_logits(v), -jnp.inf)
        pool_v = jnp.concatenate([cvals, tile], axis=1)
        pool_i = jnp.concatenate(
            [cids, jnp.broadcast_to(col[None, :], tile.shape)], axis=1)
        nvals, sel = jax.lax.top_k(pool_v, k)
        nids = jnp.take_along_axis(pool_i, sel, axis=1)
        return (nvals, nids.astype(jnp.int32)), None

    init = (jnp.full((B, k), -jnp.inf, jnp.float32),
            jnp.zeros((B, k), jnp.int32))
    (vals, ids), _ = jax.lax.scan(body, init, jnp.arange(nv))
    return ids, vals


# ---------------------------------------------------------------------------
# sharded verify (tensor-parallel LM head, DESIGN.md §9)
#
# The vocab dimension shards over ``shard.axis``; the D contraction never
# splits, so every per-column logit a shard computes is bit-identical to the
# single-device value. Each shard reduces its local slice to a tiny partial
# — (max, argmax) or top-k — inside a purely-local ``shard_map`` body (no
# collectives; partials concatenate along a leading axis via out_specs), and
# one (P, B)-sized merge outside reproduces the global tie-break contract:
# lowest global id among equal maxima (= ``jnp.argmax`` first-occurrence),
# and ``lax.top_k``'s lower-index-first ordering for top-k.
# ---------------------------------------------------------------------------
def _shard_pad(lm_head: jnp.ndarray, degree: int):
    """Pad the (D, V) head so the vocab splits evenly: -> (padded head,
    per-shard width, pad columns added). Padded columns are zeros and MUST be
    masked before any reduction — a zero logit can beat real negatives."""
    V = lm_head.shape[1]
    width = -(-V // degree)
    pad = width * degree - V
    if pad:
        lm_head = jnp.pad(lm_head, ((0, 0), (0, pad)))
    return lm_head, width, pad


def _masked_slice_logits(hn, w_local, col0, v_total, dt):
    """Materialized logits for one vocab slice with padding masked to -inf.
    ``col0`` is the slice's first GLOBAL column (traced: axis_index * width);
    ``v_total`` the unpadded vocab size. Matmul in ``dt`` then fp32, the
    exact compute path of ``verify_*_ref`` (dt=hn.dtype) and of the fp32
    streaming impls (dt=float32)."""
    logits = (hn.astype(dt) @ w_local.astype(dt)).astype(jnp.float32)
    col = col0 + jnp.arange(w_local.shape[1], dtype=jnp.int32)
    return jnp.where(col[None, :] < v_total, logits, -jnp.inf)


def _local_dtype(impl, hn):
    # "ref" verifies in hn.dtype (the historical materialized matmul);
    # "xla"/"kernel" accumulate in fp32 (the streaming contract)
    return hn.dtype if impl == "ref" else jnp.float32


def _verify_argmax_sharded(hn, lm_head, shard, impl, block_v, block_d):
    P = jax.sharding.PartitionSpec
    degree = shard.degree
    wp, width, pad = _shard_pad(lm_head, degree)
    V = lm_head.shape[1]
    if block_v is None:
        block_v = tuning.best_block_v(hn.shape[1], width)

    def local(hn, w_local):
        # per-shard partial (argmax, max) over the local vocab slice; token
        # ids are GLOBAL. With padding the masked materialized form is used
        # for every impl (the pad mask must see global column ids).
        col0 = jax.lax.axis_index(shard.axis).astype(jnp.int32) * width
        if pad:
            logits = _masked_slice_logits(hn, w_local, col0, V,
                                          _local_dtype(impl, hn))
            tok = col0 + jnp.argmax(logits, axis=-1).astype(jnp.int32)
            val = jnp.max(logits, axis=-1)
        elif impl == "kernel":
            tok, val = argmax_verify_fused(hn, w_local, block_v=block_v,
                                           block_d=block_d)
            tok = tok + col0
        elif impl == "xla":
            tok, val = _verify_streaming_xla(hn, w_local, block_v)
            tok = tok + col0
        else:
            tok, val = gate_ref.verify_argmax_ref(hn, w_local,
                                                  compute_dtype=hn.dtype)
            tok = tok + col0
        return tok[None], val[None]                        # (1, B) partials

    toks, vals = jax.shard_map(
        local, mesh=shard.mesh, check_vma=False,
        in_specs=(P(), P(None, shard.axis)),
        out_specs=(P(shard.axis), P(shard.axis)))(hn, wp)
    # merge (P, B) partials: max value wins; equal maxima take the lowest
    # global token id — jnp.argmax's first-occurrence contract on the full
    # logits (a fully-padded shard reports -inf and never wins)
    best = jnp.max(vals, axis=0)
    cand = jnp.where(vals == best[None, :], toks, _I32_MAX)
    return jnp.min(cand, axis=0).astype(jnp.int32), best


def _verify_topk_sharded(hn, lm_head, k, shard, impl, block_v, block_d):
    P = jax.sharding.PartitionSpec
    degree = shard.degree
    wp, width, pad = _shard_pad(lm_head, degree)
    V = lm_head.shape[1]
    if k > width:
        raise ValueError(
            f"verify_topk: k={k} exceeds the per-shard vocab slice "
            f"({V} cols / {degree} shards = {width}); every global top-k "
            "entry must be inside its shard's local top-k")
    if block_v is None:
        block_v = tuning.best_block_v(hn.shape[1], width)

    def local(hn, w_local):
        col0 = jax.lax.axis_index(shard.axis).astype(jnp.int32) * width
        if pad:
            logits = _masked_slice_logits(hn, w_local, col0, V,
                                          _local_dtype(impl, hn))
            vals, sel = jax.lax.top_k(logits, k)
            ids = col0 + sel.astype(jnp.int32)
        elif impl == "kernel":
            ids, vals = topk_verify_fused(hn, w_local, k, block_v=block_v,
                                          block_d=block_d)
            ids = ids + col0
        elif impl == "xla":
            ids, vals = _topk_streaming_xla(hn, w_local, k, block_v)
            ids = ids + col0
        else:
            ids, vals = gate_ref.verify_topk_ref(hn, w_local, k,
                                                 compute_dtype=hn.dtype)
            ids = ids + col0
        return ids[None], vals[None]                      # (1, B, k)

    ids, vals = jax.shard_map(
        local, mesh=shard.mesh, check_vma=False,
        in_specs=(P(), P(None, shard.axis)),
        out_specs=(P(shard.axis), P(shard.axis)))(hn, wp)
    # (P, B, k) -> shard-major (B, P·k) pool: within a shard local top-k is
    # id-ascending among equal values and shards are id-ascending, so
    # lax.top_k's lower-index-first tie-break reproduces the global contract
    B = hn.shape[0]
    pool_v = jnp.transpose(vals, (1, 0, 2)).reshape(B, degree * k)
    pool_i = jnp.transpose(ids, (1, 0, 2)).reshape(B, degree * k)
    nvals, sel = jax.lax.top_k(pool_v, k)
    nids = jnp.take_along_axis(pool_i, sel, axis=1)
    return nids.astype(jnp.int32), nvals


@partial(jax.jit, static_argnames=("impl", "block_v", "block_d", "shard"))
def verify_argmax(hn: jnp.ndarray, lm_head,
                  impl: Optional[str] = None, block_v: Optional[int] = None,
                  block_d: int = 512, shard=None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-LM-head argmax for verification. hn: (B, D); lm_head: (D, V).

    "kernel"/"xla" stream the vocab dimension with fp32 accumulation and
    never materialize (B, V); "ref" is the engine's historical materialized
    matmul in ``hn.dtype``. Auto resolves to "kernel" on TPU (where the
    saved logits round-trips are HBM traffic) and to "ref" on CPU, where
    one BLAS GEMM beats any streaming formulation and the memory win is
    moot. ``block_v=None`` takes the autotuned vocab-strip width for this
    (D, V) from ``tuning.best_block_v`` (swept by ``hillclimb.py
    --gate-blocks``, cached in repro/configs/gate_blocks.json).
    ``shard``: optional ``repro.sharding.ctx.ShardCtx`` — verify as a
    per-shard partial reduction over the local vocab slice + one tiny merge
    (bit-identical to single-device under any vocab split; see DESIGN.md
    §9). Quantized heads stay on the unsharded path (QTensor tiles ride
    replicated under a mesh; the kernel then runs whole on every device).
    Returns (token (B,) int32, max logit (B,) fp32).
    """
    impl = _resolve(impl, cpu_default="ref")
    if shard is not None and not isinstance(lm_head, QTensor):
        return _verify_argmax_sharded(hn, lm_head, shard, impl, block_v,
                                      block_d)
    if isinstance(lm_head, QTensor):
        if block_v is None:
            block_v = tuning.best_block_v(hn.shape[1], lm_head.shape[-1],
                                          wbits=lm_head.bits)
        if impl == "kernel":
            call = partial(argmax_verify_fused, block_v=block_v,
                           block_d=block_d)
            if shard is not None:
                call = shard.replicated(call)
            return call(hn, lm_head)
        if impl == "xla":
            return _verify_streaming_xla_q(hn, lm_head, block_v)
        return gate_ref.verify_argmax_ref(hn, lm_head,
                                          compute_dtype=hn.dtype)
    if block_v is None:
        block_v = tuning.best_block_v(hn.shape[1], lm_head.shape[1])
    if impl == "kernel":
        return argmax_verify_fused(hn, lm_head, block_v=block_v,
                                   block_d=block_d)
    if impl == "xla":
        return _verify_streaming_xla(hn, lm_head, block_v)
    return gate_ref.verify_argmax_ref(hn, lm_head, compute_dtype=hn.dtype)


def _topk_streaming_xla(hn: jnp.ndarray, lm_head: jnp.ndarray, k: int,
                        block_v: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """lax.scan over vocab tiles with a running (vals, ids) top-k carry.

    The carry is prepended to each tile before ``top_k`` so ties resolve to
    the earlier (lower-id) entry — bit-matching ``jax.lax.top_k`` on the
    materialized logits.
    """
    B, D = hn.shape
    V = lm_head.shape[1]
    block_v, pad_v = _pick_vocab_block(V, block_v)
    wp = jnp.pad(lm_head, ((0, 0), (0, pad_v))) if pad_v else lm_head
    nv = (V + pad_v) // block_v
    hf = hn.astype(jnp.float32)
    lanes = jnp.arange(block_v)

    def body(carry, v):
        cvals, cids = carry                                    # (B, k) each
        w = jax.lax.dynamic_slice_in_dim(wp, v * block_v, block_v, axis=1)
        tile = hf @ w.astype(jnp.float32)                      # (B, Vt)
        col = v * block_v + lanes
        tile = jnp.where(col[None, :] < V, tile, -jnp.inf)
        pool_v = jnp.concatenate([cvals, tile], axis=1)        # (B, k+Vt)
        pool_i = jnp.concatenate(
            [cids, jnp.broadcast_to(col[None, :], tile.shape)], axis=1)
        nvals, sel = jax.lax.top_k(pool_v, k)
        nids = jnp.take_along_axis(pool_i, sel, axis=1)
        return (nvals, nids.astype(jnp.int32)), None

    init = (jnp.full((B, k), -jnp.inf, jnp.float32),
            jnp.zeros((B, k), jnp.int32))
    (vals, ids), _ = jax.lax.scan(body, init, jnp.arange(nv))
    return ids, vals


@partial(jax.jit,
         static_argnames=("k", "impl", "block_v", "block_d", "shard"))
def verify_topk(hn: jnp.ndarray, lm_head, k: int,
                impl: Optional[str] = None, block_v: Optional[int] = None,
                block_d: int = 512, shard=None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-LM-head top-k — the streaming sibling of ``verify_argmax`` for
    the draft proposal path. hn: (B, D); lm_head: (D, V).

    "kernel"/"xla" tile the vocab keeping a running per-row top-k with fp32
    accumulation and never materialize (B, V); "ref" is ``propose_topk``'s
    historical materialized matmul in ``hn.dtype`` + ``jax.lax.top_k``. Auto
    resolves like ``verify_argmax`` (kernel on TPU, ref on CPU).
    ``block_v=None`` takes the autotuned strip width (the top-k kernel
    shares the argmax kernel's tiling knobs — same sweep, same table).
    ``shard``: optional ShardCtx — per-shard partial top-k over the local
    vocab slice merged by one tiny ``lax.top_k`` over the (B, P·k) pool
    (same tie contract as the single-device path; see ``verify_argmax``).
    Returns (ids (B, k) int32, vals (B, k) fp32), descending by logit.
    """
    impl = _resolve(impl, cpu_default="ref")
    if shard is not None and not isinstance(lm_head, QTensor):
        return _verify_topk_sharded(hn, lm_head, k, shard, impl, block_v,
                                    block_d)
    if isinstance(lm_head, QTensor):
        if block_v is None:
            block_v = tuning.best_block_v(hn.shape[1], lm_head.shape[-1],
                                          wbits=lm_head.bits)
        if impl == "kernel":
            call = partial(topk_verify_fused, k=k, block_v=block_v,
                           block_d=block_d)
            if shard is not None:
                call = shard.replicated(call)
            return call(hn, lm_head)
        if impl == "xla":
            return _topk_streaming_xla_q(hn, lm_head, k, block_v)
        return gate_ref.verify_topk_ref(hn, lm_head, k,
                                        compute_dtype=hn.dtype)
    if block_v is None:
        block_v = tuning.best_block_v(hn.shape[1], lm_head.shape[1])
    if impl == "kernel":
        return topk_verify_fused(hn, lm_head, k, block_v=block_v,
                                 block_d=block_d)
    if impl == "xla":
        return _topk_streaming_xla(hn, lm_head, k, block_v)
    return gate_ref.verify_topk_ref(hn, lm_head, k, compute_dtype=hn.dtype)
