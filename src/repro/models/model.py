"""Unified model API over the zoo.

A model's layer stack is decomposed into **segments**: maximal runs of a
repeating *unit* of block kinds, e.g.

    llama2-7b          -> [ ((attention,), 32) ]
    mamba2-130m        -> [ ((ssd,), 24) ]
    recurrentgemma-9b  -> [ ((rglru, rglru, local_attention), 12), ((rglru,), 2) ]

Parameters for each segment are *stacked* over the repeat count, so the
sequence path runs as ``lax.scan`` over units (fast compile at any depth) and
the early-exit decode path runs as ``lax.while_loop`` with
``dynamic_index_in_dim`` into the same stacks. SpecEE exit points sit at unit
boundaries (DESIGN.md §3: exit granularity = unit = 1 layer for homogeneous
archs, 3 layers for the hybrid).

Public surface (all functions are pure; ``Model`` just binds the config):
    m = build_model(run_config)
    params = m.init(key)
    loss, aux = m.train_loss(params, batch, rng)
    logits, cache = m.prefill(params, batch)
    logits, cache = m.decode_step(params, token, cache)          # dense baseline
    h, cache = m.run_unit(params, seg, unit_idx, h, cache, pos)  # SpecEE engine API
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.config import (ATTN, LOCAL_ATTN, RGLRU, SSD, ModelConfig, RunConfig,
                          SSMConfig)
from repro.core import paged as paged_lib
from repro.models import attention as attn_lib
from repro.models import common, frontends, moe as moe_lib, rglru as rglru_lib
from repro.models import ssd as ssd_lib
from repro.models.common import KeyGen, Params


# ---------------------------------------------------------------------------
# segment decomposition
# ---------------------------------------------------------------------------
def segments_of(blocks: Sequence[str], max_unit: int = 4
                ) -> List[Tuple[Tuple[str, ...], int]]:
    """Greedy decomposition of a block pattern into (unit, repeat) segments."""
    blocks = list(blocks)
    segs: List[Tuple[Tuple[str, ...], int]] = []
    i, n = 0, len(blocks)
    while i < n:
        best_unit, best_cov = (blocks[i],), 1
        for ul in range(1, max_unit + 1):
            if i + ul > n:
                break
            unit = blocks[i:i + ul]
            reps = 1
            while (i + (reps + 1) * ul <= n and
                   blocks[i + reps * ul: i + (reps + 1) * ul] == unit):
                reps += 1
            cov = reps * ul
            if cov > best_cov:
                best_unit, best_cov = tuple(unit), cov
        segs.append((best_unit, best_cov // len(best_unit)))
        i += best_cov
    return segs


@dataclass(frozen=True)
class ModelFlags:
    """Implementation-selection knobs (kernels, MoE formulation, remat)."""
    moe_impl: str = "dense"        # "dense" (EP-shardable einsum) | "topk" (gather)
    flash_attention: bool = False  # Pallas prefill kernel
    decode_kernel: bool = False    # Pallas split-KV decode kernel
    spec_head_kernel: bool = False  # Pallas fused speculative-LM-head kernel
    exit_gate_kernel: bool = False  # fused exit-gate pipeline (§Perf): the
    #   per-exit-point spec-head→predictor→verify chain runs through
    #   repro.kernels.exit_gate instead of the four-op reference sequence;
    #   verification streams the LM head (never materializes (B, V) logits)
    exit_gate_impl: str = "auto"   # fused backend: "auto" (kernel on TPU,
    #   fused-XLA elsewhere) | "kernel" | "xla" — only read when
    #   exit_gate_kernel is True
    remat: str = "none"            # "none" | "full"
    chunk_threshold: int = 2048    # chunked exact attention above this seq len
    chunk_size: int = 512          # query-chunk size for chunked attention
    ce_chunk: int = 512            # sequence-chunk size for the chunked CE loss
    kv_quant: bool = False         # int8 KV cache (per-vector scales) — §Perf
    #   beyond-paper optimization: halves decode's dominant HBM term
    attn_prune: bool = False       # causally-pruned chunked attention (§Perf):
    #   dynamic KV bounds recover the 2× causal FLOP saving in prefill/train
    moe_ep_quant: bool = False     # int8 EP token dispatch (§Perf): halves
    #   the MoE all-gather bytes on the ICI
    moe_bf16_reduce: bool = False  # bf16 accumulation for the MoE combine
    #   einsum (§Perf): the cross-device partial-sum reduction moves bf16
    #   instead of f32 — halves the dominant EP psum bytes
    act_seq_shard: bool = False    # Megatron sequence parallelism (§Perf):
    #   pin the residual stream's seq dim over 'model' at unit boundaries —
    #   row-parallel psums become reduce-scatters (half the AR payload)
    act_pin_full: bool = False     # pin the residual to P(batch, None, None)
    #   exactly (§Perf): stops GSPMD bouncing h between shardings across the
    #   layer body (kills the per-layer AG/AR resharding pairs)
    matmul_bf16_reduce: bool = False  # row-parallel projections emit bf16
    #   (§Perf): cross-shard psums move 2 bytes/elem instead of XLA's f32
    unroll: bool = False           # python-loop layers instead of lax.scan —
    #   identical math; used by roofline lowering so XLA cost_analysis counts
    #   every layer (scan bodies are counted once)
    # activation sharding constraints (MaxText-style): mesh axis name(s) for
    # the batch dim of the residual stream, pinned at every unit boundary so
    # GSPMD never "helpfully" replicates the batch. None = no constraints
    # (single-device tests). Example: ("pod", "data") or "data".
    act_batch_axes: Any = None
    act_batch_extent: int = 1      # product of those axes' sizes (skip the
    #   constraint when the batch dim does not divide it, e.g. long_500k B=1)


# ---------------------------------------------------------------------------
# per-block init / apply
# ---------------------------------------------------------------------------
def _init_block(cfg: ModelConfig, kind: str, kg: KeyGen) -> Params:
    if kind in (ATTN, LOCAL_ATTN):
        p: Params = {"ln1": common.init_norm(cfg, cfg.d_model),
                     "attn": attn_lib.init_attention(cfg, kg),
                     "ln2": common.init_norm(cfg, cfg.d_model)}
        if cfg.moe is not None:
            p["moe"] = moe_lib.init_moe(cfg, kg)
        else:
            p["mlp"] = common.init_mlp(cfg, kg)
        return p
    if kind == RGLRU:
        return {"ln1": common.init_norm(cfg, cfg.d_model),
                "rec": rglru_lib.init_rglru(cfg, kg),
                "ln2": common.init_norm(cfg, cfg.d_model),
                "mlp": common.init_mlp(cfg, kg)}
    if kind == SSD:
        return {"ln": common.init_norm(cfg, cfg.d_model),
                "ssd": ssd_lib.init_ssd(cfg, kg)}
    raise ValueError(kind)


def _window(cfg: ModelConfig, kind: str) -> Optional[int]:
    if kind == LOCAL_ATTN:
        return (cfg.rglru.window if cfg.rglru else 2048)
    return None


def _kv_quantize(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-(position, head) symmetric int8: x (..., hd) -> (q int8, scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) + 1e-8
    scale = (amax / 127.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _kv_dequantize(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
            ).astype(dtype)


def _entry_write_token(cache_entry: Any, vals: Dict[str, jnp.ndarray],
                       pages: Optional[jnp.ndarray], rows: jnp.ndarray,
                       pvec: jnp.ndarray) -> Any:
    """Write one token's projections into an attention cache entry.

    vals: {"k": ..., "v": ...} (+"ks"/"vs" under kv_quant), each (B, ...).
    The ONE place the dense row-scatter vs paged table-scatter choice is
    made for single-token writes — decode step and skipped-layer propagation
    share it, so the two paths cannot drift."""
    if pages is None:
        return {name: cache_entry[name].at[rows, pvec].set(
                    v.astype(cache_entry[name].dtype))
                for name, v in vals.items()}
    return {name: paged_lib.scatter_token(cache_entry[name], pages, pvec, v)
            for name, v in vals.items()}


def _wsc(x: jnp.ndarray, flags: "ModelFlags") -> jnp.ndarray:
    """Pin the batch dim of an activation to the data axes (and, under
    ``act_seq_shard``, the sequence dim to 'model'); leave every other dim to
    GSPMD (UNCONSTRAINED)."""
    if flags.act_batch_axes is None or x.ndim == 0:
        return x
    if flags.act_batch_extent and x.shape[0] % max(flags.act_batch_extent, 1):
        return x
    from jax.sharding import PartitionSpec as P
    if flags.act_pin_full and x.ndim >= 3:
        rest: list = [None] * (x.ndim - 1)
    else:
        rest = [P.UNCONSTRAINED] * (x.ndim - 1)
    if (flags.act_seq_shard and x.ndim >= 3 and
            x.shape[1] >= 1024 and x.shape[1] % 16 == 0):
        rest[0] = "model"
    return jax.lax.with_sharding_constraint(
        x, P(flags.act_batch_axes, *rest))


def _ffn(cfg: ModelConfig, p: Params, h: jnp.ndarray,
         flags: ModelFlags) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Post-attention FFN (dense MLP or MoE). Returns (out, aux_loss)."""
    if "moe" in p:
        if flags.moe_impl == "dense":
            return moe_lib.apply_moe(cfg, p["moe"], h,
                                     ep_axes=flags.act_batch_axes,
                                     ep_extent=flags.act_batch_extent,
                                     ep_quant=flags.moe_ep_quant,
                                     bf16_reduce=flags.moe_bf16_reduce)
        return moe_lib.apply_moe_topk(cfg, p["moe"], h)
    return common.apply_mlp(cfg, p["mlp"], h), jnp.float32(0.0)


# ----- sequence (train / prefill) path -------------------------------------
def _block_seq(cfg: ModelConfig, kind: str, p: Params, h: jnp.ndarray,
               positions: jnp.ndarray, flags: ModelFlags,
               collect_cache: bool) -> Tuple[jnp.ndarray, Any, jnp.ndarray]:
    """Returns (h_out, cache_entry_or_None, aux_loss)."""
    aux = jnp.float32(0.0)
    if kind in (ATTN, LOCAL_ATTN):
        x = common.apply_norm(cfg, p["ln1"], h)
        q, k, v = attn_lib.qkv(cfg, p["attn"], x, positions)
        if flags.flash_attention and cfg.causal:
            from repro.kernels.flash_attention import ops as fa_ops
            o = fa_ops.flash_attention(q, k, v, causal=True,
                                       window=_window(cfg, kind))
        elif x.shape[1] > flags.chunk_threshold:
            if flags.attn_prune and cfg.causal:
                o = attn_lib.attend_full_chunked_pruned(
                    cfg, q, k, v, _window(cfg, kind), chunk=flags.chunk_size)
            else:
                o = attn_lib.attend_full_chunked(cfg, q, k, v,
                                                 _window(cfg, kind),
                                                 chunk=flags.chunk_size)
        else:
            o = attn_lib.attend_full(cfg, q, k, v, _window(cfg, kind))
        pet = jnp.bfloat16 if flags.matmul_bf16_reduce else None
        h = h + attn_lib.out_proj(p["attn"], o, pet=pet)
        x2 = common.apply_norm(cfg, p["ln2"], h)
        if "moe" in p:
            f, aux = _ffn(cfg, p, x2, flags)
        else:
            f = common.apply_mlp(cfg, p["mlp"], x2, pet=pet)
        h = h + f
        cache = {"k": k, "v": v} if collect_cache else None
        return h, cache, aux
    if kind == RGLRU:
        x = common.apply_norm(cfg, p["ln1"], h)
        out, h_rec, conv_tail = rglru_lib.rglru_block_seq(cfg, p["rec"], x)
        h = h + out
        x2 = common.apply_norm(cfg, p["ln2"], h)
        h = h + common.apply_mlp(cfg, p["mlp"], x2)
        cache = ({"h": h_rec, "conv": conv_tail} if collect_cache else None)
        return h, cache, aux
    if kind == SSD:
        x = common.apply_norm(cfg, p["ln"], h)
        out, state, conv_tail = ssd_lib.ssd_block_seq(cfg, p["ssd"], x)
        h = h + out
        cache = ({"state": state, "conv": conv_tail} if collect_cache else None)
        return h, cache, aux
    raise ValueError(kind)


# ----- single-token decode path ---------------------------------------------
def _block_step(cfg: ModelConfig, kind: str, p: Params, h: jnp.ndarray,
                cache_entry: Any, pos: jnp.ndarray, flags: ModelFlags,
                live_mask: Optional[jnp.ndarray] = None,
                pages: Optional[jnp.ndarray] = None, shard=None
                ) -> Tuple[jnp.ndarray, Any]:
    """h: (B, D) one token; cache_entry: this block's slice of the cache.
    pos: scalar int32 — index of the current token. Returns (h_out, new_entry).

    live_mask: (B,) bool — SpecEE: rows that have exited keep their recurrent
    state stale (attention K/V writes are propagation-consistent because the
    input hidden state of exited rows is frozen at the exit value).

    pages: (B, P) int32 page table or None. When set, attention cache leaves
    are page pools ``(n_pages, page_size, ...)`` and every read/write goes
    through the table (``repro.core.paged``); the gathered logical view keeps
    the math bit-identical to the dense layout. Recurrent/SSD entries are
    never paged.

    shard: optional ShardCtx — the paged attention kernel then runs per
    KV-head shard of the pools (a Pallas kernel cannot be GSPMD-partitioned).
    """
    B, D = h.shape
    if kind in (ATTN, LOCAL_ATTN):
        x = common.apply_norm(cfg, p["ln1"], h)[:, None, :]       # (B,1,D)
        pvec = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
        positions = pvec[:, None]
        rows = jnp.arange(B)
        q, k, v = attn_lib.qkv(cfg, p["attn"], x, positions)
        if flags.kv_quant:
            kq, ks = _kv_quantize(k[:, 0])
            vq, vs = _kv_quantize(v[:, 0])
            new_entry = _entry_write_token(
                cache_entry, {"k": kq, "v": vq, "ks": ks, "vs": vs},
                pages, rows, pvec)
        else:
            new_entry = _entry_write_token(
                cache_entry, {"k": k[:, 0], "v": v[:, 0]}, pages, rows, pvec)
        use_paged_kernel = flags.decode_kernel and pages is not None
        if use_paged_kernel:
            # the paged kernel reads pages (and, under kv_quant, scale
            # pages) straight from the pool — never build the gathered view
            k_cache = v_cache = None
        else:
            if pages is None:
                k_view, v_view = new_entry["k"], new_entry["v"]
                ks_view = new_entry.get("ks")
                vs_view = new_entry.get("vs")
            else:
                k_view = paged_lib.gather_view(new_entry["k"], pages)
                v_view = paged_lib.gather_view(new_entry["v"], pages)
                ks_view = (paged_lib.gather_view(new_entry["ks"], pages)
                           if flags.kv_quant else None)
                vs_view = (paged_lib.gather_view(new_entry["vs"], pages)
                           if flags.kv_quant else None)
            if flags.kv_quant:
                k_cache = _kv_dequantize(k_view, ks_view, h.dtype)
                v_cache = _kv_dequantize(v_view, vs_view, h.dtype)
            else:
                k_cache, v_cache = k_view, v_view
        if use_paged_kernel:
            # page-table-aware split-KV kernel: reads pages straight from the
            # pool, never materializing the (B, S, ...) logical view; int8
            # pools stream codes + per-position scale pages and dequantize
            # in-register (gather∘dequant ≡ dequant∘gather — per-position
            # scales commute with the page gather)
            from repro.kernels.decode_attention import ops as da_ops
            o = da_ops.paged_decode_attention(
                cfg, q, new_entry["k"], new_entry["v"], pages, pvec + 1,
                window=_window(cfg, kind),
                k_scale=new_entry["ks"] if flags.kv_quant else None,
                v_scale=new_entry["vs"] if flags.kv_quant else None,
                shard=shard)
        elif flags.decode_kernel:
            from repro.kernels.decode_attention import ops as da_ops
            o = da_ops.decode_attention(cfg, q, k_cache, v_cache, pvec + 1,
                                        window=_window(cfg, kind))
        else:
            o = attn_lib.attend_decode(cfg, q, k_cache, v_cache, pvec + 1,
                                       _window(cfg, kind))
        h = h + attn_lib.out_proj(p["attn"], o)[:, 0, :]
        x2 = common.apply_norm(cfg, p["ln2"], h[:, None, :])
        f, _ = _ffn(cfg, p, x2, flags)
        h = h + f[:, 0, :]
        return h, new_entry
    if kind == RGLRU:
        x = common.apply_norm(cfg, p["ln1"], h)
        out, new_h, new_conv = rglru_lib.rglru_block_step(
            cfg, p["rec"], x, cache_entry["h"], cache_entry["conv"])
        if live_mask is not None:
            new_h = jnp.where(live_mask[:, None], new_h, cache_entry["h"])
        h = h + out
        x2 = common.apply_norm(cfg, p["ln2"], h)
        h = h + common.apply_mlp(cfg, p["mlp"], x2)
        return h, {"h": new_h, "conv": new_conv}
    if kind == SSD:
        x = common.apply_norm(cfg, p["ln"], h)
        out, new_state, new_conv = ssd_lib.ssd_block_step(
            cfg, p["ssd"], x, cache_entry["state"], cache_entry["conv"])
        if live_mask is not None:
            new_state = jnp.where(live_mask[:, None, None, None], new_state,
                                  cache_entry["state"])
        h = h + out
        return h, {"state": new_state, "conv": new_conv}
    raise ValueError(kind)


def _block_propagate(cfg: ModelConfig, kind: str, p: Params, h: jnp.ndarray,
                     cache_entry: Any, pos: jnp.ndarray,
                     flags: ModelFlags = ModelFlags(),
                     pages: Optional[jnp.ndarray] = None) -> Any:
    """SpecEE skipped-layer state maintenance (DESIGN.md §3).

    Attention: KV propagation — write K/V projections of the *exit* hidden
    state so future tokens can attend to this position. Recurrent/SSM blocks:
    stale state (no update) is the correct analogue; conv states DO get the
    current input pushed so the temporal window stays aligned.
    """
    if kind in (ATTN, LOCAL_ATTN):
        B, D = h.shape
        x = common.apply_norm(cfg, p["ln1"], h)[:, None, :]
        pvec = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
        rows = jnp.arange(B)
        k, v = attn_lib.kv_only(cfg, p["attn"], x, pvec[:, None])
        if flags.kv_quant:
            kq, ks = _kv_quantize(k[:, 0])
            vq, vs = _kv_quantize(v[:, 0])
            vals = {"k": kq, "v": vq, "ks": ks, "vs": vs}
        else:
            vals = {"k": k[:, 0], "v": v[:, 0]}
        return _entry_write_token(cache_entry, vals, pages, rows, pvec)
    if kind == RGLRU:
        x = common.apply_norm(cfg, p["ln1"], h)
        xb = common.apply_linear(p["rec"]["wx"], x)
        window = jnp.concatenate(
            [cache_entry["conv"].astype(xb.dtype), xb[:, None, :]], axis=1)
        return {"h": cache_entry["h"], "conv": window[:, 1:, :]}
    if kind == SSD:
        x = common.apply_norm(cfg, p["ln"], h)
        proj = common.apply_linear(p["ssd"]["in_proj"], x)
        _, xBC, _ = ssd_lib._split_proj(cfg, proj)
        window = jnp.concatenate(
            [cache_entry["conv"].astype(xBC.dtype), xBC[:, None, :]], axis=1)
        return {"state": cache_entry["state"], "conv": window[:, 1:, :]}
    raise ValueError(kind)


# ----- chunked-prefill extension step ---------------------------------------
def _block_extend(cfg: ModelConfig, kind: str, p: Params, h: jnp.ndarray,
                  cache_entry: Any, pos0: jnp.ndarray,
                  positions: jnp.ndarray, flags: ModelFlags
                  ) -> Tuple[jnp.ndarray, Any]:
    """Process a C-token prompt chunk against a dense decode cache.

    h: (B, C, D); pos0: (B,) prefix length; positions: (B, C) absolute
    positions of the chunk. Chunk K/V is written (quantized under
    ``kv_quant``) before attending, so intra-chunk causal attention sees its
    own keys exactly like the decode step does. Attention-family blocks only
    (DESIGN.md §4 — chunked prefill needs an order-free state extension,
    which recurrent/SSD blocks don't expose)."""
    assert kind in (ATTN, LOCAL_ATTN)
    B, C, D = h.shape
    x = common.apply_norm(cfg, p["ln1"], h)
    q, k, v = attn_lib.qkv(cfg, p["attn"], x, positions)
    rows = jnp.arange(B)[:, None]
    if flags.kv_quant:
        kq, ks = _kv_quantize(k)
        vq, vs = _kv_quantize(v)
        new_entry = {
            "k": cache_entry["k"].at[rows, positions].set(kq, mode="drop"),
            "v": cache_entry["v"].at[rows, positions].set(vq, mode="drop"),
            "ks": cache_entry["ks"].at[rows, positions].set(ks, mode="drop"),
            "vs": cache_entry["vs"].at[rows, positions].set(vs, mode="drop")}
        k_cache = _kv_dequantize(new_entry["k"], new_entry["ks"], h.dtype)
        v_cache = _kv_dequantize(new_entry["v"], new_entry["vs"], h.dtype)
    else:
        k_cache = cache_entry["k"].at[rows, positions].set(
            k.astype(cache_entry["k"].dtype), mode="drop")
        v_cache = cache_entry["v"].at[rows, positions].set(
            v.astype(cache_entry["v"].dtype), mode="drop")
        new_entry = {"k": k_cache, "v": v_cache}
    o = attn_lib.attend_extend(cfg, q, k_cache, v_cache, pos0,
                               window=_window(cfg, kind))
    h = h + attn_lib.out_proj(p["attn"], o)
    x2 = common.apply_norm(cfg, p["ln2"], h)
    f, _ = _ffn(cfg, p, x2, flags)
    h = h + f
    return h, new_entry


# ----- tree-verification step (T3 speculative decoding) ---------------------
def _block_step_tree(cfg: ModelConfig, p: Params, h: jnp.ndarray,
                     cache_entry: Any, mask: jnp.ndarray,
                     positions: jnp.ndarray, scratch_off: int,
                     flags: ModelFlags,
                     pages: Optional[jnp.ndarray] = None
                     ) -> Tuple[jnp.ndarray, Any]:
    """Process N tree tokens at once against a cache with N scratch slots.

    h: (B, N, D); mask: (1|B, 1, N, S+N) boolean (context + ancestor);
    positions: (B, N) absolute positions; scratch_off: static int — tree K/V
    land at LOGICAL cache slots [scratch_off, scratch_off+N) (page-table
    indirected when ``pages`` is set).
    Attention-family blocks only (DESIGN.md §4: T3 is restricted to
    transformer archs; SSM/hybrid use the AR engine).
    """
    B, N, D = h.shape
    x = common.apply_norm(cfg, p["ln1"], h)
    q, k, v = attn_lib.qkv(cfg, p["attn"], x, positions)
    if pages is None:
        new_k = jax.lax.dynamic_update_slice_in_dim(
            cache_entry["k"], k.astype(cache_entry["k"].dtype), scratch_off,
            axis=1)
        new_v = jax.lax.dynamic_update_slice_in_dim(
            cache_entry["v"], v.astype(cache_entry["v"].dtype), scratch_off,
            axis=1)
        k_cache, v_cache = new_k, new_v
    else:
        scratch_pos = jnp.broadcast_to(
            scratch_off + jnp.arange(N, dtype=jnp.int32)[None, :], (B, N))
        new_k = paged_lib.scatter_slab(cache_entry["k"], pages, scratch_pos, k)
        new_v = paged_lib.scatter_slab(cache_entry["v"], pages, scratch_pos, v)
        k_cache = paged_lib.gather_view(new_k, pages)
        v_cache = paged_lib.gather_view(new_v, pages)
    n_rep = cfg.num_heads // cfg.num_kv_heads
    kk = attn_lib._repeat_kv(k_cache, n_rep)
    vv = attn_lib._repeat_kv(v_cache, n_rep)
    o = attn_lib.sdpa(q, kk, vv, mask)
    h = h + attn_lib.out_proj(p["attn"], o)
    x2 = common.apply_norm(cfg, p["ln2"], h)
    f, _ = _ffn(cfg, p, x2, flags)
    h = h + f
    return h, {"k": new_k, "v": new_v}


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------
def _empty_cache_entry(cfg: ModelConfig, kind: str, batch: int, max_seq: int,
                       dtype, kv_quant: bool = False) -> Any:
    hd = cfg.resolved_head_dim()
    if kind in (ATTN, LOCAL_ATTN):
        shape = (batch, max_seq, cfg.num_kv_heads, hd)
        if kv_quant:
            return {"k": jnp.zeros(shape, jnp.int8),
                    "v": jnp.zeros(shape, jnp.int8),
                    "ks": jnp.zeros(shape[:-1], jnp.float32),
                    "vs": jnp.zeros(shape[:-1], jnp.float32)}
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if kind == RGLRU:
        w = rglru_lib.lru_width(cfg)
        K = (cfg.rglru.conv_kernel if cfg.rglru else 4)
        return {"h": jnp.zeros((batch, w), jnp.float32),
                "conv": jnp.zeros((batch, K - 1, w), dtype)}
    if kind == SSD:
        s = cfg.ssm or SSMConfig()
        di, nh, hdim, ds = ssd_lib.dims(cfg)
        return {"state": jnp.zeros((batch, nh, hdim, ds), jnp.float32),
                "conv": jnp.zeros((batch, s.conv_kernel - 1, di + 2 * ds), dtype)}
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------
class Model:
    def __init__(self, run: RunConfig, flags: ModelFlags = ModelFlags()):
        self.run = run
        self.cfg = run.model
        self.flags = flags
        self.segments = segments_of(list(self.cfg.blocks()))
        # exit points: one per unit instance, across segments
        self.units_per_segment = [reps for _, reps in self.segments]
        self.num_exit_points = sum(self.units_per_segment)
        # map exit point -> index of last absolute layer inside that unit
        self.exit_point_layers: List[int] = []
        abs_layer = 0
        for unit, reps in self.segments:
            for _ in range(reps):
                abs_layer += len(unit)
                self.exit_point_layers.append(abs_layer - 1)

    # ----- init -----
    def init(self, key, dtype: Optional[str] = None) -> Params:
        """Seeded random parameters with floating leaves in ``dtype``
        (default ``cfg.dtype``, the serving dtype; training passes
        ``cfg.param_dtype`` for fp32 master weights). One jit draws and
        casts every leaf, so an fp32 copy of the whole model is never
        resident on the device (``repro.sharding.serving.init_params``
        draws them straight into a mesh's serving layout)."""
        dt = common.dtype_of(dtype or self.cfg.dtype)
        return jax.jit(
            lambda k: common.cast_tree(self._draw(k), dt))(key)

    def _draw(self, key) -> Params:
        cfg = self.cfg
        kg = KeyGen(key)
        params: Params = {"embed": common.init_embedding(cfg, kg)}
        fe = frontends.init_frontend(cfg, kg)
        if fe is not None:
            params["frontend"] = fe
        seg_params = []
        for unit, reps in self.segments:
            def init_one(k):
                kg2 = KeyGen(k)
                return {f"u{i}": _init_block(cfg, kind, kg2)
                        for i, kind in enumerate(unit)}
            keys = jax.random.split(kg(), reps)
            stacked = jax.vmap(init_one)(keys)
            seg_params.append(stacked)
        params["segments"] = seg_params
        params["final_norm"] = common.init_norm(cfg, cfg.d_model)
        if not cfg.tie_embeddings:
            params["lm_head"] = {
                "w": common.normal_init(kg(), (cfg.d_model, cfg.vocab_size),
                                        1.0 / math.sqrt(cfg.d_model))}
        return params

    # ----- embedding / head -----
    def embed(self, params: Params, tokens: jnp.ndarray) -> jnp.ndarray:
        return common.embed_tokens(params["embed"], tokens,
                                   common.dtype_of(self.cfg.dtype))

    def final_norm(self, params: Params, h: jnp.ndarray) -> jnp.ndarray:
        return common.apply_norm(self.cfg, params["final_norm"], h)

    def logits(self, params: Params, h: jnp.ndarray) -> jnp.ndarray:
        w = common.lm_head_weight(params)
        return (self.final_norm(params, h) @ w.astype(h.dtype)).astype(jnp.float32)

    def lm_head_columns(self, params: Params, h: jnp.ndarray,
                        token_ids: jnp.ndarray) -> jnp.ndarray:
        """Speculative LM head: logits only for ``token_ids``.

        h: (B, D) (pre-final-norm); token_ids: (B, k) -> (B, k) fp32 logits.
        """
        w = common.lm_head_weight(params)                       # (D, V)
        hn = self.final_norm(params, h)
        cols = w.T[token_ids]                                   # (B, k, D)
        return jnp.einsum("bd,bkd->bk", hn.astype(jnp.float32),
                          cols.astype(jnp.float32))

    # ----- sequence forward -----
    def forward_hidden(self, params: Params, h: jnp.ndarray,
                       positions: jnp.ndarray, collect_cache: bool = False
                       ) -> Tuple[jnp.ndarray, Any, jnp.ndarray]:
        """h: (B, S, D). Returns (h_final, caches_per_segment|None, aux_loss)."""
        cfg, flags = self.cfg, self.flags
        aux_total = jnp.float32(0.0)
        h = _wsc(h, flags)
        seg_caches = []
        for si, (unit, reps) in enumerate(self.segments):
            def body(h_carry, unit_params):
                aux_sum = jnp.float32(0.0)
                caches = {}
                hc = h_carry
                for i, kind in enumerate(unit):
                    hc, ce, aux = _block_seq(cfg, kind, unit_params[f"u{i}"],
                                             hc, positions, flags, collect_cache)
                    hc = _wsc(hc, flags)
                    if collect_cache:
                        caches[f"u{i}"] = jax.tree_util.tree_map(
                            lambda t: _wsc(t, flags), ce)
                    aux_sum = aux_sum + aux
                return hc, (caches, aux_sum)
            if flags.remat == "full":
                body = jax.checkpoint(body)
            if flags.unroll:
                caches_l, aux_l = [], []
                for r in range(reps):
                    up = jax.tree_util.tree_map(lambda x: x[r],
                                                params["segments"][si])
                    h, (c, a) = body(h, up)
                    caches_l.append(c)
                    aux_l.append(a)
                caches = (jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *caches_l)
                    if collect_cache else None)
                auxs = jnp.stack(aux_l)
            else:
                h, (caches, auxs) = jax.lax.scan(body, h,
                                                 params["segments"][si])
            aux_total = aux_total + jnp.sum(auxs)
            seg_caches.append(caches if collect_cache else None)
        return h, (seg_caches if collect_cache else None), aux_total

    # ----- training -----
    def train_loss(self, params: Params, batch: Dict[str, jnp.ndarray]
                   ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        cfg = self.cfg
        dtype = common.dtype_of(cfg.dtype)
        if cfg.frontend == "audio_frames":
            h = frontends.apply_frontend(cfg, params["frontend"],
                                         batch["frames"], dtype)
            positions = jnp.broadcast_to(jnp.arange(h.shape[1])[None, :],
                                         h.shape[:2])
            h, _, aux = self.forward_hidden(params, h, positions)
            logits = self.logits(params, h)                      # (B,S,V)
            tgt = batch["targets"]
            mask = batch["mask"].astype(jnp.float32)
            lse = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(lse, tgt[..., None], axis=-1)[..., 0]
            loss = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
            return loss + aux, {"ce": loss, "aux": aux}
        tokens = batch["tokens"]                                 # (B, S)
        h = self.embed(params, tokens)
        if cfg.frontend == "vision_patches":
            fe = frontends.apply_frontend(cfg, params["frontend"],
                                          batch["patches"], dtype)
            h = jnp.concatenate([fe, h], axis=1)
        B, S, _ = h.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        h, _, aux = self.forward_hidden(params, h, positions)
        # next-token prediction on the text region
        txt0 = h.shape[1] - tokens.shape[1]
        loss = self._ce_loss(params, h[:, txt0:-1, :], tokens[:, 1:],
                             chunk=self.flags.ce_chunk)
        return loss + aux, {"ce": loss, "aux": aux}

    def _ce_loss(self, params: Params, h: jnp.ndarray,
                 targets: jnp.ndarray, chunk: int = 512) -> jnp.ndarray:
        """Cross-entropy without materializing the (B, S, V) logits: scan
        over sequence chunks with per-chunk recompute (``jax.checkpoint``) —
        peak logits memory is (B, chunk, V/TP)."""
        cfg = self.cfg
        B, S, D = h.shape
        if S * cfg.vocab_size <= (1 << 24):      # small: direct path
            logits = self.logits(params, h)
            lse = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(lse, targets[..., None], axis=-1)[..., 0]
            return -jnp.mean(ll)
        chunk = min(chunk, S)
        pad = (-S) % chunk
        w = jnp.pad(jnp.ones((B, S), jnp.float32), ((0, 0), (0, pad)))
        hp = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        tp = jnp.pad(targets, ((0, 0), (0, pad)))
        nc = hp.shape[1] // chunk
        hc = jnp.moveaxis(hp.reshape(B, nc, chunk, D), 1, 0)
        tc = jnp.moveaxis(tp.reshape(B, nc, chunk), 1, 0)
        wc = jnp.moveaxis(w.reshape(B, nc, chunk), 1, 0)

        @jax.checkpoint
        def body(acc, xs):
            h_c, t_c, w_c = xs
            logits = self.logits(params, h_c)                  # (B, c, V)
            lse = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(lse, t_c[..., None], axis=-1)[..., 0]
            return acc - jnp.sum(ll * w_c), None

        total, _ = jax.lax.scan(body, jnp.float32(0.0), (hc, tc, wc))
        return total / (B * S)

    # ----- prefill -----
    def prefill(self, params: Params, batch: Dict[str, jnp.ndarray],
                max_seq: Optional[int] = None
                ) -> Tuple[jnp.ndarray, Any, Dict[str, jnp.ndarray]]:
        """Returns (logits of last position (B, V), cache, extras).

        extras["h_final"]: (B, S, D) pre-final-norm hidden of every position
        (consumed by the SpecEE draft prefill and predictor training)."""
        cfg = self.cfg
        dtype = common.dtype_of(cfg.dtype)
        if cfg.frontend == "audio_frames":
            h = frontends.apply_frontend(cfg, params["frontend"],
                                         batch["frames"], dtype)
        else:
            h = self.embed(params, batch["tokens"])
            if cfg.frontend == "vision_patches":
                fe = frontends.apply_frontend(cfg, params["frontend"],
                                              batch["patches"], dtype)
                h = jnp.concatenate([fe, h], axis=1)
        B, S, _ = h.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        h, caches, _ = self.forward_hidden(params, h, positions,
                                           collect_cache=True)
        if not cfg.is_decoder():
            # encoder: return frame logits, no cache semantics
            return self.logits(params, h), None, {"h_final": h}
        cache = self._materialize_cache(caches, S, max_seq or (S + 1), dtype)
        return self.logits(params, h[:, -1, :]), cache, {"h_final": h}

    def _materialize_cache(self, seg_caches, S: int, max_seq: int, dtype):
        """Pad prefill K/V to max_seq slots; wrap with position counter."""
        cfg = self.cfg
        out_segs = []
        for (unit, reps), caches in zip(self.segments, seg_caches):
            entry = {}
            for i, kind in enumerate(unit):
                ce = caches[f"u{i}"]
                if kind in (ATTN, LOCAL_ATTN):
                    def pad(x, dt=dtype):
                        padding = [(0, 0)] * x.ndim
                        padding[2] = (0, max_seq - S)
                        return jnp.pad(x, padding).astype(dt)
                    if self.flags.kv_quant:
                        kq, ks = _kv_quantize(ce["k"])
                        vq, vs = _kv_quantize(ce["v"])
                        entry[f"u{i}"] = {"k": pad(kq, jnp.int8),
                                          "v": pad(vq, jnp.int8),
                                          "ks": pad(ks, jnp.float32),
                                          "vs": pad(vs, jnp.float32)}
                    else:
                        entry[f"u{i}"] = {"k": pad(ce["k"]),
                                          "v": pad(ce["v"])}
                else:
                    entry[f"u{i}"] = ce
            out_segs.append(entry)
        B = jax.tree_util.tree_leaves(out_segs[0])[0].shape[1]
        return {"segments": out_segs, "len": jnp.full((B,), S, jnp.int32)}

    def empty_cache(self, batch: int, max_seq: int) -> Any:
        cfg = self.cfg
        dtype = common.dtype_of(cfg.dtype)
        segs = []
        for unit, reps in self.segments:
            entry = {}
            for i, kind in enumerate(unit):
                one = _empty_cache_entry(cfg, kind, batch, max_seq, dtype,
                                         self.flags.kv_quant)
                entry[f"u{i}"] = jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(x[None], (reps,) + x.shape), one)
            segs.append(entry)
        return {"segments": segs, "len": jnp.zeros((batch,), jnp.int32)}

    # ----- chunked prefill (Sarathi-style admission) -----
    def supports_chunked_prefill(self) -> bool:
        """Chunked prefill needs blocks whose state extension is expressible
        as "write K/V, attend prefix" — attention-family only (DESIGN.md §4).
        Recurrent/SSD and frontend archs admit with one whole-prompt chunk.
        """
        return (self.cfg.is_decoder() and self.cfg.frontend == "none" and
                all(k in (ATTN, LOCAL_ATTN)
                    for unit, _ in self.segments for k in unit))

    def prefill_extend(self, params: Params, tokens: jnp.ndarray, cache: Any,
                       n_valid) -> Tuple[jnp.ndarray, Any]:
        """Extend a DENSE decode cache with one prompt chunk.

        tokens: (B, C) int32, first ``n_valid`` real (the tail is padding
        whose K/V lands past the prompt and is later overwritten or masked —
        intra-chunk causality already hides it from real queries).
        Returns (h (B, C, D) pre-final-norm hiddens, cache with
        ``len += n_valid``). The admission path of ``DecodeSession.
        prefill_chunk`` jits exactly this."""
        assert self.supports_chunked_prefill(), \
            f"{self.cfg.name}: chunked prefill requires a pure-attention " \
            "decoder stack (DESIGN.md §4)"
        h = self.embed(params, tokens)                       # (B, C, D)
        pos0 = cache["len"]
        B, C = tokens.shape
        positions = pos0[:, None] + jnp.arange(C)[None, :]
        new_segs = []
        for seg in range(len(self.segments)):
            def body(carry, xs):
                hc = carry
                unit_params, entry = xs
                new_entry = {}
                for i, kind in enumerate(self.segments[seg][0]):
                    hc, ne = _block_extend(self.cfg, kind,
                                           unit_params[f"u{i}"], hc,
                                           entry[f"u{i}"], pos0, positions,
                                           self.flags)
                    new_entry[f"u{i}"] = jax.tree_util.tree_map(
                        lambda n, o: n.astype(o.dtype), ne, entry[f"u{i}"])
                return _wsc(hc, self.flags), new_entry

            h, new_seg_cache = jax.lax.scan(
                body, h, (params["segments"][seg], cache["segments"][seg]))
            new_segs.append(new_seg_cache)
        return h, dict(cache, segments=new_segs,
                       len=pos0 + jnp.asarray(n_valid, jnp.int32))

    # ----- layer-granular decode API (SpecEE engine) -----
    def run_unit(self, params: Params, seg: int, unit_idx: jnp.ndarray,
                 h: jnp.ndarray, seg_cache: Any, pos: jnp.ndarray,
                 live_mask: Optional[jnp.ndarray] = None,
                 pages: Optional[jnp.ndarray] = None, shard=None
                 ) -> Tuple[jnp.ndarray, Any]:
        """Run unit ``unit_idx`` (dynamic) of segment ``seg`` (static) on one
        token. h: (B, D). seg_cache: the stacked cache of this segment.
        ``pages``: the session page table when the cache is paged.
        ``shard``: optional ShardCtx of a tensor-parallel mesh.
        Returns (h_out, updated seg_cache)."""
        unit, reps = self.segments[seg]
        up = jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_index_in_dim(x, unit_idx, 0, False),
            params["segments"][seg])
        ce = jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_index_in_dim(x, unit_idx, 0, False),
            seg_cache)
        new_entries = {}
        for i, kind in enumerate(unit):
            h, ne = _block_step(self.cfg, kind, up[f"u{i}"], h, ce[f"u{i}"],
                                pos, self.flags, live_mask, pages=pages,
                                shard=shard)
            new_entries[f"u{i}"] = ne
        seg_cache = jax.tree_util.tree_map(
            lambda full, new: jax.lax.dynamic_update_index_in_dim(
                full, new.astype(full.dtype), unit_idx, 0),
            seg_cache, new_entries)
        return _wsc(h, self.flags), seg_cache

    def propagate_unit(self, params: Params, seg: int, unit_idx: jnp.ndarray,
                       h: jnp.ndarray, seg_cache: Any, pos: jnp.ndarray,
                       pages: Optional[jnp.ndarray] = None) -> Any:
        """KV/state propagation for a skipped unit (SpecEE early exit)."""
        unit, reps = self.segments[seg]
        up = jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_index_in_dim(x, unit_idx, 0, False),
            params["segments"][seg])
        ce = jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_index_in_dim(x, unit_idx, 0, False),
            seg_cache)
        new_entries = {}
        for i, kind in enumerate(unit):
            new_entries[f"u{i}"] = _block_propagate(
                self.cfg, kind, up[f"u{i}"], h, ce[f"u{i}"], pos, self.flags,
                pages=pages)
        return jax.tree_util.tree_map(
            lambda full, new: jax.lax.dynamic_update_index_in_dim(
                full, new.astype(full.dtype), unit_idx, 0),
            seg_cache, new_entries)

    # ----- tree-verification API (T3) -----
    def supports_tree(self) -> bool:
        return all(k == ATTN for unit, _ in self.segments for k in unit)

    def run_unit_tree(self, params: Params, seg: int, unit_idx: jnp.ndarray,
                      h: jnp.ndarray, seg_cache: Any, mask: jnp.ndarray,
                      positions: jnp.ndarray, scratch_off: int,
                      pages: Optional[jnp.ndarray] = None
                      ) -> Tuple[jnp.ndarray, Any]:
        """Tree analogue of ``run_unit``: h is (B, N, D) tree-node hiddens."""
        unit, reps = self.segments[seg]
        up = jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_index_in_dim(x, unit_idx, 0, False),
            params["segments"][seg])
        ce = jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_index_in_dim(x, unit_idx, 0, False),
            seg_cache)
        new_entries = {}
        for i, kind in enumerate(unit):
            assert kind == ATTN, "tree mode requires pure-attention stacks"
            h, ne = _block_step_tree(self.cfg, up[f"u{i}"], h, ce[f"u{i}"],
                                     mask, positions, scratch_off, self.flags,
                                     pages=pages)
            new_entries[f"u{i}"] = ne
        seg_cache = jax.tree_util.tree_map(
            lambda full, new: jax.lax.dynamic_update_index_in_dim(
                full, new.astype(full.dtype), unit_idx, 0),
            seg_cache, new_entries)
        return h, seg_cache

    def propagate_unit_tree(self, params: Params, seg: int,
                            unit_idx: jnp.ndarray, h: jnp.ndarray,
                            seg_cache: Any, positions: jnp.ndarray,
                            scratch_off: int,
                            pages: Optional[jnp.ndarray] = None) -> Any:
        """KV propagation for tree scratch slots of a skipped unit."""
        unit, reps = self.segments[seg]
        up = jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_index_in_dim(x, unit_idx, 0, False),
            params["segments"][seg])
        ce = jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_index_in_dim(x, unit_idx, 0, False),
            seg_cache)
        N = h.shape[1]
        scratch_pos = scratch_off + jnp.arange(N, dtype=jnp.int32)[None, :]
        new_entries = {}
        for i, kind in enumerate(unit):
            p = up[f"u{i}"]
            x = common.apply_norm(self.cfg, p["ln1"], h)
            k, v = attn_lib.kv_only(self.cfg, p["attn"], x, positions)
            entry = ce[f"u{i}"]
            if pages is None:
                new_entries[f"u{i}"] = {
                    "k": jax.lax.dynamic_update_slice_in_dim(
                        entry["k"], k.astype(entry["k"].dtype), scratch_off,
                        axis=1),
                    "v": jax.lax.dynamic_update_slice_in_dim(
                        entry["v"], v.astype(entry["v"].dtype), scratch_off,
                        axis=1),
                }
            else:
                pos_mat = jnp.broadcast_to(scratch_pos, (h.shape[0], N))
                new_entries[f"u{i}"] = {
                    "k": paged_lib.scatter_slab(entry["k"], pages, pos_mat, k),
                    "v": paged_lib.scatter_slab(entry["v"], pages, pos_mat, v),
                }
        return jax.tree_util.tree_map(
            lambda full, new: jax.lax.dynamic_update_index_in_dim(
                full, new.astype(full.dtype), unit_idx, 0),
            seg_cache, new_entries)

    def accept_tree_kv(self, cache: Any, accepted_nodes: jnp.ndarray,
                       accepted_len: jnp.ndarray, pos0: jnp.ndarray,
                       scratch_off: int) -> Any:
        """Copy the K/V of accepted tree nodes from scratch slots into their
        real positions. accepted_nodes: (B, Dmax) node ids (-1 pad);
        accepted_len: (B,); node at chain index d lands at pos0+d. Paged
        caches (``cache["page_table"]``) route the copy through the table."""
        B, Dmax = accepted_nodes.shape
        rows = jnp.arange(B)
        pages = cache.get("page_table")

        def copy_leaf(x):
            # dense: x (reps, B, S+N, kvh, hd)
            for d in range(Dmax):
                node = accepted_nodes[:, d]
                valid = (d < accepted_len) & (node >= 0)
                src = x[:, rows, scratch_off + jnp.maximum(node, 0)]
                dst = x[:, rows, pos0 + d]
                x = x.at[:, rows, pos0 + d].set(
                    jnp.where(valid[None, :, None, None], src, dst))
            return x

        def copy_leaf_paged(x):
            # paged: x (reps, n_pages, ps, kvh, hd) — per-row logical slots
            # resolve through the page table
            ps = x.shape[2]
            xf = x.reshape((x.shape[0], x.shape[1] * ps) + x.shape[3:])
            for d in range(Dmax):
                node = accepted_nodes[:, d]
                valid = (d < accepted_len) & (node >= 0)
                src_slot = paged_lib.flat_slots(
                    pages, ps, scratch_off + jnp.maximum(node, 0))
                dst_slot = paged_lib.flat_slots(pages, ps, pos0 + d)
                src = xf[:, src_slot]                       # (reps, B, ...)
                dst = xf[:, dst_slot]
                vb = valid.reshape((1, B) + (1,) * (src.ndim - 2))
                xf = xf.at[:, dst_slot].set(jnp.where(vb, src, dst))
            return xf.reshape(x.shape)

        new_segs = []
        for seg, (unit, reps) in enumerate(self.segments):
            fn = copy_leaf if pages is None else copy_leaf_paged
            new_segs.append(jax.tree_util.tree_map(fn, cache["segments"][seg]))
        return dict(cache, segments=new_segs)

    # ----- dense decode (baseline, no early exit) -----
    def decode_step(self, params: Params, token: jnp.ndarray, cache: Any
                    ) -> Tuple[jnp.ndarray, Any]:
        """token: (B,) int32. Returns (logits (B, V) fp32, new cache)."""
        h, cache = self.decode_step_hidden(params, token, cache)
        return self.logits(params, h), cache

    def decode_step_hidden(self, params: Params, token: jnp.ndarray,
                           cache: Any, shard=None
                           ) -> Tuple[jnp.ndarray, Any]:
        """Full-depth decode returning the PRE-final-norm hidden instead of
        logits — the emit (LM head) is the caller's: ``dense_decode_step``
        streams it through ``verify_argmax`` so greedy dense decode never
        materializes the (B, V) logits either.
        token: (B,) int32; shard: optional ShardCtx of a tensor-parallel
        mesh. Returns (h (B, D), new cache)."""
        h = self.embed(params, token[:, None])[:, 0, :]          # (B, D)
        pos = cache["len"]
        pages = cache.get("page_table")
        new_segs = []
        for seg in range(len(self.segments)):
            seg_cache = cache["segments"][seg]
            reps = self.segments[seg][1]
            # the stacked cache rides in the loop carry and each unit writes
            # its entry back in place (``run_unit``) — a scan emitting the
            # entries as outputs would build a second copy of the cache
            if self.flags.unroll:
                for u in range(reps):
                    h, seg_cache = self.run_unit(params, seg, jnp.int32(u), h,
                                                 seg_cache, pos, pages=pages,
                                                 shard=shard)
            else:
                h, seg_cache = jax.lax.fori_loop(
                    0, reps,
                    lambda u, c, seg=seg: self.run_unit(
                        params, seg, u, c[0], c[1], pos, pages=pages,
                        shard=shard),
                    (h, seg_cache))
            new_segs.append(seg_cache)
        return h, dict(cache, segments=new_segs, len=pos + 1)


def build_model(run: RunConfig, flags: ModelFlags = ModelFlags()) -> Model:
    return Model(run, flags)
