"""Pallas TPU kernel: split-KV (flash-decoding) attention for long-context
decode (decode_32k / long_500k shapes).

One query token vs. a (B, S, KVH, hd) cache. The sequence dimension is tiled
(grid dim innermost, "arbitrary") with online-softmax scratch carried across
tiles — so a 512k-token cache streams through VMEM in ``block_k`` chunks and
the HBM traffic is exactly one pass over the valid prefix. ``cache_len`` is
scalar-prefetched: tiles entirely beyond the valid prefix (or entirely below
the sliding window) are skipped with ``pl.when`` — decode cost is
proportional to the *live* context, not the allocated cache.

The q head group for a KV head is processed as the matrix row dimension
(GQA-natural layout): q block (n_rep, hd) × k block (hd, Bk) uses the MXU
even at decode (n_rep up to 16 for our archs — paired with 128-wide k tiles).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _online_softmax_step(ki, clen, k_start, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, scale: float,
                         block_k: int, window: Optional[int], nk: int,
                         ks_ref=None, vs_ref=None):
    """Shared flash-decoding tile body for the dense and paged kernels.

    The two kernels differ ONLY in how a grid step locates its K/V block
    (sequential block index vs page-table indirection) — every numerics
    decision (masking, NEG_INF, online-softmax accumulation, the l == 0
    guard for fully-masked rows) lives here exactly once. ``k_start`` is the
    LOGICAL position of the block's first key.

    ``ks_ref``/``vs_ref``: optional per-(position, head) int8 dequant scales
    (``ModelFlags.kv_quant`` pools) as (Bk, 1) tiles; when present the K/V
    tiles are int8 codes and dequant happens here, in-register — the same
    per-position scales ``model._kv_dequantize`` applies to the gathered
    view, so dequant∘gather ≡ gather∘dequant holds bit-for-bit.
    """
    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = k_start < clen
    if window is not None:
        live = live & (k_start + block_k > clen - window)

    def _body():
        q = q_ref[0, 0].astype(jnp.float32)                 # (n_rep, hd)
        k = k_ref[0, 0].astype(jnp.float32)                 # (Bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)                 # (Bk, hd)
        if ks_ref is not None:
            k = k * ks_ref[0, 0].astype(jnp.float32)        # (Bk, 1) scales
        if vs_ref is not None:
            v = v * vs_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = kpos < clen
        if window is not None:
            valid = valid & (kpos >= clen - window)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    pl.when(live)(_body)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_k: int, window: Optional[int], nk: int):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    _online_softmax_step(ki, len_ref[b], ki * block_k, q_ref, k_ref, v_ref,
                         o_ref, m_scr, l_scr, acc_scr, scale=scale,
                         block_k=block_k, window=window, nk=nk)


def decode_attention_fwd(q: jnp.ndarray, k_cache: jnp.ndarray,
                         v_cache: jnp.ndarray, cache_len,
                         window: Optional[int] = None,
                         block_k: int = 512) -> jnp.ndarray:
    """q: (B, 1, H, hd); k_cache/v_cache: (B, S, KVH, hd);
    cache_len: scalar or (B,). Returns (B, 1, H, hd)."""
    B, S, KVH, hd = k_cache.shape
    H = q.shape[2]
    n_rep = H // KVH
    scale = 1.0 / math.sqrt(hd)
    block_k = min(block_k, S)
    while S % block_k:
        block_k //= 2
    nk = S // block_k

    clen = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (B,))
    # (B, KVH, n_rep, hd) — q head h belongs to kv group h // n_rep, so the
    # head group becomes the q-block row dimension
    qg = q[:, 0].reshape(B, KVH, n_rep, hd)
    kt = jnp.moveaxis(k_cache, 2, 1)                         # (B,KVH,S,hd)
    vt = jnp.moveaxis(v_cache, 2, 1)

    from repro.kernels import interpret_default
    kernel = functools.partial(_kernel, scale=scale, block_k=block_k,
                               window=window, nk=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KVH, nk),
        in_specs=[
            pl.BlockSpec((1, 1, n_rep, hd),
                         lambda b, g, ki, lens: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, g, ki, lens: (b, g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, g, ki, lens: (b, g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, n_rep, hd),
                               lambda b, g, ki, lens: (b, g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_rep, 1), jnp.float32),
            pltpu.VMEM((n_rep, 1), jnp.float32),
            pltpu.VMEM((n_rep, hd), jnp.float32),
        ],
    )
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, n_rep, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_default(),
        name="specee_decode_attention",
    )
    out = fn(clen, qg, kt, vt)                               # (B,KVH,n_rep,hd)
    out = out.reshape(B, KVH * n_rep, hd)
    return out[:, None].reshape(B, 1, H, hd)


# ---------------------------------------------------------------------------
# page-table-aware variant (paged KV cache — repro.api.cache.PagedKVCache)
# ---------------------------------------------------------------------------
def _paged_kernel(len_ref, tbl_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, page_size: int,
                  window: Optional[int], npg: int):
    # identical tile math to the dense kernel; the page-table indirection
    # happens in the K/V BlockSpec index maps, so k_start here is the
    # LOGICAL position of page pi (pi * page_size), not the physical one
    b = pl.program_id(0)
    pi = pl.program_id(2)
    _online_softmax_step(pi, len_ref[b], pi * page_size, q_ref, k_ref, v_ref,
                         o_ref, m_scr, l_scr, acc_scr, scale=scale,
                         block_k=page_size, window=window, nk=npg)


def _paged_kernel_q(len_ref, tbl_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                    o_ref, m_scr, l_scr, acc_scr, *, scale: float,
                    page_size: int, window: Optional[int], npg: int):
    # int8 pools (ModelFlags.kv_quant): same tile math, but the K/V pages
    # arrive as int8 codes + per-(position, head) scale pages gathered
    # through the SAME page-table index map — dequant runs in-register
    b = pl.program_id(0)
    pi = pl.program_id(2)
    _online_softmax_step(pi, len_ref[b], pi * page_size, q_ref, k_ref, v_ref,
                         o_ref, m_scr, l_scr, acc_scr, scale=scale,
                         block_k=page_size, window=window, nk=npg,
                         ks_ref=ks_ref, vs_ref=vs_ref)


def paged_decode_attention_fwd(q: jnp.ndarray, k_pool: jnp.ndarray,
                               v_pool: jnp.ndarray, page_table: jnp.ndarray,
                               cache_len, window: Optional[int] = None,
                               k_scale: Optional[jnp.ndarray] = None,
                               v_scale: Optional[jnp.ndarray] = None
                               ) -> jnp.ndarray:
    """Split-KV decode attention reading K/V through a page table.

    q: (B, 1, H, hd); k_pool/v_pool: (n_pages, page_size, KVH, hd) — the
    shared physical pool; page_table: (B, P) int32 logical→physical page map;
    cache_len: scalar or (B,) valid logical length per row.

    ``k_scale``/``v_scale``: optional (n_pages, page_size, KVH) fp32 dequant
    scale pools for int8 K/V pools (``ModelFlags.kv_quant``). Scale pages
    ride the SAME page-table index map as their value pages, so the kernel
    reads ~4× fewer K/V bytes per page and the dequantized math is
    bit-identical to dequantizing the gathered logical view (per-position
    scales commute with the gather).

    The page table is scalar-prefetched and consumed by the K/V BlockSpec
    index maps, so each grid step DMAs exactly one physical page — the
    (B, S, ...) logical view is never materialized. Pages past a row's valid
    prefix skip both compute (`pl.when`) AND traffic: their index map clamps
    to the last live page, and Pallas elides the DMA when the block index is
    unchanged between grid steps — this is what makes per-row compaction
    (freed pages, zeroed length) a real HBM-bytes win, not just masked
    compute.
    """
    n_pages, ps, KVH, hd = k_pool.shape
    B, _, H, _ = q.shape
    n_rep = H // KVH
    P = page_table.shape[1]
    scale = 1.0 / math.sqrt(hd)
    quantized = k_scale is not None

    clen = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (B,))
    tbl = jnp.asarray(page_table, jnp.int32)
    qg = q[:, 0].reshape(B, KVH, n_rep, hd)
    kt = jnp.moveaxis(k_pool, 2, 1)                          # (NP,KVH,ps,hd)
    vt = jnp.moveaxis(v_pool, 2, 1)

    from repro.kernels import interpret_default

    def kv_page(b, g, pi, lens, tbl):
        # pages beyond the valid prefix are dead (pl.when masks compute);
        # clamp them to the last live page so consecutive grid steps keep
        # the same block index and Pallas elides the DMA entirely
        last_live = jnp.maximum((lens[b] + ps - 1) // ps - 1, 0)
        return (tbl[b, jnp.minimum(pi, last_live)], g, 0, 0)

    q_spec = pl.BlockSpec((1, 1, n_rep, hd),
                          lambda b, g, pi, lens, tbl: (b, g, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, ps, hd), kv_page)
    if quantized:
        kernel = functools.partial(_paged_kernel_q, scale=scale,
                                   page_size=ps, window=window, npg=P)
        # (NP, ps, KVH) -> (NP, KVH, ps, 1): scale pages under the value
        # pages' index map, broadcasting over hd inside the tile
        kst = jnp.moveaxis(k_scale, 2, 1).reshape(n_pages, KVH, ps, 1)
        vst = jnp.moveaxis(v_scale, 2, 1).reshape(n_pages, KVH, ps, 1)
        s_spec = pl.BlockSpec((1, 1, ps, 1), kv_page)
        in_specs = [q_spec, kv_spec, s_spec, kv_spec, s_spec]
        operands = (clen, tbl, qg, kt, kst, vt, vst)
        name = "specee_paged_decode_attention_q8"
    else:
        kernel = functools.partial(_paged_kernel, scale=scale, page_size=ps,
                                   window=window, npg=P)
        in_specs = [q_spec, kv_spec, kv_spec]
        operands = (clen, tbl, qg, kt, vt)
        name = "specee_paged_decode_attention"

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KVH, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, n_rep, hd),
                               lambda b, g, pi, lens, tbl: (b, g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_rep, 1), jnp.float32),
            pltpu.VMEM((n_rep, 1), jnp.float32),
            pltpu.VMEM((n_rep, hd), jnp.float32),
        ],
    )
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, n_rep, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_default(),
        name=name,
    )
    out = fn(*operands)
    out = out.reshape(B, KVH * n_rep, hd)
    return out[:, None].reshape(B, 1, H, hd)
