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


def _online_softmax_step(ki, clen, k_start, load, o_ref, m_scr, l_scr,
                         acc_scr, *, scale: float, positions: int,
                         kv_heads: int, n_rep: int, window: Optional[int],
                         nk: int):
    """Shared flash-decoding tile body for the dense and paged kernels.

    The two kernels differ ONLY in how a grid step locates its K/V block
    (sequential block index vs page-table indirection) and in how many KV
    heads one block holds — every numerics decision (masking, NEG_INF,
    online-softmax accumulation, the l == 0 guard for fully-masked rows)
    lives here exactly once. ``k_start`` is the LOGICAL position of the
    block's first key; the block holds ``positions`` of them.

    ``load()`` returns the tile as ``(q, k, v, k_scale, v_scale)``: q
    (R, hd), k and v (N, hd) with N = positions · kv_heads, key row c
    holding position k_start + c // kv_heads of KV head c % kv_heads, and
    query row r belonging to KV head r // n_rep. A query row attends only
    the key rows of its own head: the others are masked like positions past
    the length, so their softmax weights are exactly 0 and one pair of
    matmuls serves every head of the block. ``k_scale``/``v_scale`` are
    None or (1, N) int8 dequant scales (``ModelFlags.kv_quant`` pools): a
    per-key scale multiplies that key's logit column, and a per-value
    scale that value's softmax weight, which is the dequantized tile's
    arithmetic with the scale moved across the dot.
    """
    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = k_start < clen
    if window is not None:
        live = live & (k_start + positions > clen - window)

    def _body():
        q, k, v, k_scale, v_scale = load()
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if k_scale is not None:
            s = s * k_scale
        s = s * scale
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        kpos = k_start + (col // kv_heads if kv_heads > 1 else col)
        valid = kpos < clen
        if window is not None:
            valid = valid & (kpos >= clen - window)
        if kv_heads > 1:
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            valid = valid & (col % kv_heads == row // n_rep)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        if v_scale is not None:
            p = p * v_scale
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    pl.when(live)(_body)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l).reshape(o_ref.shape).astype(
            o_ref.dtype)


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_k: int, window: Optional[int], nk: int):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    def load():
        return (q_ref[0, 0].astype(jnp.float32),            # (n_rep, hd)
                k_ref[0, 0].astype(jnp.float32),            # (Bk, hd)
                v_ref[0, 0].astype(jnp.float32), None, None)

    _online_softmax_step(ki, len_ref[b], ki * block_k, load, o_ref, m_scr,
                         l_scr, acc_scr, scale=scale, positions=block_k,
                         kv_heads=1, n_rep=q_ref.shape[2], window=window,
                         nk=nk)


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
# Scoped VMEM a kernel gets by default on a TPU v5e TensorCore: the most a
# paged step's working set (see _head_groups) may take.
_VMEM_BYTES = 16 * 2**20


def _sublanes(dtype) -> int:
    """Rows of one native VMEM tile for ``dtype``: 8 at 32 bits, 16 at 16,
    32 at 8 (a block's second-minor dim is a multiple of it or whole)."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _head_groups(ps: int, kv_heads: int, n_rep: int, hd: int, pool_dtype,
                 q_dtype, quantized: bool) -> int:
    """How many groups the KV heads are split into, one grid step each: the
    fewest whose step fits ``_VMEM_BYTES``. A step holds a page of the
    group's keys and values twice over (the pipeline's two buffers, plus
    scale pages under ``kv_quant``), the values in float32 and the
    (queries × keys) logits. On the v5e compiler this count put the limit
    where it lies: steps of 14.7 MB compile, steps of 21 MB and more are
    refused for VMEM. A group must tile as a block: its KV heads (and query
    heads) a multiple of the native sublane count, and under ``kv_quant``,
    where the scale page's heads lie on lanes, all heads or 128 of them."""
    item = jnp.dtype(pool_dtype).itemsize

    def step_bytes(kvh):
        n = ps * kvh
        pages = 2 * 2 * n * (hd * item + (4 if quantized else 0))
        return pages + n * hd * 4 + kvh * n_rep * n * 4

    def tiles(kvh):
        return (kvh % _sublanes(pool_dtype) == 0
                and kvh * n_rep % _sublanes(q_dtype) == 0
                and (not quantized or kvh % 128 == 0))

    legal = [g for g in range(1, kv_heads + 1) if kv_heads % g == 0
             and (g == 1 or tiles(kv_heads // g))]
    fits = [g for g in legal if step_bytes(kv_heads // g) <= _VMEM_BYTES]
    return fits[0] if fits else legal[-1]


def _paged_kernel(len_ref, tbl_ref, q_ref, k_ref, v_ref, *refs,
                  scale: float, window: Optional[int], n_rep: int, npg: int):
    # the page-table indirection happens in the K/V BlockSpec index maps,
    # so k_start here is the LOGICAL position of page pi (pi * page size),
    # not the physical one. Under kv_quant two scale refs come first.
    *scale_refs, o_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    pi = pl.program_id(2)
    _, ps, kvh, hd = k_ref.shape
    n = ps * kvh

    def load():
        # (ps, kvh, hd) page -> (ps·kvh, hd) key rows, position-major
        q = q_ref[0]
        k = k_ref[0].reshape(n, hd)
        v = v_ref[0].reshape(n, hd).astype(jnp.float32)
        if not scale_refs:
            dt = jnp.promote_types(q.dtype, k.dtype)
            return q.astype(dt), k.astype(dt), v, None, None
        ks_ref, vs_ref = scale_refs
        return (q, k.astype(q.dtype), v, ks_ref[0].reshape(1, n),
                vs_ref[0].reshape(1, n))

    _online_softmax_step(pi, len_ref[b], pi * ps, load, o_ref, m_scr, l_scr,
                         acc_scr, scale=scale, positions=ps, kv_heads=kvh,
                         n_rep=n_rep, window=window, nk=npg)


def paged_decode_attention_fwd(q: jnp.ndarray, k_pool: jnp.ndarray,
                               v_pool: jnp.ndarray, page_table: jnp.ndarray,
                               cache_len, window: Optional[int] = None,
                               k_scale: Optional[jnp.ndarray] = None,
                               v_scale: Optional[jnp.ndarray] = None
                               ) -> jnp.ndarray:
    """Split-KV decode attention reading K/V through a page table.

    q: (B, 1, H, hd); k_pool/v_pool: (n_pages, page_size, KVH, hd) — the
    shared physical pool, read in this stored layout (no copy of it is
    made); page_table: (B, P) int32 logical→physical page map; cache_len:
    scalar or (B,) valid logical length per row.

    ``k_scale``/``v_scale``: optional (n_pages, page_size, KVH) fp32 dequant
    scale pools for int8 K/V pools (``ModelFlags.kv_quant``). Scale pages
    ride the SAME page-table index map as their value pages, so the kernel
    reads ~4× fewer K/V bytes per page; per-position scales commute with
    the page gather, so this is the dequantized logical view's attention.

    The grid is (B, G, P): row, KV-head group, logical page. One step DMAs
    one physical page of the group's heads, a (1, page_size, KVH / G, hd)
    block, and attends every query head of the group to it at once
    (``_online_softmax_step``). G is the fewest groups whose step fits the
    chip's VMEM (``_head_groups``, from the shapes and dtypes alone): 1 at
    every served width, so a call takes B·P steps. The page table is
    scalar-prefetched and consumed by the K/V BlockSpec index maps — the
    (B, S, ...) logical view is never materialized. Pages past a row's
    valid prefix skip both compute (`pl.when`) AND traffic: their index map
    clamps to the last live page, and Pallas elides the DMA when the block
    index is unchanged between grid steps — this is what makes per-row
    compaction (freed pages, zeroed length) a real HBM-bytes win, not just
    masked compute.
    """
    _, ps, KVH, hd = k_pool.shape
    B, _, H, _ = q.shape
    n_rep = H // KVH
    P = page_table.shape[1]
    quantized = k_scale is not None
    G = _head_groups(ps, KVH, n_rep, hd, k_pool.dtype, q.dtype, quantized)
    kvh = KVH // G

    clen = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (B,))
    tbl = jnp.asarray(page_table, jnp.int32)
    qg = q.reshape(B, H, hd)

    from repro.kernels import interpret_default

    def page(b, g, pi, lens, tbl):
        # pages beyond the valid prefix are dead (pl.when masks compute);
        # clamp them to the last live page so consecutive grid steps keep
        # the same block index and Pallas elides the DMA entirely
        last_live = jnp.maximum((lens[b] + ps - 1) // ps - 1, 0)
        return tbl[b, jnp.minimum(pi, last_live)]

    def row(b, g, pi, lens, tbl):
        return (b, g, 0)

    kv_spec = pl.BlockSpec((1, ps, kvh, hd), lambda *a: (page(*a), 0, a[1], 0))
    in_specs = [pl.BlockSpec((1, kvh * n_rep, hd), row), kv_spec, kv_spec]
    operands = (clen, tbl, qg, k_pool, v_pool)
    name = "specee_paged_decode_attention"
    if quantized:
        s_spec = pl.BlockSpec((1, ps, kvh), lambda *a: (page(*a), 0, a[1]))
        in_specs += [s_spec, s_spec]
        operands += (k_scale, v_scale)
        name += "_q8"

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, G, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kvh * n_rep, hd), row),
        scratch_shapes=[
            pltpu.VMEM((kvh * n_rep, 1), jnp.float32),
            pltpu.VMEM((kvh * n_rep, 1), jnp.float32),
            pltpu.VMEM((kvh * n_rep, hd), jnp.float32),
        ],
    )
    fn = pl.pallas_call(
        functools.partial(_paged_kernel, scale=1.0 / math.sqrt(hd),
                          window=window, n_rep=n_rep, npg=P),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_default(),
        name=name,
    )
    return fn(*operands).reshape(B, 1, H, hd)
