"""Pallas TPU kernel: blocked flash attention (prefill path).

Online-softmax formulation: grid (B, H, nQ, nK) with the KV dimension
innermost ("arbitrary" semantics); running max / denominator / weighted
accumulator live in VMEM scratch carried across KV steps. Causality is
exploited twice:
  * whole KV blocks strictly above the diagonal are skipped via ``pl.when``
    (no MXU work, no VMEM traffic) — the scheduler still iterates the grid
    but the body is predicated off;
  * the diagonal block applies the elementwise triangular mask.
GQA maps query head h to KV head h // (H // KVH) inside the BlockSpec
index_map — KV blocks are fetched once per group, not per query head.
Sliding-window (local) attention masks out-of-window keys and skips blocks
entirely below the window.

VMEM budget per step: q (Bq×hd) + k,v (Bk×hd each) + scratch (Bq×hd + 2·Bq)
fp32 ≈ 4·128·128·4 B ≈ 256 KB at the default 128/128 tiling — comfortably
inside the ~16 MB v5e VMEM with double buffering.
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


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_q: int, block_k: int, causal: bool,
            window: Optional[int], nk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    def _body():
        q = q_ref[0, 0].astype(jnp.float32)                 # (Bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                 # (Bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)                 # (Bk, hd)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            mask = kpos <= qpos
            if window is not None:
                mask = mask & (kpos > qpos - window)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                                 # (Bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    if causal:
        # block-level skip: the block is live iff some (q, k) pair with
        # k <= q (and q - k < window) exists — dead blocks cost nothing
        live = k_start <= q_start + block_q - 1
        if window is not None:
            live = live & (k_start + block_k - 1 > q_start - window)
        pl.when(live)(_body)
    else:
        _body()

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)                     # fully-masked rows
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True, window: Optional[int] = None,
                        block_q: int = 128, block_k: int = 128) -> jnp.ndarray:
    """q: (B, S, H, hd); k, v: (B, S, KVH, hd) -> (B, S, H, hd)."""
    B, S, H, hd = q.shape
    KVH = k.shape[2]
    n_rep = H // KVH
    scale = 1.0 / math.sqrt(hd)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    while S % block_q:
        block_q //= 2
    while S % block_k:
        block_k //= 2
    nq, nk = S // block_q, S // block_k

    # layout: (B, H, S, hd) blocks of (1, 1, block, hd)
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)

    from repro.kernels import interpret_default
    kernel = functools.partial(_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, causal=causal, window=window,
                               nk=nk)
    fn = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, qi, ki: (b, h // n_rep, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, qi, ki: (b, h // n_rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret_default(),
        name="specee_flash_attention",
    )
    out = fn(qt, kt, vt)
    return jnp.moveaxis(out, 1, 2)
