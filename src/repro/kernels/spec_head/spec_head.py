"""Pallas TPU kernel: fused speculative-LM-head (paper §6.2, TPU-adapted).

The paper computes speculative token logits with a cutlass/MegaBlocks group
GEMM over LM-head *columns* selected by the draft's token ids. Here XLA
gathers those k columns of every row into a ``(k, B, D)`` strip outside the
kernel (``column_strip``) — HBM traffic is exactly k columns per row, the
k·D weights instead of the V·D a full-head matmul would read — and the
kernel reduces the strip against the hidden over D tiles.

All B rows are blocked together. Mosaic requires each block's last two dims
to be multiples of (8, 128) or the array's own extent, so a per-row
``(1, Dt)`` hidden block, a ``(Dt, 1)`` column block or a ``(1, 1)`` output
block cannot compile at real B; the ``(B, Dt)`` hidden tile, ``(k, B, Dt)``
strip tile and whole ``(B, k)`` output can.

Grid: (D/Dt,), Dt = 128-aligned reduction tile ("arbitrary" semantics: the
(B, k) output block stays resident and accumulates in fp32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def fit_block(dim: int, block: int) -> int:
    """Largest power-of-two fraction of ``block`` that divides ``dim``."""
    block = min(block, dim)
    while dim % block:
        block //= 2
    return block


def column_strip(lm_head, spec_ids: jnp.ndarray) -> jnp.ndarray:
    """The LM-head columns named by ``spec_ids`` (B, k) as a (k, B, D) strip.

    A ``repro.quant.QTensor`` head gathers its integer codes and
    per-column scales and dequantizes just the strip (``take_columns``):
    k·B·D values, exactly what the reference gathers.
    """
    if hasattr(lm_head, "bits"):
        from repro.quant import take_columns
        cols = take_columns(lm_head, spec_ids)              # (D, B, k) fp32
    else:
        cols = jnp.take(lm_head, spec_ids, axis=1)          # (D, B, k)
    return jnp.transpose(cols, (2, 1, 0))


def strip_specs(B: int, k: int, D: int, block_d: int):
    """(nd, [hidden spec, strip spec]) for a grid over nd D tiles."""
    block_d = fit_block(D, block_d)
    return D // block_d, [
        pl.BlockSpec((B, block_d), lambda d: (0, d)),
        pl.BlockSpec((k, B, block_d), lambda d: (0, 0, d)),
    ]


def accumulate_strip(h_ref, c_ref, acc_ref) -> None:
    """acc (B, k) += this D tile's per-row dot with each strip column."""
    k = c_ref.shape[0]
    h = h_ref[...].astype(jnp.float32)                       # (B, Dt)
    lane = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 1)
    part = jnp.zeros(acc_ref.shape, jnp.float32)
    for j in range(k):                                       # static, k tiny
        c = c_ref[j].astype(jnp.float32)                     # (B, Dt)
        part = jnp.where(lane == j,
                         jnp.sum(h * c, axis=1, keepdims=True), part)
    acc_ref[...] += part


def _kernel(h_ref, c_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    accumulate_strip(h_ref, c_ref, out_ref)


def spec_head_logits(hn: jnp.ndarray, lm_head, spec_ids: jnp.ndarray,
                     block_d: int = 512, shard=None) -> jnp.ndarray:
    """hn: (B, D); lm_head: (D, V) array or ``repro.quant.QTensor``;
    spec_ids: (B, k) -> logits (B, k) fp32. ``shard``: optional ShardCtx —
    the gather runs under GSPMD, the kernel on every device."""
    call = functools.partial(_spec_head_call, block_d=block_d)
    if shard is not None:
        call = shard.replicated(call)
    return call(hn, column_strip(lm_head, spec_ids))


def _spec_head_call(hn, strip, *, block_d: int):
    B, D = hn.shape
    k = strip.shape[0]
    nd, in_specs = strip_specs(B, k, D, block_d)
    from repro.kernels import interpret_default
    fn = pl.pallas_call(
        _kernel,
        grid=(nd,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((B, k), lambda d: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret_default(),
        name="specee_spec_head",
    )
    return fn(hn, strip)
