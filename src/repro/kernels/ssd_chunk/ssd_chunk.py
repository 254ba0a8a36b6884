"""Pallas TPU kernel: Mamba2 SSD intra-chunk term.

The SSD "diagonal block" is decay-masked attention: scores = (C·Bᵀ) ⊙
exp(cum_t − cum_s) under a causal mask, applied to dt-weighted inputs. The
C·Bᵀ Gram matrix is head-INDEPENDENT (single B/C group in mamba2-130m), so
the kernel computes it once per (batch, chunk) grid cell and sweeps heads in
the innermost grid dim, reusing the (c × c) score skeleton from VMEM — the
TPU-shaped equivalent of mamba2's fused CUDA chunk kernel.

Grid: (B·nc, nh). Per cell: Cc,Bc (c, ds) + cum (c, 1) + xdt (c, hd) tiles.
c = 64, ds = 128, hd = 64 ⇒ ~200 KB VMEM — trivially resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(c_ref, b_ref, cum_ref, x_ref, o_ref, *, chunk: int):
    Cc = c_ref[0].astype(jnp.float32)                  # (c, ds)
    Bc = b_ref[0].astype(jnp.float32)                  # (c, ds)
    cum = cum_ref[0, 0].astype(jnp.float32)            # (c, 1)
    x = x_ref[0, 0].astype(jnp.float32)                # (c, hd)
    cb = jnp.dot(Cc, Bc.T, preferred_element_type=jnp.float32)   # (c, c)
    rel = cum - cum.T                                  # (c, c): cum_t - cum_s
    qpos = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    w = jnp.where(kpos <= qpos, cb * jnp.exp(rel), 0.0)
    o_ref[0, 0] = jnp.dot(w, x, preferred_element_type=jnp.float32)


def ssd_chunk_fwd(xdt: jnp.ndarray, cum: jnp.ndarray, Bc: jnp.ndarray,
                  Cc: jnp.ndarray) -> jnp.ndarray:
    """xdt: (B, c, nh, hd); cum: (B, c, nh); Bc/Cc: (B, c, ds).
    Returns y_diag (B, c, nh, hd) fp32."""
    B, c, nh, hd = xdt.shape
    ds = Bc.shape[-1]
    # (B, c, nh, hd) -> (B, nh, c, hd) blocks keyed by (b, h)
    xt = jnp.moveaxis(xdt, 2, 1)                       # (B, nh, c, hd)
    cumt = jnp.moveaxis(cum, 2, 1)[..., None]          # (B, nh, c, 1)

    from repro.kernels import interpret_default
    fn = pl.pallas_call(
        functools.partial(_kernel, chunk=c),
        grid=(B, nh),
        in_specs=[
            pl.BlockSpec((1, c, ds), lambda b, h: (b, 0, 0)),
            pl.BlockSpec((1, c, ds), lambda b, h: (b, 0, 0)),
            pl.BlockSpec((1, 1, c, 1), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, c, hd), lambda b, h: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, c, hd), lambda b, h: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nh, c, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_default(),
        name="ssd_chunk_diag",
    )
    out = fn(Cc, Bc, cumt, xt)                         # (B, nh, c, hd)
    return jnp.moveaxis(out, 1, 2)
