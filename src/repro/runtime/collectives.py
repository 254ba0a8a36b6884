"""Distributed-optimization primitives.

* int8 gradient compression with error feedback — for cross-pod (DCN-class)
  all-reduces where link bandwidth, not compute, bounds step time.
* overlapped collective matmul — all-gather-of-activations matmul where each
  ``ppermute`` hop overlaps with the partial GEMM of the shard already in
  hand (the "collective matmul" / Wang et al. decomposition). Used by the
  §Perf hillclimb as a beyond-paper optimization for TP layers.

Both are shard_map-level building blocks; GSPMD handles the default paths.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# int8 compression with error feedback
# ---------------------------------------------------------------------------
def quantize_int8(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-tensor int8 quantization. Returns (q, scale)."""
    amax = jnp.max(jnp.abs(x)) + 1e-12
    scale = amax / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def compressed_psum(x: jnp.ndarray, axis_name: str,
                    error: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Error-feedback int8 all-reduce: participants agree on a SHARED scale
    (one scalar pmax), quantize (x+error), reduce the int8 payload (4-8× less
    link traffic than fp32/bf16), and keep the per-participant quantization
    residual locally for the next step. The int8 sum × shared scale is an
    UNBIASED estimate of the fp32 sum (error ≤ P·scale/2 elementwise, feedback
    absorbs it across steps). Call inside shard_map.
    Returns (reduced fp32, new local error)."""
    target = x.astype(jnp.float32) + error
    gmax = jax.lax.pmax(jnp.max(jnp.abs(target)), axis_name) + 1e-12
    scale = gmax / 127.0
    q = jnp.clip(jnp.round(target / scale), -127, 127).astype(jnp.int8)
    new_error = target - q.astype(jnp.float32) * scale
    total = jax.lax.psum(q.astype(jnp.int32), axis_name)
    return total.astype(jnp.float32) * scale, new_error


# ---------------------------------------------------------------------------
# overlapped collective matmul (all-gather x GEMM pipelining)
# ---------------------------------------------------------------------------
def collective_matmul_ag(x_shard: jnp.ndarray, w: jnp.ndarray,
                         axis_name: str) -> jnp.ndarray:
    """Compute (all_gather(x) @ w) as a ppermute ring where each hop's
    transfer overlaps the GEMM on the shard already received.

    x_shard: (rows/P, K) local activation shard; w: (K, N) local weight
    (typically itself TP-sharded on N). Returns (rows, N) — the full product
    for this TP group, rows ordered by source rank.
    Called inside shard_map with ``axis_name`` a mesh axis of size P.
    """
    P_ = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    rows = x_shard.shape[0]

    def step(i, carry):
        buf, out = carry
        # GEMM on the shard in hand — XLA schedules the next permute's DMA
        # concurrently because there is no data dependence between them.
        part = jnp.dot(buf, w, preferred_element_type=jnp.float32)
        src = (idx - i) % P_  # which rank's rows we just multiplied
        out = jax.lax.dynamic_update_slice_in_dim(
            out, part.astype(out.dtype), src * rows, axis=0)
        buf = jax.lax.ppermute(
            buf, axis_name,
            perm=[(j, (j + 1) % P_) for j in range(P_)])
        return buf, out

    out0 = jnp.zeros((rows * P_, w.shape[1]), x_shard.dtype)
    # mark the accumulator as device-varying along the ring axis (shard_map
    # VMA typing: the carry is written with per-device data every hop)
    out0 = jax.lax.pcast(out0, (axis_name,), to="varying")
    buf, out = jax.lax.fori_loop(0, P_, step, (x_shard, out0))
    return out
