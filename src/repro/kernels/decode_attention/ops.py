"""Public jit'd wrapper for split-KV decode attention."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.decode_attention.decode_attention import (
    decode_attention_fwd, paged_decode_attention_fwd)


@partial(jax.jit, static_argnames=("window", "block_k"))
def decode_attention_raw(q: jnp.ndarray, k_cache: jnp.ndarray,
                         v_cache: jnp.ndarray, cache_len,
                         window: Optional[int] = None,
                         block_k: int = 512) -> jnp.ndarray:
    return decode_attention_fwd(q, k_cache, v_cache, cache_len,
                                window=window, block_k=block_k)


def decode_attention(cfg, q, k_cache, v_cache, cache_len,
                     window: Optional[int] = None) -> jnp.ndarray:
    """Model-layer adapter (matches ``attention.attend_decode`` signature)."""
    return decode_attention_raw(q, k_cache, v_cache, cache_len, window=window)


@partial(jax.jit, static_argnames=("window",))
def paged_decode_attention_raw(q: jnp.ndarray, k_pool: jnp.ndarray,
                               v_pool: jnp.ndarray, page_table: jnp.ndarray,
                               cache_len, window: Optional[int] = None,
                               k_scale: Optional[jnp.ndarray] = None,
                               v_scale: Optional[jnp.ndarray] = None
                               ) -> jnp.ndarray:
    return paged_decode_attention_fwd(q, k_pool, v_pool, page_table,
                                      cache_len, window=window,
                                      k_scale=k_scale, v_scale=v_scale)


def paged_decode_attention(cfg, q, k_pool, v_pool, page_table, cache_len,
                           window: Optional[int] = None,
                           k_scale: Optional[jnp.ndarray] = None,
                           v_scale: Optional[jnp.ndarray] = None,
                           shard=None) -> jnp.ndarray:
    """Model-layer adapter: page-table-aware gather variant consumed by the
    paged decode path (``model._block_step`` under ``flags.decode_kernel``).
    ``k_scale``/``v_scale`` carry the int8 dequant scale pools under
    ``flags.kv_quant`` (same page-table gather as the value pools).

    ``shard``: optional ShardCtx. A Pallas kernel cannot be partitioned by
    GSPMD, so under a mesh it runs in a ``shard_map``: per KV-head shard
    when the pools split their head dim over the mesh (``core.paged.
    pool_partition_dims`` — each device attends its own heads, no
    collective), otherwise whole on every device."""
    kernel = partial(paged_decode_attention_raw, window=window)
    if shard is None:
        return kernel(q, k_pool, v_pool, page_table, cache_len,
                      k_scale=k_scale, v_scale=v_scale)
    heads = k_pool.shape[2] % shard.degree == 0
    ax = shard.axis if heads else None
    head_spec = P(None, None, ax, None)     # q (B,1,H,hd), pools (…,KVH,hd)
    scale_spec = P(None, None, ax)              # (n_pages, page_size, KVH)
    scales = () if k_scale is None else (k_scale, v_scale)
    fn = jax.shard_map(
        lambda q, k, v, tbl, lens, *sc: kernel(
            q, k, v, tbl, lens, **dict(zip(("k_scale", "v_scale"), sc))),
        mesh=shard.mesh,
        in_specs=(head_spec,) * 3 + (P(), P()) + (scale_spec,) * len(scales),
        out_specs=head_spec, check_vma=False)
    return fn(q, k_pool, v_pool, page_table,
              jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32),
                               (q.shape[0],)), *scales)
