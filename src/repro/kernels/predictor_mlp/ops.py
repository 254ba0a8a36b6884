"""Public jit'd wrapper for the fused predictor MLP."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.predictor_mlp.predictor_mlp import (predictor_mlp_fused,
                                                       predictor_mlp_fused_q)
from repro.quant import QTensor


def _run(x: jnp.ndarray, p) -> jnp.ndarray:
    l1, l2 = p["layers"]
    if isinstance(l1["w"], QTensor):
        return predictor_mlp_fused_q(x, l1["w"], l1["b"], l2["w"], l2["b"])
    return predictor_mlp_fused(x, l1["w"], l1["b"], l2["w"], l2["b"])


def _on(shard):
    # under a mesh the kernel runs whole on every device
    return _run if shard is None else shard.replicated(_run)


@partial(jax.jit, static_argnames=("shard",))
def predictor_mlp(x: jnp.ndarray, params, shard=None) -> jnp.ndarray:
    """x: (B, F); params: {"layers": [{w,b}, {w,b}]} (repro.core.predictor
    layout, 2-layer case; ``w`` leaves may be ``repro.quant.QTensor`` —
    dequant then fuses into the kernel tiles) -> (B,) exit probabilities.
    ``shard``: optional ShardCtx."""
    return _on(shard)(x, params)


@partial(jax.jit, static_argnames=("shard",))
def predictor_mlp_at(x: jnp.ndarray, stacked, ep: jnp.ndarray,
                     shard=None) -> jnp.ndarray:
    """Stacked-bank entry: dynamic-index predictor ``ep`` out of the
    (E, ...)-stacked bank and run the fused MLP, all inside one jit so the
    weight slice feeds the kernel without an HBM round-trip. Quantized
    banks (QTensor ``w`` leaves) index transparently — codes and scales
    both carry the leading (E,) dim.

    x: (B, F); stacked: bank with leading (E,) on every leaf."""
    p = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, ep, 0, False), stacked)
    return _on(shard)(x, p)
