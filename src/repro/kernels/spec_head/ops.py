"""Public jit'd wrapper for the fused speculative LM head."""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.spec_head.spec_head import spec_head_logits


@partial(jax.jit, static_argnames=("block_d", "shard"))
def spec_head(hn: jnp.ndarray, lm_head, spec_ids: jnp.ndarray,
              block_d: int = 512, shard=None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused gather + k-GEMM + softmax.

    hn: (B, D) final-normed hidden; lm_head: (D, V) array or a
    ``repro.quant.QTensor`` (int8 / packed-int4 codes + per-column scales
    — only the gathered columns are dequantized); spec_ids: (B, k) int32;
    shard: optional ShardCtx (the kernel then runs on every device).
    Returns (logits (B, k) fp32, local_probs (B, k) fp32).
    """
    logits = spec_head_logits(hn, lm_head, spec_ids, block_d=block_d,
                              shard=shard)
    return logits, jax.nn.softmax(logits, axis=-1)
