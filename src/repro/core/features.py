"""T1 — speculation features (paper §4.3.1).

Three features per speculative token, k=4 tokens -> 12-dim input:
  (1) speculative token logits  — h·lm_head[:, spec_ids], the (1×D)·(D×k) GEMM
  (2) local probabilities       — softmax over the k logits
  (3) probability variation     — local probs minus previous layer's

The (D×k) gather-GEMM is the hot spot the paper's custom operator targets; the
Pallas TPU version lives in ``repro.kernels.spec_head`` and is selected with
``use_kernel=True`` (identical numerics, fused gather+GEMM+softmax+Δ).

The AR decode engine no longer stops at the features: with
``ModelFlags.exit_gate_kernel`` the whole feature→predictor→verify chain runs
through ``repro.kernels.exit_gate`` in one fused pipeline. This module stays
the feature-level building block for the tree path (whose hyper-token merge
sits between features and predictor) and for predictor training.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.common import Params, lm_head_weight


def spec_logits_ref(hn: jnp.ndarray, lm_head,
                    spec_ids: jnp.ndarray) -> jnp.ndarray:
    """hn: (B, D) final-normed hidden; lm_head: (D, V) array or a quantized
    ``repro.quant.QTensor``; spec_ids: (B, k).

    Returns (B, k) fp32 logits — reference implementation of the speculative
    LM head (columns of the LM head gathered per row). For a quantized head
    the columns are gathered then dequantized — identical to dequantizing
    first because the scales are per-output-column.
    """
    if hasattr(lm_head, "bits"):                      # QTensor
        from repro.quant import take_columns
        cols = take_columns(lm_head, spec_ids)        # (D, B, k) fp32
    else:
        cols = jnp.take(lm_head, spec_ids, axis=1)    # (D, B, k)
    cols = jnp.moveaxis(cols, 1, 0)                   # (B, D, k)
    return jnp.einsum("bd,bdk->bk", hn.astype(jnp.float32),
                      cols.astype(jnp.float32))


def extract_features(hn: jnp.ndarray, lm_head,
                     spec_ids: jnp.ndarray, prev_probs: jnp.ndarray,
                     use_kernel: bool = False, shard=None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compute the 3k feature vector for one exit point.

    hn: (B, D) — final-normed hidden state of the current layer
    prev_probs: (B, k) — local probabilities at the previous exit point
    shard: optional ShardCtx for the spec-head kernel under a mesh
    Returns (features (B, 3k) fp32, local_probs (B, k) fp32).
    """
    if use_kernel:
        from repro.kernels.spec_head import ops as sh_ops
        logits, probs = sh_ops.spec_head(hn, lm_head, spec_ids, shard=shard)
    else:
        logits = spec_logits_ref(hn, lm_head, spec_ids)
        probs = jax.nn.softmax(logits, axis=-1)
    variation = probs - prev_probs
    feats = jnp.concatenate([logits, probs, variation], axis=-1)
    return feats, probs


def merge_path_features(node_feats: jnp.ndarray, node_probs: jnp.ndarray,
                        path_nodes: jnp.ndarray, path_len: jnp.ndarray
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """T3 — hyper-token feature merge (paper §6.2, Cannikin law).

    node_feats: (B, N, 3k) per-node features; node_probs: (B, N, k);
    path_nodes: (P, Dmax) int32 node indices per path (-1 padded);
    path_len:   (P,) int32.

    A path exits only when its *weakest* node would exit, so the merged
    feature is the elementwise minimum over the path's nodes — one predictor
    evaluation per path (linear in #paths instead of exponential per-node
    mapping). Returns (path_feats (B, P, 3k), path_probs (B, P, k)).
    """
    P, Dmax = path_nodes.shape
    safe = jnp.maximum(path_nodes, 0)                          # (P, Dmax)
    gathered = node_feats[:, safe, :]                          # (B, P, Dmax, 3k)
    gp = node_probs[:, safe, :]                                # (B, P, Dmax, k)
    valid = (path_nodes >= 0)[None, :, :, None]
    big = jnp.float32(1e30)
    merged = jnp.min(jnp.where(valid, gathered, big), axis=2)
    merged_p = jnp.min(jnp.where(valid, gp, big), axis=2)
    return merged, merged_p
