"""T1 — the lightweight early-exit predictor (paper §4).

A 2-layer MLP (hidden 512, ReLU, sigmoid head, threshold 0.5) over the
12-dimensional speculation feature vector. Paper DSE (Fig. 8) fixes
(layers=2, hidden=512); both are configurable for the DSE benchmark.

One predictor per exit point, parameters stacked over exit points so the
decode loop can ``dynamic_index_in_dim`` into them. Total size for Llama2-7B
(32 predictors, k=4): (12·512 + 512 + 512·1 + 1) · 32 · 4B ≈ 416 KB — the
paper's §7.4.2 number (theirs omits biases: (12·512 + 512·1)·32·4 = 852 KB/2…
we assert the same order in tests).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.config import SpecEEConfig
from repro.models.common import KeyGen, Params, normal_init, zeros_init


def init_predictor(spec: SpecEEConfig, key) -> Params:
    """Single predictor MLP: feature_dim -> hidden^(layers-1) -> 1."""
    kg = KeyGen(key)
    dims = ([spec.feature_dim()] +
            [spec.predictor_hidden] * (spec.predictor_layers - 1) + [1])
    layers = []
    for i in range(len(dims) - 1):
        layers.append({
            "w": normal_init(kg(), (dims[i], dims[i + 1]),
                             1.0 / math.sqrt(dims[i])),
            "b": zeros_init((dims[i + 1],)),
        })
    return {"layers": layers}


def init_predictors(spec: SpecEEConfig, num_exit_points: int, key) -> Params:
    """Stacked predictors: every leaf gains a leading (num_exit_points,) dim."""
    keys = jax.random.split(key, num_exit_points)
    return jax.vmap(lambda k: init_predictor(spec, k))(keys)


def apply_predictor(p: Params, features: jnp.ndarray) -> jnp.ndarray:
    """features: (..., feature_dim) -> exit probability (...,) in [0, 1].

    Quantized banks (``repro.quant.QTensor`` weight leaves) are dequantized
    in place — this is the reference path the fused quantized MLP kernel is
    tested against.
    """
    x = features.astype(jnp.float32)
    layers = p["layers"]
    for i, layer in enumerate(layers):
        w = layer["w"]
        if hasattr(w, "dequantize"):
            w = w.dequantize()
        x = x @ w + layer["b"]
        if i + 1 < len(layers):
            x = jax.nn.relu(x)
    return jax.nn.sigmoid(x[..., 0])


def predictor_at(stacked: Params, idx: jnp.ndarray) -> Params:
    """Dynamic-index one predictor out of the stacked bank."""
    return jax.tree_util.tree_map(
        lambda x: jax.lax.dynamic_index_in_dim(x, idx, 0, False), stacked)


def apply_predictor_banked(stacked: Params, idx: jnp.ndarray,
                           features: jnp.ndarray,
                           use_kernel: bool = False, shard=None
                           ) -> jnp.ndarray:
    """Single entry point for a stacked-bank predictor evaluation.

    ``use_kernel=True`` routes the bank ``dynamic_index`` + the fused 2-layer
    Pallas MLP through one jit (``repro.kernels.predictor_mlp.ops``); falls
    back to the reference path for non-2-layer banks (DSE sweeps).
    ``shard``: optional ShardCtx for the kernel under a mesh.
    features: (..., feature_dim) -> exit probability (...,).
    """
    if use_kernel and len(stacked["layers"]) == 2:
        from repro.kernels.predictor_mlp.ops import predictor_mlp_at
        lead = features.shape[:-1]
        flat = features.reshape(-1, features.shape[-1])
        return predictor_mlp_at(flat, stacked, idx,
                                shard=shard).reshape(lead)
    return apply_predictor(predictor_at(stacked, idx), features)


def predictor_param_bytes(spec: SpecEEConfig, num_exit_points: int) -> int:
    dims = ([spec.feature_dim()] +
            [spec.predictor_hidden] * (spec.predictor_layers - 1) + [1])
    per = sum(dims[i] * dims[i + 1] + dims[i + 1] for i in range(len(dims) - 1))
    return per * num_exit_points * 4
