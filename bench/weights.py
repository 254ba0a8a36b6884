"""Seeded weights in a plain layout, shared by the program's adapter and the
reference (neither takes the other's arrays).

Every leaf has a key of its own, folded from the seed, the layer and the
leaf's index, and is drawn in float32 and rounded once to the served dtype.
So one layer drawn alone is bit for bit the layer of the whole stack, and the
reference can rebuild the weights a layer at a time after the program's
state is freed.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
NORM_NOISE = 0.05       # norm scales are 1 + NORM_NOISE * N(0, 1)
BIAS_STD = 0.02


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


class Arch:
    """The sizes and kinds of one configuration file, read once."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.d = cfg["hidden_size"]
        self.f = cfg["intermediate_size"]
        self.heads = cfg["num_attention_heads"]
        self.kv_heads = cfg["num_key_value_heads"]
        self.hd = cfg.get("head_dim", self.d // self.heads)
        self.layers = cfg["num_hidden_layers"]
        self.vocab = cfg["vocab_size"]
        self.layernorm = cfg.get("norm_type") == "layer_norm"
        self.eps = cfg.get("norm_epsilon", cfg.get("rms_norm_eps"))
        self.bias = bool(cfg.get("use_bias", False))
        self.gated = cfg["architecture"] == "llama"   # SwiGLU MLP
        self.act = cfg["hidden_act"]
        self.theta = float(cfg["rope_theta"])
        self.window = cfg.get("sliding_window")
        self.std = cfg["initializer_range"]
        self.dtype = jnp.dtype(cfg["torch_dtype"])

    def layer_leaves(self) -> List[Tuple[str, tuple, str]]:
        """(name, shape, kind) of one layer's leaves, in key order. kind is
        "w" (a projection), "b" (a bias) or "s" (a norm scale)."""
        d, f, hq, hk = (self.d, self.f, self.heads * self.hd,
                        self.kv_heads * self.hd)
        out = [("ln1_scale", (d,), "s")]
        if self.layernorm:
            out.append(("ln1_bias", (d,), "b"))
        out += [("wq", (d, hq), "w"), ("wk", (d, hk), "w"),
                ("wv", (d, hk), "w"), ("wo", (hq, d), "w")]
        if self.bias:
            out += [("bq", (hq,), "b"), ("bk", (hk,), "b"),
                    ("bv", (hk,), "b"), ("bo", (d,), "b")]
        out.append(("ln2_scale", (d,), "s"))
        if self.layernorm:
            out.append(("ln2_bias", (d,), "b"))
        out.append(("w_up", (d, f), "w"))
        if self.gated:
            out.append(("w_gate", (d, f), "w"))
        out.append(("w_down", (f, d), "w"))
        if self.bias:
            out += [("b_up", (f,), "b"), ("b_down", (d,), "b")]
        return out

    def outer_leaves(self) -> List[Tuple[str, tuple, str]]:
        out = [("embed", (self.vocab, self.d), "w"),
               ("lm_head", (self.d, self.vocab), "w"),
               ("final_scale", (self.d,), "s")]
        if self.layernorm:
            out.append(("final_bias", (self.d,), "b"))
        return out


def root_key(seed: int):
    """A key from any whole number up to 64 bits (PRNGKey takes 32)."""
    seed = int(seed)
    lo = np.uint32(seed & 0xFFFFFFFF)
    hi = np.uint32((seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), hi),
                              lo)


def _leaf(arch: Arch, key, shape, kind):
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "w":
        x = arch.std * x
    elif kind == "b":
        x = BIAS_STD * x
    else:
        x = 1.0 + NORM_NOISE * x
    return x.astype(arch.dtype)


def _draw(arch: Arch, key, leaves) -> Dict[str, jnp.ndarray]:
    return {name: _leaf(arch, jax.random.fold_in(key, i), shape, kind)
            for i, (name, shape, kind) in enumerate(leaves)}


def layer_key(seed_key, layer):
    return jax.random.fold_in(jax.random.fold_in(seed_key, 1), layer)


def draw_layer(arch: Arch, seed_key, layer) -> Dict[str, jnp.ndarray]:
    """One layer's leaves (to be called under jit)."""
    return _draw(arch, layer_key(seed_key, layer), arch.layer_leaves())


def draw_outer(arch: Arch, seed_key) -> Dict[str, jnp.ndarray]:
    """Embedding, LM head and final norm (to be called under jit)."""
    return _draw(arch, jax.random.fold_in(seed_key, 0), arch.outer_leaves())


def draw_stack(arch: Arch, seed_key) -> Dict[str, jnp.ndarray]:
    """Every layer's leaves stacked on a leading layer axis."""
    return jax.vmap(lambda l: draw_layer(arch, seed_key, l))(
        jnp.arange(arch.layers))
