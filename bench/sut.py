"""The system under test, as the benchmark drives it: the repository's
``ServingEngine`` built on weights the benchmark draws.

This is the only module of the benchmark that imports the program. It maps
a configuration file onto the program's ``RunConfig``, lays the seeded
plain weights (``bench.weights``) into the program's parameter tree, builds
the SpecEE weights that pin the exits, and makes the engine.

Pinned exits. Random weights give exit decisions that are noise, so the
SpecEE weights are built to give a stated per-token distribution:

* the draft is a copy of the target's first layer, fed the token's
  embedding alone (its fuse projection is [I; 0]). Its hidden state is the
  target's after one layer, so its top-k speculative set holds the argmax
  of the LM head at the first exit point, and verification there passes;
* the predictor at the first exit point fires when the top speculative
  token's local probability (softmax over the k speculative logits) exceeds
  a threshold c; every other predictor never fires;
* the offline schedule keeps the first exit point active.

Given its hidden state, a position's logits across the vocabulary are
independent normal draws (the LM head's entries are), so the local
probability of the top of k such draws has a distribution that depends on
the vocabulary size and the logit scale alone. c is its (1 - share)
quantile, found by Monte Carlo from a fixed key. A token then exits after
one layer with probability ``share`` and otherwise runs the full depth.
"""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import Arch, draw_outer, draw_stack, root_key

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(CHECKOUT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(CHECKOUT, "src"))

from repro.config import (FAMILY_DENSE, ModelConfig, RunConfig,  # noqa: E402
                          ServeConfig)
from repro.core import engine as eng  # noqa: E402
from repro.models.model import build_model  # noqa: E402

MC_SAMPLES = 4096
GATE_SLOPE = 64.0       # predictor logit per unit of local probability
NEVER = -16.0           # predictor logit of the exit points that never fire


def run_config(arch: Arch) -> RunConfig:
    cfg = arch.cfg
    model = ModelConfig(
        name=cfg["name"], family=FAMILY_DENSE, num_layers=arch.layers,
        d_model=arch.d, num_heads=arch.heads, num_kv_heads=arch.kv_heads,
        d_ff=arch.f, vocab_size=arch.vocab, head_dim=arch.hd,
        use_bias=arch.bias,
        norm="layernorm" if arch.layernorm else "rmsnorm",
        activation="silu" if arch.act == "silu" else "gelu",
        gated_mlp=arch.gated, rope_theta=arch.theta,
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        max_seq_len=cfg["serving"]["max_seq_len"],
        dtype=cfg["torch_dtype"])
    s = cfg["serving"]
    serve = ServeConfig(max_batch=s["max_batch"], max_seq_len=s["max_seq_len"],
                        page_size=s["page_size"],
                        prefill_chunk=s["prefill_chunk"])
    return RunConfig(model=model, serve=serve)


def _lin(w, b=None):
    return {"w": w} if b is None else {"w": w, "b": b}


def _norm(scale, bias=None):
    return {"scale": scale} if bias is None else {"scale": scale,
                                                  "bias": bias}


def _unit(arch: Arch, w):
    """One decoder layer's leaves (or a stack of them) in the program's
    unit layout."""
    g = w.get
    unit = {
        "ln1": _norm(w["ln1_scale"], g("ln1_bias")),
        "attn": {"wq": _lin(w["wq"], g("bq")), "wk": _lin(w["wk"], g("bk")),
                 "wv": _lin(w["wv"], g("bv")), "wo": _lin(w["wo"], g("bo"))},
        "ln2": _norm(w["ln2_scale"], g("ln2_bias")),
        "mlp": {"wi": _lin(w["w_up"], g("b_up")),
                "wo": _lin(w["w_down"], g("b_down"))},
    }
    if arch.gated:
        unit["mlp"]["wg"] = _lin(w["w_gate"])
    return unit


def _program_params(arch: Arch, stack, outer):
    return {"embed": {"tok": outer["embed"]},
            "segments": [{"u0": _unit(arch, stack)}],
            "final_norm": _norm(outer["final_scale"], outer.get("final_bias")),
            "lm_head": {"w": outer["lm_head"]}}


def exit_threshold(arch: Arch, k: int, share: float) -> float:
    """c such that the top of k local probabilities exceeds it with
    probability ``share``, for logits that are i.i.d. normal across the
    vocabulary with the scale the weights give them."""
    # logit std: the final norm's output has squared norm ~ d (times the
    # mean squared scale), the LM head entries have std initializer_range
    var = arch.std ** 2 * arch.d * (1.0 + 0.05 ** 2)
    if arch.layernorm:
        var += arch.std ** 2 * arch.d * 0.02 ** 2      # the final bias
    z = jax.random.normal(jax.random.PRNGKey(0), (MC_SAMPLES, arch.vocab))
    top = jax.lax.top_k(z * np.sqrt(var), k)[0]
    p1 = np.asarray(jax.nn.softmax(top, axis=-1)[:, 0], np.float64)
    return float(np.quantile(p1, 1.0 - share))


def _specee_weights(arch: Arch, model, stack, c: float, after_units: int):
    """SpecEEWeights that pin the exits (see the module docstring)."""
    assert after_units == 1, "the draft copies the first layer only"
    spec = model.run.specee
    E, k, H = model.num_exit_points, spec.num_speculative, \
        spec.predictor_hidden
    d, dt = arch.d, arch.dtype
    draft = _unit(arch, {n: x[0] for n, x in stack.items()})
    draft["fuse"] = {"w": jnp.concatenate([jnp.eye(d, dtype=dt),
                                           jnp.zeros((d, d), dt)], 0),
                     "b": jnp.zeros((d,), dt)}
    w0 = np.zeros((E, 3 * k, H), np.float32)
    b0 = np.zeros((E, H), np.float32)
    w1 = np.zeros((E, H, 1), np.float32)
    b1 = np.full((E, 1), NEVER, np.float32)
    # exit point 0: out = slope * (probs[0] - c), as two ReLUs
    w0[0, k, 0], b0[0, 0] = GATE_SLOPE, -GATE_SLOPE * c
    w0[0, k, 1], b0[0, 1] = -GATE_SLOPE, GATE_SLOPE * c
    w1[0, 0, 0], w1[0, 1, 0], b1[0, 0] = 1.0, -1.0, 0.0
    predictors = {"layers": [{"w": jnp.asarray(w0), "b": jnp.asarray(b0)},
                             {"w": jnp.asarray(w1), "b": jnp.asarray(b1)}]}
    offline = jnp.zeros((E,), bool).at[0].set(True)
    return eng.SpecEEWeights(draft=draft, predictors=predictors,
                             offline_mask=offline)


def _check_tree(name, got, want):
    gs = jax.tree_util.tree_structure(got)
    ws = jax.tree_util.tree_structure(want)
    if gs != ws:
        raise RuntimeError(f"{name}: the program's tree changed:\n{ws}\n"
                           f"the benchmark lays out:\n{gs}")
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise RuntimeError(f"{name}: leaf {a.shape} {a.dtype}, the "
                               f"program wants {b.shape} {b.dtype}")


def build(arch: Arch, seed: int, exits=None):
    """(model, params, sw or None), drawn on the device in one jitted call.
    ``exits``: the traffic's pinned-exit spec, or None for a dense cell."""
    model = build_model(run_config(arch))
    key = root_key(seed)
    c = None
    if exits is not None:
        c = exit_threshold(arch, model.run.specee.num_speculative,
                           exits["share"])

    def draw(key):
        stack, outer = draw_stack(arch, key), draw_outer(arch, key)
        params = _program_params(arch, stack, outer)
        sw = (None if exits is None else
              _specee_weights(arch, model, stack, c, exits["after_units"]))
        return params, sw

    shapes = jax.eval_shape(draw, key)
    _check_tree("params", shapes[0], jax.eval_shape(model.init, key))
    if exits is not None:
        _check_tree("SpecEE weights", shapes[1], jax.eval_shape(
            lambda k: eng.init_specee(model, k), key))
    params, sw = jax.jit(draw)(key)
    return model, params, sw, c


def engine(model, params, sw, strategy: str, megatick: int):
    from repro.api import SpecEEStrategy
    from repro.serving import ServingEngine
    strat = SpecEEStrategy() if strategy == "specee" else "dense"
    return ServingEngine(model, params, sw, strategy=strat,
                         megatick=megatick, cache="paged")
