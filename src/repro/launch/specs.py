"""ShapeDtypeStruct stand-ins for every dry-run input (no allocation).

``input_specs(run, shape_cell)`` returns (fn_args, sharding_specs) for the
step function of that cell:
  train   -> (params_f32, opt_state, batch)        for train_step
  prefill -> (params_lowp, batch)                  for prefill_step
  decode  -> (params_lowp, specee_weights, state)  for serve_step (SpecEE AR)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.config import RunConfig, ShapeCell
from repro.core import draft as draft_lib
from repro.core import engine as eng
from repro.core import scheduler as sched_lib
from repro.data.pipeline import make_batch_specs
from repro.models.common import dtype_of
from repro.models.model import Model
from repro.optim.adamw import adamw_init
from repro.sharding import (batch_specs, cache_specs, param_specs,
                            specee_specs, state_specs)


def params_struct(model: Model, low_precision: bool) -> Any:
    dtype = model.cfg.dtype if low_precision else model.cfg.param_dtype
    return jax.eval_shape(lambda k: model.init(k, dtype=dtype),
                          jax.random.PRNGKey(0))


def batch_struct(model: Model, cell: ShapeCell) -> Dict[str, Any]:
    cfg = model.cfg
    seq = cell.seq_len
    if cfg.frontend == "vision_patches":
        seq = max(seq - cfg.frontend_tokens, 1)  # total length incl. patches
    spec = make_batch_specs(cfg, cell.global_batch, seq)
    return {k: jax.ShapeDtypeStruct(shape, dt) for k, (shape, dt) in
            spec.items()}


def decode_state_struct(model: Model, cell: ShapeCell) -> eng.DecodeState:
    cfg = model.cfg
    B, S = cell.global_batch, cell.seq_len

    def build():
        dtype = dtype_of(cfg.dtype)
        cache = model.empty_cache(B, S)
        # mark the cache as "full" semantically; shapes are what matter here
        dcache = draft_lib.draft_cache(cfg, B, S, dtype)
        return eng.DecodeState(
            cache=cache, draft_cache=dcache,
            sched=sched_lib.init_state(B, model.run.specee),
            last_token=jnp.zeros((B,), jnp.int32),
            h_last=jnp.zeros((B, cfg.d_model), dtype),
            prng=jax.random.PRNGKey(0))

    return jax.eval_shape(build)


def specee_struct(model: Model) -> eng.SpecEEWeights:
    return jax.eval_shape(lambda: eng.init_specee(model,
                                                 jax.random.PRNGKey(0)))


def input_specs(model: Model, cell: ShapeCell, mesh) -> Tuple[Tuple, Tuple]:
    """Returns (arg_structs, arg_partition_specs) for this cell's step fn."""
    policy_serve = model.run.sharding.policy
    if cell.kind == "train":
        params = params_struct(model, low_precision=False)
        pspec = param_specs(model, mesh, "fsdp_tp", params)
        opt = jax.eval_shape(adamw_init, params)
        ospec = state_specs(mesh, "fsdp_tp", pspec, opt)
        batch = batch_struct(model, cell)
        bspec = batch_specs(model, mesh, batch)
        return (params, opt, batch), (pspec, ospec, bspec)
    if cell.kind == "prefill":
        params = params_struct(model, low_precision=True)
        pspec = param_specs(model, mesh, policy_serve, params)
        batch = batch_struct(model, cell)
        bspec = batch_specs(model, mesh, batch)
        return (params, batch), (pspec, bspec)
    # decode
    params = params_struct(model, low_precision=True)
    pspec = param_specs(model, mesh, policy_serve, params)
    sw = specee_struct(model)
    swspec = specee_specs(model, mesh, policy_serve, sw)
    state = decode_state_struct(model, cell)
    data_ax = ("pod", "data") if "pod" in mesh.shape else "data"

    def fit(dim, ax):
        import numpy as _np
        size = (_np.prod([mesh.shape[a] for a in ax])
                if isinstance(ax, tuple) else mesh.shape[ax])
        return ax if dim % size == 0 else None

    B = cell.global_batch
    b_ax = fit(B, data_ax) or fit(B, "data")
    sspec = eng.DecodeState(
        cache=cache_specs(model, mesh, policy_serve, state.cache,
                          model.run.sharding.kv_seq_shard),
        draft_cache=cache_specs(model, mesh, policy_serve,
                                state.draft_cache,
                                model.run.sharding.kv_seq_shard),
        sched={"queue": P(b_ax, None), "qpos": P(b_ax)},
        last_token=P(b_ax),
        h_last=P(b_ax, None),
        prng=P(None),
    )
    return (params, sw, state), (pspec, swspec, sspec)
