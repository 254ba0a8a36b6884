"""Decode-state + engine weight shardings for tensor-parallel serving.

The serving path (DESIGN.md §9) runs the megatick under GSPMD: ``Engine``
device_puts its weights with the Megatron-role specs from ``policies`` and
pins every ``DecodeState`` it hands to a session with ``decode_state_specs``
— the KV cache head-sharded over 'model' (paged pools via
``KVCacheManager.partition_specs``), everything else replicated so the
host-side admission/retire row edits stay layout-oblivious. The exit-gate
verify region is the one explicitly shard_mapped piece (``exit_gate.ops``);
its vocab-split partial-reduce contract is what keeps sharded decode
token-identical to single-device.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.sharding import policies as pol


def _replicated(tree: Any) -> Any:
    return jax.tree_util.tree_map(lambda x: P(*([None] * np.ndim(x))), tree)


def decode_state_specs(model, mesh: Mesh, policy: str, state,
                       cache_mgr=None) -> Any:
    """PartitionSpec pytree for a ``DecodeState``.

    KV cache: the manager's own layout when given (paged pools shard their
    head dim, page table / lengths replicated), else the generic
    ``cache_specs`` with sequence sharding OFF — decode appends one position
    per tick and a seq-sharded cache would ship every write cross-shard.
    Draft cache, scheduler state, last_token/h_last, PRNG: replicated — the
    draft layer and predictors run per-shard identically (paper §3.2: the
    speculation side is ~3% of the model; replicating it costs little and
    keeps its argmax bit-identical without any collective).
    """
    from repro.core import engine as eng
    if cache_mgr is not None:
        cache_spec = cache_mgr.partition_specs(state.cache, mesh, policy)
    else:
        cache_spec = pol.cache_specs(model, mesh, policy, state.cache,
                                     kv_seq_shard=False)
    return eng.DecodeState(
        cache=cache_spec,
        draft_cache=_replicated(state.draft_cache),
        sched=_replicated(state.sched),
        last_token=_replicated(state.last_token),
        h_last=_replicated(state.h_last),
        prng=_replicated(state.prng),
    )


def engine_shardings(model, mesh: Mesh, policy: str, params, sw, qw
                     ) -> Tuple[Any, Optional[Any], Optional[Any]]:
    """NamedSharding trees for (params, sw, qw).

    Params take the Megatron roles (column/row/vocab-parallel); SpecEE
    weights shard the draft layer like a TP block with predictors
    replicated; quantized tiles ride replicated — the int pools are already
    ~4-8x smaller than fp and the dequant-fused kernels index them
    locally (sharding them would need spec-aware tile offsets; the sharded
    verify path skips QTensor heads for the same reason, ops.py).
    """
    p_named = pol.named(mesh, pol.param_specs(model, mesh, policy, params))
    s_named = (pol.named(mesh, pol.specee_specs(model, mesh, policy, sw))
               if sw is not None else None)
    q_named = (pol.named(mesh, _replicated(qw)) if qw is not None else None)
    return p_named, s_named, q_named


def init_params(model, key, mesh: Mesh) -> Any:
    """``model.init(key)`` drawn straight into the layout ``Engine`` gives
    the parameters on ``mesh`` (default policy), for a model that fits the
    mesh but not one of its devices. ``Model.init`` is one jit, which this
    outer jit inlines, so no unsharded or fp32 copy is ever resident."""
    shapes = jax.eval_shape(model.init, key)
    p_named, _, _ = engine_shardings(model, mesh, "tp_dp", shapes, None, None)
    return jax.jit(model.init, out_shardings=p_named)(key)
