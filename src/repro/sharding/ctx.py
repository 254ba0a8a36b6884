"""Shard context — the one hashable handle the decode hot path threads.

``ShardCtx`` rides through the jitted strategy step as a STATIC argument
(``jax.sharding.Mesh`` hashes by device assignment + axis names), so the
exit-gate entry points can open a ``shard_map`` region over the mesh's
tensor-parallel axis without the engine layers knowing anything about
partitioning beyond "a mesh is active". Threading it explicitly (rather
than ambient module state) matters because ``verify_argmax``/``verify_topk``
are module-level jits shared by every Engine in the process: the mesh must
key their compilation caches.

Pallas kernels cannot be partitioned by GSPMD: under a mesh every kernel
call sits inside a ``shard_map`` (``replicated`` for kernels each device runs
whole; the head-split paged attention and the vocab-split verify build their
own specs).

Leaf module on purpose: imports jax only, so the kernel wrappers can use it
without dragging in the model/policy stack (ops.py -> policies -> model
would be an import cycle).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class ShardCtx:
    """Tensor-parallel context for the decode step.

    mesh: the serving mesh; ``axis`` names the dimension vocab/head dims
        shard over. Other mesh axes (e.g. a trivial 'data' axis) must hold
        the decode state replicated — the sharded-verify region only
        partitions along ``axis``.
    """
    mesh: Mesh
    axis: str = "model"

    @property
    def degree(self) -> int:
        return int(self.mesh.shape[self.axis])

    def replicated(self, fn: Callable) -> Callable:
        """``fn`` run whole on every device of the mesh, with every operand
        and result replicated — how a Pallas kernel that works on
        replicated decode state runs under tensor parallelism."""
        return jax.shard_map(fn, mesh=self.mesh, in_specs=P(), out_specs=P(),
                             check_vma=False)

    @staticmethod
    def from_mesh(mesh: Optional[Mesh],
                  axis: str = "model") -> Optional["ShardCtx"]:
        """None / missing axis / degree-1 mesh -> None (sharding inactive),
        so every caller can treat ``shard is None`` as the single-device
        path."""
        if mesh is None or axis not in mesh.shape or mesh.shape[axis] <= 1:
            return None
        return ShardCtx(mesh=mesh, axis=axis)
