"""Production mesh construction.

16×16 = 256 chips per v5e pod; the multi-pod mesh adds a leading 'pod' axis
(2 pods = 512 chips for the dry-run; the same code scales the pod extent).
Defined as functions — importing this module never touches jax device state.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — run "
            f"under XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices[:n])


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / smoke runs)."""
    devices = jax.devices()[: data * model]
    if len(devices) < data * model:
        raise RuntimeError(
            f"mesh ({data},{model}) needs {data * model} devices, found "
            f"{len(devices)} — run under XLA_FLAGS="
            f"--xla_force_host_platform_device_count={data * model}")
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices)


def make_replica_meshes(replicas: int, model: int = 1):
    """Disjoint per-replica (1, model) meshes for a ``ReplicaPool``: replica
    i owns devices [i·model, (i+1)·model) — tensor parallelism within a
    replica, pure data parallelism (no collective) across them. ``model=1``
    with one device total returns ``[None] * replicas`` (replicas time-share
    the device — the CPU smoke-test degeneration)."""
    need = replicas * model
    devices = jax.devices()
    if model == 1 and len(devices) == 1:
        return [None] * replicas
    if len(devices) < need:
        raise RuntimeError(
            f"{replicas} replicas × model={model} need {need} devices, "
            f"found {len(devices)} — run under XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need}")
    return [jax.make_mesh((1, model), ("data", "model"),
                          axis_types=(AxisType.Auto,) * 2,
                          devices=devices[i * model:(i + 1) * model])
            for i in range(replicas)]
