"""Production mesh construction (single-pod 16×16, multi-pod 2×16×16).

A FUNCTION, not a module constant — importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before anything else).

Every mesh the graph database runs on has ``AxisType.Auto`` axes: the
sharded bodies are written for ``jax.shard_map`` plus compiler-propagated
shardings around it, so a scatter that mixes a data-sharded operand with
a replicated one is left to the partitioner rather than typed (the
``Explicit`` default of ``jax.make_mesh`` would reject it). The functions
here make such meshes, and every sharded entry point passes a mesh it is
given through :func:`auto_axes` first.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes, devices=None):
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis ``Auto``: same devices, same axis names."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = max(n // model, 1)
    return _auto_mesh((data, model), ("data", "model"))


def make_replay_mesh(shards: int | None = None):
    """1-D data mesh for the sharded traffic replay CLI / benchmarks.

    The replay is embarrassingly parallel over op chunks, so every device
    goes on the single ``data`` axis. ``shards`` defaults to all visible
    devices and must not exceed them (on CPU, force more with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* any
    jax import).
    """
    n = len(jax.devices())
    shards = n if shards is None else int(shards)
    if not 1 <= shards <= n:
        raise ValueError(f"shards={shards} outside 1..{n} visible devices")
    return _auto_mesh((shards,), ("data",), devices=jax.devices()[:shards])
