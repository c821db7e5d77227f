"""Distributed per-vertex counter reduction (sharded traffic replay).

The sharded traffic replayer (:mod:`repro.core.traffic_sharded`) counts
per-vertex frontier mass on every mesh data shard and needs the *global*
per-vertex totals back — the same reduction shape as
:mod:`repro.distributed.halo`'s boundary publish, but for integer counters:
each shard scatter-adds its (vertex id, mass) pairs into a dense row
vector, then one ``psum`` over the data axes publishes the wave total to
every shard. No x64 on device, so the contract is split:

* **device, per wave**: int32 — callers bound wave sizes so a single
  wave's per-vertex mass stays far below 2³¹ (the replayer derives wave
  boundaries from per-op work so this holds by construction);
* **host, per log**: :class:`CounterAccumulator` folds int32 waves into
  int64 totals — a million-op log concentrated on one hub vertex cannot
  wrap.

All helpers are graph- and pattern-agnostic; anything that counts things
per vertex on a data-sharded mesh can reuse them.
"""

from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.launch.mesh import auto_axes

__all__ = ["CounterAccumulator", "data_shard_count", "make_scatter_psum"]


def data_shard_count(mesh: Mesh, data_axes: Tuple[str, ...] = ("data",)) -> int:
    """Number of shards along the mesh data axes."""
    n = 1
    for a in data_axes:
        n *= mesh.shape[a]
    return n


def make_scatter_psum(
    mesh: Mesh,
    n_rows: int,
    data_axes: Tuple[str, ...] = ("data",),
    shared_ids: bool = False,
) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """Return a jitted ``(ids, mass [S, W] int32) -> [n_rows] int32``.

    Each data shard owns one row of ``mass``; the result is the dense
    global scatter-add, identical (replicated) on every shard.
    Out-of-range ids are dropped — pad with ``n_rows`` (or any id ≥
    ``n_rows``) to make padding inert.

    ``ids`` is ``[S, W]`` (one row per shard) by default; with
    ``shared_ids=True`` it is one replicated ``[W]`` row every shard
    scatters through — the shape of the sharded replayer's whole-graph
    redo pass, where all shards solve on the same replicated layout.
    """
    mesh = auto_axes(mesh)

    def body(ids, mass):
        row = ids if shared_ids else ids[0]
        local = jnp.zeros((n_rows,), jnp.int32).at[row].add(mass[0], mode="drop")
        return jax.lax.psum(local, data_axes)

    smapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P() if shared_ids else P(data_axes, None), P(data_axes, None)),
        out_specs=P(),
        check_vma=False,
    )

    @jax.jit
    def scatter_psum(ids: jax.Array, mass: jax.Array) -> jax.Array:
        return smapped(ids.astype(jnp.int32), mass.astype(jnp.int32))

    return scatter_psum


class CounterAccumulator:
    """int64 host accumulation of int32 per-wave device counters.

    The int32 → int64 hand-off point: device waves are bounded by
    construction, the log-lifetime totals are not. ``add`` widens before
    summing, so a counter that is already at int32 range cannot wrap.
    """

    def __init__(self, n_rows: int):
        self.total = np.zeros(n_rows, dtype=np.int64)

    def add(self, wave) -> None:
        wave = np.asarray(wave)
        self.total += wave.astype(np.int64, copy=False)
