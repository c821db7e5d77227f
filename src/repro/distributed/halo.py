"""Halo (ghost-vertex) exchange for partition-aware GNN message passing.

The TPU translation of the thesis's *Shadow Construct* (§5.3.1): a remote
neighbor is materialized locally as a ghost row, refreshed once per
message-passing step by a collective. Each shard exports its boundary
rows (nodes referenced by any other shard); one ``all_gather`` over the
data axes publishes all boundaries; each shard then gathers exactly the
ghosts it needs with a static index table built host-side.

Collective volume per step = S × B_max × F × bytes, where B_max tracks the
edge cut — **a better DiDiC partitioning directly shrinks the collective
roofline term**, which is the paper's claim restated in hardware units.

All per-shard tables are padded to common shapes and stacked ``[S, ...]``
so a single ``shard_map`` body serves every shard with static shapes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.tree_util import Partial

from repro.distributed.placement import PartitionedLayout
from repro.graphs.structure import Graph
from repro.launch.mesh import auto_axes

__all__ = ["HaloProgram", "build_halo_program", "make_partitioned_spmm"]


@dataclasses.dataclass
class HaloProgram:
    """Static, per-shard-stacked index tables for the halo exchange."""

    edge_src: np.ndarray       # [S, E_max] index into [local(block) ++ ghosts(G_max)]
    edge_dst: np.ndarray       # [S, E_max] local destination index (0..block)
    edge_w: np.ndarray         # [S, E_max] float32
    edge_mask: np.ndarray      # [S, E_max] float32
    boundary_idx: np.ndarray   # [S, B_max] local indices exported by each shard
    ghost_src: np.ndarray      # [S, G_max] index into flattened all-gather [S·B_max]
    block: int
    n_shards: int

    @property
    def e_max(self) -> int:
        return self.edge_src.shape[1]

    @property
    def b_max(self) -> int:
        return self.boundary_idx.shape[1]

    @property
    def g_max(self) -> int:
        return self.ghost_src.shape[1]

    def halo_bytes(self, d_feat: int, bytes_per_el: int = 4) -> int:
        """all_gather volume per step per device."""
        return self.n_shards * self.b_max * d_feat * bytes_per_el


def _pad_stack(rows, pad_value, dtype) -> np.ndarray:
    width = max((len(r) for r in rows), default=0)
    width = max(width, 1)
    out = np.full((len(rows), width), pad_value, dtype=dtype)
    for i, r in enumerate(rows):
        out[i, : len(r)] = r
    return out


def build_halo_program(
    graph: Graph,
    layout: PartitionedLayout,
    edge_weights: np.ndarray | None = None,
) -> HaloProgram:
    """Precompute the per-shard edge/boundary/ghost tables (host-side)."""
    s_arr, r_arr, w_arr = graph.undirected
    if edge_weights is not None:
        w_arr = edge_weights
    S, block = layout.n_shards, layout.block
    new_s = layout.old_to_new[s_arr]
    new_r = layout.old_to_new[r_arr]
    shard_s = new_s // block
    shard_r = new_r // block
    local_s = new_s % block
    local_r = new_r % block

    # Boundary sets: nodes referenced by any foreign shard.
    cross = shard_s != shard_r
    boundary_rows = []
    boundary_pos = {}  # (shard, local_idx) -> position in that shard's export list
    for s in range(S):
        exported = np.unique(local_s[cross & (shard_s == s)])
        boundary_rows.append(exported)
        for pos, li in enumerate(exported):
            boundary_pos[(s, int(li))] = pos
    boundary_idx = _pad_stack(boundary_rows, 0, np.int32)
    b_max = boundary_idx.shape[1]

    # Per destination shard: edges grouped by receiver's shard; ghost table.
    edge_src_rows, edge_dst_rows, edge_w_rows = [], [], []
    ghost_rows = []
    for s in range(S):
        mask = shard_r == s
        es, ed, ew = local_s[mask], local_r[mask], w_arr[mask]
        eshard = shard_s[mask]
        is_local = eshard == s
        # ghosts: unique (src shard, src local) pairs for foreign senders
        foreign = ~is_local
        gkey = eshard[foreign] * block + es[foreign]
        guniq, ginv = np.unique(gkey, return_inverse=True)
        g_shard = guniq // block
        g_local = guniq % block
        ghost_src = np.array(
            [g_shard[i] * b_max + boundary_pos[(int(g_shard[i]), int(g_local[i]))] for i in range(guniq.shape[0])],
            dtype=np.int64,
        )
        src_index = np.where(is_local, es, 0)
        src_index_f = np.empty(es.shape[0], dtype=np.int64)
        src_index_f[is_local] = es[is_local]
        src_index_f[foreign] = block + ginv  # ghosts appended after locals
        edge_src_rows.append(src_index_f)
        edge_dst_rows.append(ed)
        edge_w_rows.append(ew)
        ghost_rows.append(ghost_src)

    edge_src = _pad_stack(edge_src_rows, 0, np.int32)
    edge_dst = _pad_stack(edge_dst_rows, 0, np.int32)
    edge_w = _pad_stack(edge_w_rows, 0.0, np.float32)
    edge_mask = _pad_stack([np.ones(len(r), np.float32) for r in edge_w_rows], 0.0, np.float32)
    ghost_src = _pad_stack(ghost_rows, 0, np.int32)
    # Clamp padded ghost capacity so edge_src stays in range.
    g_max = ghost_src.shape[1]
    edge_src = np.minimum(edge_src, block + g_max - 1)
    return HaloProgram(
        edge_src=edge_src,
        edge_dst=edge_dst,
        edge_w=edge_w,
        edge_mask=edge_mask,
        boundary_idx=boundary_idx,
        ghost_src=ghost_src,
        block=block,
        n_shards=S,
    )


def make_partitioned_spmm(
    program: HaloProgram, mesh: Mesh, data_axes: Tuple[str, ...] = ("data",)
) -> Partial:
    """Return ``x [S·block, F] → Σ_e w·x[src]`` with halo exchange.

    ``x`` must be sharded ``P(data_axes, None)``; the result has the same
    sharding. This is the distributed form of the DiDiC/GCN SpMM: local
    segment-sum + one all-gather of boundary rows. The halo tables live
    sharded on the mesh and are the :class:`jax.tree_util.Partial`'s
    arguments, so a jitted caller takes them as inputs, not constants.
    """
    mesh = auto_axes(mesh)
    block = program.block
    spec_x = P(data_axes, None)
    spec_tab = P(data_axes, None)

    tab_sharding = NamedSharding(mesh, spec_tab)
    tabs = tuple(
        jax.device_put(np.asarray(t), tab_sharding)
        for t in (
            program.edge_src,
            program.edge_dst,
            program.edge_w,
            program.edge_mask,
            program.boundary_idx,
            program.ghost_src,
        )
    )

    def body(esrc, edst, ew, emask, bidx, gsrc, x_l):
        # shapes per shard: x_l [block, F]; tables [1, ...]
        x_l = x_l.reshape(block, -1)
        boundary = x_l[bidx[0]]                                   # [B_max, F]
        all_b = jax.lax.all_gather(boundary, data_axes, tiled=False)
        all_b = all_b.reshape(-1, x_l.shape[1])                   # [S·B_max, F]
        ghosts = all_b[gsrc[0]]                                   # [G_max, F]
        xx = jnp.concatenate([x_l, ghosts], axis=0)
        contrib = (ew[0] * emask[0])[:, None] * xx[esrc[0]]
        agg = jax.ops.segment_sum(contrib, edst[0], num_segments=block)
        return agg

    smapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec_tab,) * 6 + (spec_x,),
        out_specs=spec_x,
        check_vma=False,
    )
    return Partial(jax.jit(smapped), *tabs)
