"""Truly distributed DiDiC — the thesis's Future Work (§8.2) implemented.

    "…the implementation of these algorithms in a truly distributed
     environment — rather than in a simulator."

DiDiC's inner loops are SpMM against the Metropolis-scaled adjacency
(didic.py). Here the SpMM runs through the partition-aware halo exchange
(`distributed.halo`), so each mesh data-shard owns one block of vertices
and diffusion loads cross shards only via boundary collectives — the
algorithm partitions the graph while *running on* a partitioned layout.

Bootstrap: vertices are laid out by a cheap linear partitioning; DiDiC
then refines in place. The returned partition map can be fed back into
``build_layout`` to re-place the graph for subsequent GNN training — the
full production loop of DESIGN.md §4.

Two entry points share one cached mesh program (layout + halo SpMM +
coefficient degrees, built once per (graph, mesh, data_axes)):

* :func:`didic_partition_distributed` — initial partitioning from a
  random start (paper Static experiment, T=100);
* :func:`didic_refine_distributed`    — the maintenance pass of the
  Dynamic/Stress experiments (T=1, deterministic commit, full smoothing
  width — the same adaptations as :func:`repro.core.didic.didic_refine`),
  with the diffusion state carried **sharded on the mesh** between calls
  so an intermittent maintenance schedule never round-trips it to host.

The sharded passes run the same arithmetic as the single-device ones but
sum float32 in a different association (per-shard segment-sums + psum vs
one global segment-sum), so results are quality-equivalent, not
bit-equal; callers needing bit-parity with the host loop use the
single-device refine (see ``PartitionedGraphService(maintenance=...)``).

Store-backed graphs (the delta-overlay growth path) refine through a
**capacity mesh program** instead: halo tables padded to the store's
capacity, cached on the store lineage with the jitted step taking them
as arguments (:class:`_CapacityMeshProgram`), so vertex growth within a
standing capacity re-pads host-side and retraces nothing — the same
contract as ``get_replayer``/``get_engine`` and the single-device
overlay step of :mod:`repro.core.didic`.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.didic import (
    _INIT_LOAD,
    DidicConfig,
    DidicState,
    _init_state,
    _make_step,
    _smooth_schedule,
    _step_body,
)
from repro.core import partitioners
from repro.graphs.structure import Graph
from repro.launch.mesh import auto_axes

if False:  # typing only — real imports are lazy (core ↔ distributed cycle)
    from repro.distributed.placement import PartitionedLayout  # noqa: F401

__all__ = ["didic_partition_distributed", "didic_refine_distributed"]


def _distributed_coefficients(graph: Graph) -> np.ndarray:
    """Metropolis edge coefficients (same as didic._edge_coefficients)."""
    s, r, wt = graph.undirected
    deg = graph.weighted_degree
    return (wt / (1.0 + np.maximum(deg[s], deg[r]))).astype(np.float32)


def _mesh_program(graph: Graph, mesh, data_axes: Tuple[str, ...],
                  bootstrap_parts: Optional[np.ndarray] = None):
    """(layout, halo spmm, degc) for DiDiC on ``mesh`` — cached on the
    graph's store when it has one (keyed by mesh/axes + structural
    extents), else on the graph object.

    The layout is placement, not partitioning: vertices stay on their
    bootstrap shard while their *logical* partition label diffuses, so one
    halo program serves initial partitioning and every later maintenance
    pass. Only an explicit ``bootstrap_parts`` bypasses the cache.
    """
    from repro.distributed.halo import build_halo_program, make_partitioned_spmm
    from repro.distributed.placement import build_layout

    n_shards = 1
    for a in data_axes:
        n_shards *= mesh.shape[a]

    cache = graph.__dict__.setdefault("_didic_mesh_cache", {})
    key = (mesh, tuple(data_axes)) if bootstrap_parts is None else None
    if key is not None and key in cache:
        return cache[key]

    out = _mesh_program_build(
        graph, mesh, data_axes, n_shards, bootstrap_parts,
        build_halo_program, make_partitioned_spmm, build_layout,
    )
    if key is not None:
        cache[key] = out
    return out


def _mesh_program_build(graph, mesh, data_axes, n_shards, bootstrap_parts,
                        build_halo_program, make_partitioned_spmm, build_layout):
    if bootstrap_parts is None:
        bootstrap_parts = partitioners.linear_partition(graph.n_nodes, n_shards)
    layout = build_layout(graph, bootstrap_parts, n_shards)

    ce = _distributed_coefficients(graph)
    program = build_halo_program(graph, layout, edge_weights=ce)
    spmm_halo = make_partitioned_spmm(program, mesh, data_axes)

    # degc in the padded layout (padding rows have zero degree → inert).
    s, _, _ = graph.undirected
    degc_host = np.zeros(graph.n_nodes, dtype=np.float64)
    np.add.at(degc_host, s, ce)
    from jax.sharding import NamedSharding, PartitionSpec as P

    degc = jax.device_put(
        layout.scatter_features(degc_host.astype(np.float32)),
        NamedSharding(auto_axes(mesh), P(data_axes)),
    )
    return (layout, spmm_halo, degc)


def _live_rows(layout, mesh, data_axes) -> jax.Array:
    """bool [padded_n]: the layout's rows that hold a vertex, mesh-sharded."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(jnp.asarray(layout.new_to_old >= 0),
                          NamedSharding(mesh, P(data_axes)))


def _sharded_state(layout, k: int, parts_padded: np.ndarray, mesh, data_axes):
    """Fresh DidicState seeded from a padded partition map, mesh-sharded."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    shard = NamedSharding(mesh, P(data_axes, None))
    shard1 = NamedSharding(mesh, P(data_axes))
    state = _init_state(layout.padded_n, k, jnp.asarray(parts_padded))
    return DidicState(
        w=jax.device_put(state.w, shard),
        l=jax.device_put(state.l, shard),
        parts=jax.device_put(state.parts, shard1),
        beta=state.beta,
    )


# ===========================================================================
# Capacity-keyed mesh program (ISSUE 9 satellite): store-backed graphs run
# sharded maintenance through halo tables padded to the store's *capacity*,
# cached on the store lineage like ``get_replayer``/``get_engine`` — so
# delta-overlay growth re-pads host-side but never rebuilds the layout and
# never retraces the jitted step. The legacy extent-shaped program above
# remains for storeless graphs and explicit bootstraps.
# ===========================================================================
_MESH_OVERLAY_STEP_CACHE: dict = {}


class _CapacityMeshProgram:
    """Halo tables + DiDiC coefficients at capacity shapes for one store.

    The layout places the store's full capacity (``n_cap`` rows) linearly
    over the shards once; every grown graph sharing the store adopts into
    the same shapes: edge tables are right-padded with masked entries
    (weight/mask 0 → zero contribution), the coefficient degree and the
    live-row mask are scattered over the padded rows, and ``ghost_src``
    is re-strided from the fresh program's boundary width to the fixed
    capacity width. Dead rows are inert by construction — no live edge
    references them, their coefficient degree is 0, and the overlay step
    masks every reduction to the live rows.
    """

    def __init__(self, store, mesh, data_axes: Tuple[str, ...], n_shards: int):
        from types import SimpleNamespace

        from repro.distributed.placement import build_layout

        boot = partitioners.linear_partition(store.n_cap, n_shards)
        # build_layout reads the graph only for n_nodes — a capacity shim
        # lays out n_cap rows without materializing a capacity graph.
        self.layout = build_layout(
            SimpleNamespace(n_nodes=store.n_cap), boot, n_shards
        )
        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        self.e_max = max(1, 2 * store.e_cap)  # symmetrized edges ≤ 2·e_cap
        self.b_max = max(1, self.layout.block)
        self.g_max = max(1, min(self.e_max, (n_shards - 1) * self.b_max))
        self.extents: Optional[Tuple[int, int]] = None
        self.tables = None
        self.degc = None
        self.live = None

    def adopt(self, graph: Graph) -> None:
        """Re-pad the tables for ``graph``'s extents (no-op when current)."""
        extents = (graph.n_nodes, graph.n_edges)
        if extents == self.extents:
            return
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.distributed.halo import build_halo_program

        layout = self.layout
        S = layout.n_shards
        prog = build_halo_program(
            graph, layout, edge_weights=_distributed_coefficients(graph)
        )
        if prog.e_max > self.e_max or prog.g_max > self.g_max:
            raise ValueError(
                f"graph exceeds the capacity program "
                f"(edges {prog.e_max} > {self.e_max} or ghosts "
                f"{prog.g_max} > {self.g_max})"
            )

        def pad(tab: np.ndarray, width: int, fill) -> np.ndarray:
            out = np.full((S, width), fill, dtype=tab.dtype)
            out[:, : tab.shape[1]] = tab
            return out

        # ghost_src indexes the flattened [S · b_max] all-gather; restride
        # from the fresh program's boundary width to the capacity width.
        g_shard = prog.ghost_src // prog.b_max
        g_pos = prog.ghost_src % prog.b_max
        ghost_src = (g_shard * self.b_max + g_pos).astype(np.int32)

        n = graph.n_nodes
        rows = layout.old_to_new[:n]
        s, _, _ = graph.undirected
        ce = _distributed_coefficients(graph)
        degc_host = np.zeros(n, dtype=np.float64)
        np.add.at(degc_host, s, ce)
        degc = np.zeros(layout.padded_n, dtype=np.float32)
        degc[rows] = degc_host.astype(np.float32)
        live = np.zeros(layout.padded_n, dtype=bool)
        live[rows] = True

        mesh, axes = self.mesh, self.data_axes
        tab_shard = NamedSharding(mesh, P(axes, None))
        row_shard = NamedSharding(mesh, P(axes))
        self.tables = tuple(
            jax.device_put(jnp.asarray(t), tab_shard)
            for t in (
                pad(prog.edge_src, self.e_max, 0),
                pad(prog.edge_dst, self.e_max, 0),
                pad(prog.edge_w, self.e_max, 0.0),
                pad(prog.edge_mask, self.e_max, 0.0),
                pad(prog.boundary_idx, self.b_max, 0),
                pad(ghost_src, self.g_max, 0),
            )
        )
        self.degc = jax.device_put(jnp.asarray(degc), row_shard)
        self.live = jax.device_put(jnp.asarray(live), row_shard)
        self.extents = extents


def _capacity_mesh_program(graph: Graph, mesh,
                           data_axes: Tuple[str, ...]) -> _CapacityMeshProgram:
    """The store-lineage cache: one program per (store, mesh, axes), with
    no extents in the key — growth adopts, only a compaction (a new store
    object, hence a fresh ``caches`` dict) rebuilds."""
    store = graph.store
    n_shards = 1
    for a in data_axes:
        n_shards *= mesh.shape[a]
    key = ("mesh_program", mesh, tuple(data_axes))
    prog = store.caches.get(key)
    if prog is None:
        prog = _CapacityMeshProgram(store, mesh, data_axes, n_shards)
        store.caches[key] = prog
    prog.adopt(graph)
    return prog


def _make_mesh_overlay_step(mesh, data_axes: Tuple[str, ...],
                            config: DidicConfig, block: int):
    """Jitted sharded overlay iteration with the graph as arguments.

    The mesh twin of :func:`repro.core.didic._make_overlay_step`: halo
    tables, coefficient degrees, the live mask, and the live count are
    arguments, so one compiled program (module-cached per mesh/axes/
    config/block) serves every grown graph sharing a capacity. Numerics
    are the overlay live-masking on top of the halo-exchange SpMM — the
    sharded pass stays quality-equivalent, not bit-equal, to the
    single-device refine (different float32 reduction association).
    """
    cache_key = (mesh, tuple(data_axes), config, block)
    step = _MESH_OVERLAY_STEP_CACHE.get(cache_key)
    if step is not None:
        return step

    from jax.sharding import PartitionSpec as P

    spec_x = P(data_axes, None)
    spec_tab = P(data_axes, None)

    def body(x_l, esrc, edst, ew, emask, bidx, gsrc):
        x_l = x_l.reshape(block, -1)
        boundary = x_l[bidx[0]]
        all_b = jax.lax.all_gather(boundary, data_axes, tiled=False)
        all_b = all_b.reshape(-1, x_l.shape[1])
        ghosts = all_b[gsrc[0]]
        xx = jnp.concatenate([x_l, ghosts], axis=0)
        contrib = (ew[0] * emask[0])[:, None] * xx[esrc[0]]
        return jax.ops.segment_sum(contrib, edst[0], num_segments=block)

    smapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec_x,) + (spec_tab,) * 6,
        out_specs=spec_x,
        check_vma=False,
    )

    @jax.jit
    def step(w, l, parts, beta, key, smooth_steps,
             esrc, edst, ew, emask, bidx, gsrc, degc, live, live_n):
        def spmm(x):
            return smapped(x, esrc, edst, ew, emask, bidx, gsrc)

        return _step_body(config, spmm, degc, live, live_n.astype(w.dtype), w, l, parts, beta,
                          key, smooth_steps)

    _MESH_OVERLAY_STEP_CACHE[cache_key] = step
    return step


def _refine_capacity(
    graph: Graph,
    parts: np.ndarray,
    config: DidicConfig,
    mesh,
    data_axes: Tuple[str, ...],
    state: Optional[DidicState],
    iterations: int,
    seed: int,
) -> Tuple[np.ndarray, DidicState]:
    """Sharded maintenance through the capacity mesh program."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    prog = _capacity_mesh_program(graph, mesh, data_axes)
    layout = prog.layout
    if config.k % layout.n_shards:
        raise ValueError(
            f"k={config.k} must be a multiple of shards={layout.n_shards}"
        )
    n = graph.n_nodes
    rows = layout.old_to_new[:n]
    parts_pad = np.zeros(layout.padded_n, dtype=np.int32)
    parts_pad[rows] = np.asarray(parts, dtype=np.int32)
    row_shard = NamedSharding(mesh, P(data_axes))
    mat_shard = NamedSharding(mesh, P(data_axes, None))
    parts_j = jax.device_put(jnp.asarray(parts_pad), row_shard)
    if state is None or state.w.shape[0] != layout.padded_n:
        live = np.zeros(layout.padded_n, dtype=bool)
        live[rows] = True
        onehot = (
            parts_pad[:, None] == np.arange(config.k, dtype=np.int32)[None, :]
        ) & live[:, None]
        load = jax.device_put(
            jnp.asarray(_INIT_LOAD * onehot.astype(np.float32)), mat_shard
        )
        state = DidicState(
            w=load, l=load, parts=parts_j,
            beta=jnp.ones((config.k,), jnp.float32),
        )
    w, l, beta = state.w, state.l, state.beta
    parts_cur = parts_j

    step = _make_mesh_overlay_step(mesh, tuple(data_axes), config, layout.block)
    schedule = _smooth_schedule(config, iterations, start_wide=True)
    key = jax.random.PRNGKey(seed)
    live_n = jnp.int32(n)
    for it in range(iterations):
        key, sub = jax.random.split(key)
        w, l, parts_cur, beta = step(
            w, l, parts_cur, beta, sub, jnp.int32(schedule[it]),
            *prog.tables, prog.degc, prog.live, live_n,
        )
    new_state = DidicState(w=w, l=l, parts=parts_cur, beta=beta)
    return np.asarray(parts_cur)[rows].copy(), new_state


def didic_partition_distributed(
    graph: Graph,
    config: DidicConfig,
    mesh,
    data_axes: Tuple[str, ...] = ("data",),
    seed: int = 0,
    bootstrap_parts: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, "PartitionedLayout"]:
    """Run DiDiC with shard-resident loads + halo-exchange diffusion.

    Returns (parts[N] in ORIGINAL vertex ids, the bootstrap layout used).
    ``config.k`` must be a multiple of the data-shard count.
    """
    mesh = auto_axes(mesh)
    layout, spmm_halo, degc = _mesh_program(graph, mesh, data_axes, bootstrap_parts)
    if config.k % layout.n_shards:
        raise ValueError(
            f"k={config.k} must be a multiple of shards={layout.n_shards}"
        )

    rng = np.random.default_rng(seed)
    parts0_host = rng.integers(0, config.k, size=graph.n_nodes).astype(np.int32)
    parts0 = layout.scatter_features(parts0_host, fill=0)

    state = _sharded_state(layout, config.k, parts0, mesh, data_axes)
    w, l, parts, beta = state.w, state.l, state.parts, state.beta

    step = _make_step(spmm_halo, degc, config, _live_rows(layout, mesh, data_axes),
                      graph.n_nodes)
    schedule = _smooth_schedule(config, config.iterations, start_wide=False)
    key = jax.random.PRNGKey(seed)
    for it in range(config.iterations):
        key, sub = jax.random.split(key)
        w, l, parts, beta = step(w, l, parts, beta, sub, jnp.int32(schedule[it]))
    return np.asarray(parts)[layout.old_to_new], layout


def didic_refine_distributed(
    graph: Graph,
    parts: np.ndarray,
    config: DidicConfig,
    mesh,
    data_axes: Tuple[str, ...] = ("data",),
    state: Optional[DidicState] = None,
    iterations: int = 1,
    seed: int = 0,
    pinned: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, DidicState]:
    """Maintenance pass on the mesh (the sharded twin of ``didic_refine``).

    Seeds the assignment from the degraded ``parts`` (like the
    single-device refine, the input map always wins over ``state.parts``),
    runs at full smoothing width with deterministic commit (one-iteration
    budgets must not strand damaged vertices), and returns
    (parts[N] original ids, carried state). The diffusion loads and
    balance scalars live sharded over ``mesh``'s data axes; feed the
    state back on the next call and the intermittent maintenance of the
    Dynamic experiment never moves the diffusion system off the mesh.

    ``pinned`` (the placement exception table) is honored exactly as in
    the single-device refine: a host-side restore on the returned map,
    outside every compiled/sharded step, so pinning never retraces the
    mesh program.
    """
    from repro.core.didic import _capture_pins, _restore_pins

    mesh = auto_axes(mesh)
    config = dataclasses.replace(config, commit_prob=1.0)
    pinned, before = _capture_pins(parts, pinned)
    if graph.store is not None:
        # Store-backed graphs run the capacity program: cached on the
        # store lineage, so growth under a standing capacity reuses the
        # layout, the halo tables' shapes, and the compiled step.
        out, new_state = _refine_capacity(
            graph, parts, config, mesh, tuple(data_axes),
            state, iterations, seed,
        )
        return _restore_pins(out, pinned, before), new_state
    layout, spmm_halo, degc = _mesh_program(graph, mesh, data_axes)
    if config.k % layout.n_shards:
        raise ValueError(
            f"k={config.k} must be a multiple of shards={layout.n_shards}"
        )

    from jax.sharding import NamedSharding, PartitionSpec as P

    parts_padded = layout.scatter_features(
        np.asarray(parts, dtype=np.int32), fill=0
    )
    parts_j = jax.device_put(
        jnp.asarray(parts_padded), NamedSharding(mesh, P(data_axes))
    )
    if state is None:
        state = _sharded_state(layout, config.k, parts_padded, mesh, data_axes)
    w, l, beta = state.w, state.l, state.beta
    parts_cur = parts_j

    step = _make_step(spmm_halo, degc, config, _live_rows(layout, mesh, data_axes),
                      graph.n_nodes)
    schedule = _smooth_schedule(config, iterations, start_wide=True)
    key = jax.random.PRNGKey(seed)
    for it in range(iterations):
        key, sub = jax.random.split(key)
        w, l, parts_cur, beta = step(w, l, parts_cur, beta, sub, jnp.int32(schedule[it]))
    new_state = DidicState(w=w, l=l, parts=parts_cur, beta=beta)
    return _restore_pins(
        np.asarray(parts_cur)[layout.old_to_new], pinned, before
    ), new_state
