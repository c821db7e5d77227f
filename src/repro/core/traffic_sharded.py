"""Multi-host sharded traffic replay (ISSUE 2 tentpole).

The batched engine (:mod:`repro.core.traffic_batched`) already collapses an
evaluation log into a handful of device programs, but runs them on one
device. This module shards the **log** over the mesh data axes — the
thesis's Future Work (§8.2) "truly distributed environment" applied to the
measurement side of the paper: replaying ≈1M-op logs is the step where
partition quality becomes a hardware cost (Besta et al., *Demystifying
Graph Databases*).

Execution model (one ``shard_map`` family per pattern, all reusing the
engine's compiled layouts):

**Linear BFS sweep (filesystem, Twitter).** The level-prefix table
``P [N, t+1, 2]`` is ops-independent — built once on device. Ops are
split contiguously over the data shards; each shard gathers its per-op
counters from the replicated table (`per-op` stays int32: a single op is
< 2³¹ by the engine contract). The per-vertex frontier mass ``tm`` is
*linear in the ops*, so each shard folds its own level histograms through
the ``Σ_l (Aᵀ)^l c_l`` SpMV cascade in int32 and one ``psum`` over the
data axes publishes the wave total — the halo-exchange reduction shape of
:mod:`repro.distributed.counters`. Waves are sized from the per-op edge
counts (already known from the per-op pass) so a wave's per-vertex int32
mass provably cannot wrap; :class:`~repro.distributed.counters.CounterAccumulator`
folds waves into int64 on the host.

**Windowed batched SSSP (GIS).** Each round hands every data shard one
chunk of ops in the engine's difficulty order, packed by the engine's own
:meth:`~repro.core.traffic_batched.BatchedTrafficEngine.build_sssp_problem`
(windows, capped gather layout, coordinates) and padded to
common shapes. The per-shard solve is literally
:func:`~repro.core.traffic_batched._sssp_solve_body` — the same float32
operations as the single-device engine, so distances (and therefore the
deterministic A* expansion sets) are **bit-identical**. Membership mass is
reduced on-device (``member & accepted`` summed over ops, scattered by
global window ids) through :func:`repro.distributed.counters.make_scatter_psum`;
per-op counters return to the host and are written back in log order.
Window acceptance stays host-side in float64 (a float32 false-accept would
break exactness); rejected ops are re-solved on the whole graph in a redo
pass whose gather layout is **replicated once, device-resident** — per-op
columns stay data-sharded, but the layout tables are no longer restacked
per shard per round (ROADMAP "sharded GIS redo-pass locality").

Exactness: both engines are exact vs the scalar oracle, and every
reduction here is integer (order-free) while every float path reuses the
engine's verbatim solve body — so ``replay_sharded`` is bit-equal to
``execute_ops(..., engine="batched")`` on all four counters, for any mesh
shape and any (including uneven) log split. The equivalence suite in
``tests/test_traffic_sharded.py`` asserts this on a forced 8-device CPU
mesh.

**Resident replay (ISSUE 4 tentpole).** A log's solve artifacts split
into a parts-independent majority (GIS window membership + invalidation
footprint masks, per-op edge counts, BFS expansion levels and per-vertex
frontier mass) and a parts-dependent remainder (the cross counters).
:class:`ResidentReplayState` keeps the former device-resident across
replays of one log, so replaying the same log against an evolving
partition map — every slice of the dynamic experiment — reduces to an
integer ``member × cross_deg`` fold over the resident masks plus the
host-side finalize. Integer folds are order-free, so the resident path is
**bit-identical** to a cold solve. Structural dynamism (edge inserts)
dirties the touched vertices; ops whose footprint intersects the dirty
set are re-solved through the replicated whole-graph redo layout on the
next replay (see :mod:`repro.core.dynamic_runtime` for the lifecycle).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import tracing
from repro.core.traffic_batched import (
    _BIG_ID,
    _sssp_solve_body,
    get_engine,
    resolve_max_expansions,
)
from repro.distributed.counters import (
    CounterAccumulator,
    data_shard_count,
    make_scatter_psum,
)
from repro.graphs.structure import Graph
from repro.launch.mesh import auto_axes

__all__ = [
    "ResidentReplayState",
    "ShardedTrafficReplayer",
    "bfs_wave_ranges",
    "get_replayer",
    "migrate_resident_states",
    "replay_sharded",
]

# Per-(wave, shard) bound on Σ(1 + edges_op): keeps the int32 per-vertex
# frontier mass of one BFS wave below 2³⁰ — half the int32 range as margin.
_WAVE_BUDGET = 1 << 30


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _pad_to(arr: np.ndarray, length: int, fill) -> np.ndarray:
    if arr.shape[0] == length:
        return arr
    out = np.full((length,) + arr.shape[1:], fill, dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


def bfs_wave_ranges(per_op_edges: np.ndarray, budget: Optional[int] = None):
    """Contiguous op ranges whose Σ(1+edges) ≤ ``budget`` each (every wave
    has ≥ 1 op) — makes the per-wave int32 device mass safe by
    construction; real logs fit in a single wave. A range's work may equal
    the budget *exactly* (the 2³⁰ margin is itself safe: it is half the
    int32 range); only the op that would exceed it starts a new wave."""
    budget = _WAVE_BUDGET if budget is None else budget
    work = np.cumsum(1 + per_op_edges.astype(np.int64))
    waves, lo = [], 0
    while lo < per_op_edges.shape[0]:
        base = work[lo - 1] if lo else 0
        hi = int(np.searchsorted(work, base + budget, side="right"))
        hi = max(hi, lo + 1)
        waves.append((lo, hi))
        lo = hi
    return waves


# ===========================================================================
# Device-resident replay state
# ===========================================================================
@dataclasses.dataclass(eq=False)
class _ResidentRound:
    """One solved GIS round's device-resident artifacts.

    ``ids`` is ``[S, W]`` for windowed rounds (one window per shard) or
    ``[1, W]`` for whole-graph redo rounds (a single replicated layout —
    broadcasting recovers the per-shard view). ``member``/``foot`` are the
    solve body's masks; ``ok`` marks the columns whose counters this round
    owns (ops rejected by window acceptance or invalidated by a dirty set
    have ``ok=False`` here and ``ok=True`` in a later redo round).
    """

    ids: jax.Array        # [S, W] or [1, W] int32 global window ids
    member: jax.Array     # [S, W, C] bool expansion membership
    foot: jax.Array       # [S, W, C] bool invalidation footprint (f ≤ f_dst)
    opidx: np.ndarray     # [S, C] int64 op index, -1 where padding
    ok: np.ndarray        # [S, C] bool — column counted from this round


@dataclasses.dataclass(eq=False)
class ResidentReplayState:
    """Parts-independent solve artifacts of one (graph, log), kept
    device-resident across replays (module docstring; lifecycle documented
    in :mod:`repro.core.dynamic_runtime`).

    ``per_op_edges``/``tm`` are graph-pure int64 host counters;
    ``rounds`` hold the GIS masks on device; ``bfs_starts``/``bfs_levels``
    are the BFS per-op gather columns. ``mark_dirty`` queues structurally
    touched vertices — the owning replayer converts them into dirty *ops*
    (footprint intersection) and re-solves exactly those on next replay.
    """

    graph: Graph
    pattern: str
    n_ops: int
    per_op_edges: Optional[np.ndarray] = None   # [n_ops] int64, graph-pure
    tm: Optional[np.ndarray] = None             # [N] int64 frontier mass
    bfs_starts: Optional[jax.Array] = None      # [S, B] int32 (BFS kinds)
    bfs_levels: Optional[jax.Array] = None      # [S, B] int32
    rounds: List[_ResidentRound] = dataclasses.field(default_factory=list)
    dirty_ops: Optional[np.ndarray] = None      # [n_ops] bool
    pending_dirty: Optional[np.ndarray] = None  # queued dirty vertex ids

    @property
    def solved(self) -> bool:
        return self.per_op_edges is not None and self.tm is not None

    def mark_dirty(self, vertices) -> None:
        """Queue structurally-touched vertices for op invalidation."""
        v = np.unique(np.asarray(vertices, dtype=np.int64))
        if v.size == 0:
            return
        self.pending_dirty = (
            v if self.pending_dirty is None
            else np.union1d(self.pending_dirty, v)
        )

    def reset(self) -> None:
        """Drop every artifact (next replay is a full cold solve)."""
        self.per_op_edges = None
        self.tm = None
        self.bfs_starts = None
        self.bfs_levels = None
        self.rounds = []
        self.dirty_ops = None
        self.pending_dirty = None

    def state_bytes(self) -> int:
        """Total array footprint of the resident artifacts, in bytes.

        Computed from shapes/dtypes only (no device→host transfer), so
        it is safe to call every tick. Device-resident GIS round masks
        dominate; the host counters are included for completeness. This
        is the observability hook for the ROADMAP resident-memory
        ceiling: :meth:`repro.core.framework.RuntimeLogger.health_report`
        surfaces the per-service sum as ``resident_state_bytes``.
        """
        arrays = [self.per_op_edges, self.tm, self.bfs_starts,
                  self.bfs_levels, self.dirty_ops, self.pending_dirty]
        for rnd in self.rounds:
            arrays.extend([rnd.ids, rnd.member, rnd.foot, rnd.opidx, rnd.ok])
        return sum(
            int(a.size) * int(a.dtype.itemsize) for a in arrays if a is not None
        )


class ShardedTrafficReplayer:
    """Replay evaluation logs sharded over a mesh's data axes.

    One replayer per (graph, pattern, mesh) — or, for a delta-overlay
    store-backed graph, per (store, pattern, mesh): every closure is
    sized to the store's row capacity and graph tables enter as jit
    arguments, so :meth:`adopt_graph` moves the same replayer (and its
    compiled programs, and its resident states) onto each grown graph
    without retracing. Jitted shard_map closures are built once and
    cached here (per-shape variants cache inside jit, as in the
    single-device engine).
    """

    def __init__(
        self,
        graph: Graph,
        pattern: str,
        mesh: Mesh,
        data_axes: Tuple[str, ...] = ("data",),
        chunk: Optional[int] = None,
        max_expansions: Optional[int] = None,
        delta_scale: Optional[float] = None,
        use_kernel: Optional[bool] = None,
    ):
        self.graph = graph
        self.mesh = mesh = auto_axes(mesh)
        self.data_axes = tuple(data_axes)
        self.n_shards = data_shard_count(mesh, self.data_axes)
        self.engine = get_engine(
            graph, pattern, chunk=chunk, max_expansions=max_expansions,
            delta_scale=delta_scale, use_kernel=use_kernel,
        )
        self.n_nodes = graph.n_nodes
        # Growth-invariant scatter/fold row count: the store capacity for
        # overlay graphs (real ids always fit; the tail rows are inert and
        # sliced off host-side), exact logical size otherwise.
        self._row_cap = graph.store.n_cap if graph.store is not None else graph.n_nodes
        self.last_redo_ops = 0  # windowed-pass rejects of the last replay
        if self.engine.kind == "bfs":
            self._build_bfs_fns()
        else:
            self._build_sssp_fns()
        self._scatter_psum = make_scatter_psum(mesh, self._row_cap, self.data_axes)

    def adopt_graph(self, graph: Graph) -> None:
        """Adopt a grown graph from the same store lineage in place.

        Delegates structural refresh to the engine (host rebuild + H2D at
        frozen capacity shapes), then refreshes the replayer's resident
        graph-pure artifacts. No compiled program is invalidated."""
        if graph is self.graph:
            return
        self.engine.adopt(graph)
        self.graph = graph
        self.n_nodes = graph.n_nodes
        if self.engine.kind == "bfs":
            eng = self.engine
            self._deg_table = self._one_table_fn(eng._deg_j, eng._s_j, eng._r_j)
        else:
            # Whole-graph redo layout tracks logical extents; rebuilt
            # lazily from the adopted engine on next use.
            self._full_static_dev = None

    # =================================================== linear BFS patterns
    def _build_bfs_fns(self) -> None:
        eng = self.engine
        t, n = eng.max_levels, eng._n_rows
        axes = self.data_axes
        s2 = P(axes, None)

        # The deg-column prefix table is pure graph structure — built once
        # per structure revision and kept device-resident; only the cross
        # column (parts-dependent) is recomputed per replay. With a
        # resident state the per-op deg gather happens once per log too,
        # so a slice replay is one cross table build + one cross gather.
        self._one_table_fn = jax.jit(eng._bfs_prefix_one)
        self._deg_table = self._one_table_fn(eng._deg_j, eng._s_j, eng._r_j)
        self._per_op_one_fn = jax.jit(lambda st, lvl, table: table[st, lvl])

        def tm_body(starts, levels, valid, s_e, r_e):
            # Per-shard level histograms c[l][u] = #{ops: start=u, L>l},
            # folded through Σ_l (Aᵀ)^l c_l in int32 (wave-bounded), then
            # one psum publishes the wave's global per-vertex mass.
            lvl = jnp.minimum(levels[0], t) - 1
            idx = lvl * n + starts[0]
            hist = (
                jnp.zeros((t * n,), jnp.int32)
                .at[idx].add(valid[0].astype(jnp.int32), mode="drop")
                .reshape(t, n)
            )
            c = jnp.flip(jnp.cumsum(jnp.flip(hist, 0), axis=0), 0)
            tm = c[t - 1]
            for lvl_i in range(t - 2, -1, -1):
                push = jnp.zeros((n,), jnp.int32).at[r_e].add(tm[s_e])
                tm = c[lvl_i] + push
            return jax.lax.psum(tm, axes)

        self._tm_fn = jax.jit(jax.shard_map(
            tm_body,
            mesh=self.mesh,
            in_specs=(s2, s2, s2, P(), P()),
            out_specs=P(),
            check_vma=False,
        ))

    def _shard_pad(self, arr: np.ndarray, fill, width: Optional[int] = None) -> np.ndarray:
        """[n] -> [S, B] contiguous split (shard s owns rows s·B..(s+1)·B)."""
        s = self.n_shards
        b = width if width is not None else _ceil_div(max(arr.shape[0], 1), s)
        return _pad_to(arr, s * b, fill).reshape(s, b)

    def _round_opidx(self, round_idx: np.ndarray, chunk: int) -> np.ndarray:
        """A round's per-(shard, column) op index, -1 where padding."""
        opidx = np.full((self.n_shards, chunk), -1, dtype=np.int64)
        for sh in range(self.n_shards):
            idx = round_idx[sh * chunk: (sh + 1) * chunk]
            opidx[sh, : idx.shape[0]] = idx
        return opidx

    def _run_bfs(self, ops, cross_deg: np.ndarray,
                 state: Optional[ResidentReplayState] = None):
        eng = self.engine
        n_ops = ops.n_ops
        levels, _ = eng.bfs_log(ops)
        if state is not None and state.pending_dirty is not None:
            # BFS artifacts (ancestor levels, subtree prefix tables,
            # frontier mass) are global properties of the tree/edge list —
            # a structural insert invalidates them wholesale, so the state
            # resets and the next replay below re-solves cold.
            state.reset()
        if state is not None and state.solved:
            # Resident fast path: everything except the cross counters is
            # (graph, ops)-pure. One cross table + one gather per slice.
            cross = np.asarray(self._per_op_one_fn(
                state.bfs_starts, state.bfs_levels,
                self._one_table_fn(
                    jnp.asarray(eng._pad_rows(cross_deg)), eng._s_j, eng._r_j
                ),
            )).reshape(-1)[:n_ops].astype(np.int64)
            return state.per_op_edges, cross, state.tm

        starts = ops.starts.astype(np.int32)
        st_dev = jnp.asarray(self._shard_pad(starts, 0))
        lvl_dev = jnp.asarray(self._shard_pad(levels, 0))
        edges = np.asarray(
            self._per_op_one_fn(st_dev, lvl_dev, self._deg_table)
        ).reshape(-1)[:n_ops].astype(np.int64)
        cross = np.asarray(self._per_op_one_fn(
            st_dev, lvl_dev,
            self._one_table_fn(
                jnp.asarray(eng._pad_rows(cross_deg)), eng._s_j, eng._r_j
            ),
        )).reshape(-1)[:n_ops].astype(np.int64)

        # Frontier mass is (graph, ops)-pure — independent of the partition
        # map — so the resident state keeps it across replays of one log:
        # the dynamic experiment replays the same evaluation log against an
        # evolving partition map every slice, and this is the "per-vertex
        # traffic lives on the mesh across the cycle" leg of the device
        # runtime (only the cross/partition counters, which do depend on
        # parts, are recomputed per slice).
        acc = CounterAccumulator(eng._n_rows)
        for lo, hi in bfs_wave_ranges(edges):
            b = _ceil_div(hi - lo, self.n_shards)
            valid = np.ones(hi - lo, dtype=bool)
            acc.add(self._tm_fn(
                self._shard_pad(starts[lo:hi], 0, b),
                self._shard_pad(levels[lo:hi], 1, b),
                self._shard_pad(valid, False, b),
                eng._s_j, eng._r_j,
            ))
        tm = acc.total[: self.n_nodes]
        if state is not None:
            state.bfs_starts, state.bfs_levels = st_dev, lvl_dev
            state.per_op_edges, state.tm = edges, tm
        return edges, cross, tm

    # ====================================================== GIS batched SSSP
    def _build_sssp_fns(self) -> None:
        eng = self.engine
        axes = self.data_axes
        s2 = P(axes, None)
        s3 = P(axes, None, None)

        def solve_body(loc_src, loc_dst, dst_ids, valid, deg_w, cross_w,
                       ids_w, nbr, w_inf, sp_s, sp_r, sp_w,
                       lon_w, lat_w, dst_lon, dst_lat, delta):
            out = _sssp_solve_body(
                loc_src[0], loc_dst[0], dst_ids[0], valid[0],
                deg_w[0], cross_w[0], ids_w[0],
                nbr[0], w_inf[0], sp_s[0], sp_r[0], sp_w[0],
                lon_w[0], lat_w[0], dst_lon[0], dst_lat[0],
                delta,
                max_expansions=eng.max_expansions,
                finite_delta=eng.delta_scale is not None,
                use_kernel=eng.use_kernel,
                interpret=eng.interpret,
            )
            return tuple(a[None] for a in out)

        out_specs = (s3, s3, s2, s2, s2, s2, P(axes), P(axes))
        stack_specs = (s2, s2, s2, s2, s2, s2, s2, s3, s3, s2, s2, s2, s2, s2, s2, s2)
        self._solve_fn = jax.jit(jax.shard_map(
            solve_body,
            mesh=self.mesh,
            in_specs=stack_specs + (P(),),
            out_specs=out_specs,
            check_vma=False,
        ))
        # Stacked problems go to the device in the layout the solve reads;
        # the stack waits for the transfer, so the solve's time is its own.
        self._stack_shardings = tuple(NamedSharding(self.mesh, sp) for sp in stack_specs)

        # Redo (whole-graph) pass: the gather layout and the coordinates
        # are op- and parts-independent, so they are replicated once — only
        # the per-op columns (src/dst/valid/destination coordinates) are
        # data-sharded.
        def solve_full_body(loc_src, loc_dst, dst_ids, valid, dst_lon, dst_lat,
                            deg_w, cross_w, ids_w, nbr, w_inf,
                            sp_s, sp_r, sp_w, lon_w, lat_w, delta):
            out = _sssp_solve_body(
                loc_src[0], loc_dst[0], dst_ids[0], valid[0],
                deg_w, cross_w, ids_w, nbr, w_inf, sp_s, sp_r, sp_w,
                lon_w, lat_w, dst_lon[0], dst_lat[0],
                delta,
                max_expansions=eng.max_expansions,
                finite_delta=eng.delta_scale is not None,
                use_kernel=eng.use_kernel,
                interpret=eng.interpret,
            )
            return tuple(a[None] for a in out)

        per_op_specs = (s2,) * 6
        self._solve_full_fn = jax.jit(jax.shard_map(
            solve_full_body,
            mesh=self.mesh,
            in_specs=per_op_specs + (P(),) * 11,
            out_specs=out_specs,
            check_vma=False,
        ))
        self._per_op_shardings = tuple(NamedSharding(self.mesh, sp) for sp in per_op_specs)
        self._full_static_dev = None
        self._scatter_psum_shared = None

        # member [S, W, C] stays device-resident between the solve and this
        # shard-local mass reduce (no communication: inputs are data-sharded).
        self._mass_fn = jax.jit(
            lambda member, okm: (member & okm[:, None, :]).sum(axis=2, dtype=jnp.int32)
        )

        # Resident-state primitives (all integer/bool — order-free, so the
        # resident replay stays bit-equal to the cold solve). ``ids`` may
        # be [S, W] (windowed rounds) or [1, W] (replicated redo rounds) —
        # broadcasting recovers the per-shard view. Out-of-range padding
        # ids (_BIG_ID) index a sentinel 0/False row via the clamp. Sizes
        # are the growth-invariant row capacity, not the logical count.
        n_sentinel = jnp.int32(self._row_cap)
        self._fold_cross_fn = jax.jit(
            lambda ids, member, cross_full: (
                member.astype(jnp.int32)
                * cross_full[jnp.minimum(ids, n_sentinel)][..., None]
            ).sum(axis=1)
        )
        self._touched_fn = jax.jit(
            lambda ids, foot, dirty_full: (
                foot & dirty_full[jnp.minimum(ids, n_sentinel)][..., None]
            ).any(axis=1)
        )
        self._drop_cols_fn = jax.jit(lambda m, keep: m & keep[:, None, :])
        n_rows = self._row_cap
        self._scatter_rows_fn = jax.jit(
            lambda ids, mass: jnp.zeros((n_rows,), jnp.int32)
            .at[jnp.broadcast_to(ids, mass.shape).reshape(-1)]
            .add(mass.reshape(-1), mode="drop")
        )

    def _full_static(self):
        """Device-resident replicated whole-graph layout (built once)."""
        if self._full_static_dev is None:
            w_pad, nbr, w_inf, sp_s, sp_r, sp_w, ids_w, deg_w, lon_w, lat_w = (
                self.engine.ensure_full_layout()
            )
            self._full_static_dev = (w_pad,) + tuple(
                jnp.asarray(a) for a in (deg_w, ids_w, nbr, w_inf, sp_s, sp_r, sp_w,
                                         lon_w, lat_w)
            )
            if self._scatter_psum_shared is None:
                self._scatter_psum_shared = make_scatter_psum(
                    self.mesh, self._row_cap, self.data_axes, shared_ids=True
                )
        return self._full_static_dev

    def _stack_problems(self, probs):
        """Pad per-shard problems to common shapes and stack [S, ...]."""
        w_pad = max(p[7].shape[0] for p in probs)   # nbr rows
        d = max(p[7].shape[1] for p in probs)       # nbr slots
        sp = max(p[9].shape[0] for p in probs)      # spill length
        out = []
        for (loc_src, loc_dst, dst_ids, valid, deg_w, cross_w, ids_w,
             nbr, w_inf, sp_s, sp_r, sp_w, lon_w, lat_w, dst_lon, dst_lat) in probs:
            wr = nbr.shape[0]
            nbr_p = np.zeros((w_pad, d), np.int32)
            nbr_p[:wr, : nbr.shape[1]] = nbr
            w_inf_p = np.full((w_pad, d), np.inf, np.float32)
            w_inf_p[:wr, : w_inf.shape[1]] = w_inf
            out.append((
                loc_src, loc_dst, dst_ids, valid,
                _pad_to(deg_w, w_pad, 0), _pad_to(cross_w, w_pad, 0),
                _pad_to(ids_w, w_pad, _BIG_ID),
                nbr_p, w_inf_p,
                _pad_to(sp_s, sp, 0), _pad_to(sp_r, sp, 0),
                _pad_to(sp_w, sp, np.float32(np.inf)),
                _pad_to(lon_w, w_pad, 0), _pad_to(lat_w, w_pad, 0), dst_lon, dst_lat,
            ))
        return tuple(np.stack(col) for col in zip(*out))

    @staticmethod
    def _solve(fn, n_ops: int, *args):
        """Run one sharded solve round until the host holds its per-op
        results: ``(member, foot, edges, cross, f_dst)``, the masks on
        the device. Books the round's ops, relax sweeps and corrected
        heuristic entries (summed over shards) to the tracing counters."""
        with tracing.span("sssp.solve"):
            member, foot, edges, cross, f_dst, done, rounds, corrected = fn(*args)
            done, edges, cross, f_dst, rounds, corrected = jax.device_get(
                (done, edges, cross, f_dst, rounds, corrected)
            )
        tracing.count("sssp.op_solves", n_ops)
        tracing.count("sssp.relax_rounds", int(np.sum(rounds, dtype=np.int64)))
        tracing.count("sssp.heuristic_corrected", int(np.sum(corrected, dtype=np.int64)))
        if not np.asarray(done).all():
            raise RuntimeError(
                "sharded SSSP hit its round cap before all ops "
                "settled; raise delta_scale (or use delta_scale=None)"
            )
        return (member, foot, np.asarray(edges, dtype=np.int64),
                np.asarray(cross, dtype=np.int64), np.asarray(f_dst, dtype=np.float64))

    def _run_sssp(self, ops, cross_deg: np.ndarray,
                  state: Optional[ResidentReplayState] = None):
        eng = self.engine
        if state is not None and state.solved:
            return self._replay_resident_sssp(ops, cross_deg, state)
        if state is not None:
            # A previous cold solve may have died mid-pass (round-cap
            # RuntimeError) after capturing some rounds; a retry must not
            # stack a second set of ok=True columns on top of them.
            state.reset()
        with tracing.span("sssp.order"):
            order = eng._compile_sssp_log(ops)
        n_ops, s, chunk = ops.n_ops, self.n_shards, eng.chunk
        per_op_edges = np.zeros(n_ops, dtype=np.int64)
        per_op_cross = np.zeros(n_ops, dtype=np.int64)
        acc = CounterAccumulator(self._row_cap)
        redo: List[np.ndarray] = []

        def run_pass(op_idx: np.ndarray) -> None:
            for lo in range(0, op_idx.shape[0], s * chunk):
                round_idx = op_idx[lo: lo + s * chunk]
                probs, metas = [], []
                for sh in range(s):
                    idx = round_idx[sh * chunk: (sh + 1) * chunk]
                    srcs = _pad_to(ops.starts[idx], chunk, 0)
                    dsts = _pad_to(ops.ends[idx], chunk, 0)
                    valid = _pad_to(np.ones(idx.shape[0], bool), chunk, False)
                    if idx.shape[0]:
                        args, window, w_real, box, eff_full = eng.build_sssp_problem(
                            srcs, dsts, valid, cross_deg, False
                        )
                    else:
                        # Idle shard this round: an inert all-invalid
                        # problem (solve retires it in zero rounds).
                        args = (
                            np.zeros(chunk, np.int32), np.zeros(chunk, np.int32),
                            np.zeros(chunk, np.int32), valid,
                            np.zeros(1, np.int32), np.zeros(1, np.int32),
                            np.full(1, _BIG_ID, np.int32),
                            np.zeros((1, 1), np.int32),
                            np.full((1, 1), np.inf, np.float32),
                            np.zeros(0, np.int32), np.zeros(0, np.int32),
                            np.zeros(0, np.float32),
                            np.zeros(1, np.float32), np.zeros(1, np.float32),
                            np.zeros(chunk, np.float32), np.zeros(chunk, np.float32),
                        )
                        window, w_real, box, eff_full = None, 0, None, False
                    probs.append(args)
                    metas.append((idx, srcs, dsts, valid, window, w_real, box, eff_full))

                with tracing.span("sssp.stack"):
                    stacked = jax.block_until_ready(jax.device_put(
                        self._stack_problems(probs), self._stack_shardings
                    ))
                member, foot, edges_h, cross_h, f_dst_h = self._solve(
                    self._solve_fn, round_idx.shape[0], *stacked, jnp.float32(eng.delta)
                )

                ok_all = np.zeros((s, chunk), dtype=bool)
                with tracing.span("sssp.accept"):
                    for sh, (idx, srcs, dsts, valid, _w, _wr, box, eff_full) in enumerate(metas):
                        if not idx.shape[0]:
                            continue
                        ok = eng.window_accept(srcs, dsts, valid, f_dst_h[sh], box, eff_full)
                        ok_all[sh] = ok
                        nsh = idx.shape[0]
                        accepted = idx[ok[:nsh]]
                        per_op_edges[accepted] = edges_h[sh, :nsh][ok[:nsh]]
                        per_op_cross[accepted] = cross_h[sh, :nsh][ok[:nsh]]
                        if not eff_full:
                            rejected = idx[~ok[:nsh]]
                            if rejected.size:
                                redo.append(rejected)

                # Per-vertex mass: shard-local (member & ok) summed over
                # ops, scattered by global window id, one psum — int32 per
                # round (≤ S·chunk), int64 across rounds on the host.
                with tracing.span("sssp.mass"):
                    mass = self._mass_fn(member, jnp.asarray(ok_all))
                    acc.add(self._scatter_psum(stacked[6], mass))
                if state is not None:
                    state.rounds.append(_ResidentRound(
                        ids=stacked[6], member=member, foot=foot,
                        opidx=self._round_opidx(round_idx, chunk), ok=ok_all,
                    ))

        run_pass(order)
        self.last_redo_ops = int(sum(r.shape[0] for r in redo))
        tracing.count("sssp.redo_ops", self.last_redo_ops)
        if redo:
            with tracing.span("sssp.redo"):
                self._run_full_pass(
                    ops, np.concatenate(redo), cross_deg,
                    per_op_edges, per_op_cross, acc, state=state,
                )
        tm = acc.total[: self.n_nodes]
        if state is not None:
            state.per_op_edges = per_op_edges
            state.tm = tm
            state.dirty_ops = np.zeros(n_ops, dtype=bool)
        return per_op_edges, per_op_cross, tm

    def _run_full_pass(
        self,
        ops,
        op_idx: np.ndarray,
        cross_deg: np.ndarray,
        per_op_edges: np.ndarray,
        per_op_cross: np.ndarray,
        acc: CounterAccumulator,
        state: Optional[ResidentReplayState] = None,
    ) -> None:
        """Re-solve rejected ops on the whole graph, replicated-layout form.

        The gather layout is shared by every shard (one device-resident
        copy, not one stacked copy per shard per round); only the per-op
        columns are packed and sharded. The solve body — and therefore
        every float32 operation and counter — is identical to the windowed
        pass and the single-device engine, so the pass stays bit-exact.
        Serves both window-acceptance rejects (cold solve) and dirty-set
        redos (resident replay after structural inserts) — with a
        ``state``, each round is captured as a resident ``[1, W]``
        replicated-ids round.
        """
        eng, s, chunk = self.engine, self.n_shards, self.engine.chunk
        (w_pad, deg_w_d, ids_w_d, nbr_d, w_inf_d, sp_s_d, sp_r_d, sp_w_d,
         lon_w_d, lat_w_d) = self._full_static()
        cross_w = np.zeros(w_pad, dtype=np.int32)
        cross_w[: self.n_nodes] = cross_deg
        cross_w_d = jnp.asarray(cross_w)
        for lo in range(0, op_idx.shape[0], s * chunk):
            round_idx = op_idx[lo: lo + s * chunk]
            per_op, metas = [], []
            for sh in range(s):
                idx = round_idx[sh * chunk: (sh + 1) * chunk]
                srcs = _pad_to(ops.starts[idx], chunk, 0)
                dsts = _pad_to(ops.ends[idx], chunk, 0)
                valid = _pad_to(np.ones(idx.shape[0], bool), chunk, False)
                if idx.shape[0]:
                    loc_src, loc_dst, dst_ids, dst_lon, dst_lat = eng.full_per_op(
                        srcs, dsts, valid
                    )
                    per_op.append((loc_src, loc_dst, dst_ids, valid, dst_lon, dst_lat))
                else:
                    per_op.append((
                        np.zeros(chunk, np.int32), np.zeros(chunk, np.int32),
                        np.zeros(chunk, np.int32), valid,
                        np.zeros(chunk, np.float32), np.zeros(chunk, np.float32),
                    ))
                metas.append((idx, srcs, dsts, valid))

            with tracing.span("sssp.stack"):
                stacked = jax.block_until_ready(jax.device_put(
                    tuple(np.stack(col) for col in zip(*per_op)), self._per_op_shardings
                ))
            member, foot, edges_h, cross_h, f_dst_h = self._solve(
                self._solve_full_fn, round_idx.shape[0],
                *stacked, deg_w_d, cross_w_d, ids_w_d, nbr_d, w_inf_d,
                sp_s_d, sp_r_d, sp_w_d, lon_w_d, lat_w_d, jnp.float32(eng.delta),
            )

            ok_all = np.zeros((s, chunk), dtype=bool)
            with tracing.span("sssp.accept"):
                for sh, (idx, srcs, dsts, valid) in enumerate(metas):
                    if not idx.shape[0]:
                        continue
                    ok = eng.window_accept(srcs, dsts, valid, f_dst_h[sh], None, True)
                    ok_all[sh] = ok
                    nsh = idx.shape[0]
                    accepted = idx[ok[:nsh]]
                    per_op_edges[accepted] = edges_h[sh, :nsh][ok[:nsh]]
                    per_op_cross[accepted] = cross_h[sh, :nsh][ok[:nsh]]

            with tracing.span("sssp.mass"):
                mass = self._mass_fn(member, jnp.asarray(ok_all))
                acc.add(self._scatter_psum_shared(ids_w_d, mass))
            if state is not None:
                state.rounds.append(_ResidentRound(
                    ids=ids_w_d[None], member=member, foot=foot,
                    opidx=self._round_opidx(round_idx, chunk), ok=ok_all,
                ))

    # ------------------------------------------------- resident replay path
    def _replay_resident_sssp(self, ops, cross_deg: np.ndarray,
                              state: ResidentReplayState):
        """Per-slice GIS replay from resident artifacts.

        Absorb any queued dirty vertices into dirty *ops* (footprint
        intersection), re-solve exactly those through the replicated redo
        layout, then reduce the slice to the parts-dependent integer
        ``member × cross_deg`` fold over the resident masks. Every
        reduction is integer, so the result is bit-identical to a cold
        solve of the whole log against the same partition map.
        """
        self.last_redo_ops = 0
        if state.pending_dirty is not None:
            self._absorb_dirty(state)
        if state.dirty_ops is not None and state.dirty_ops.any():
            self._redo_dirty(ops, state, cross_deg)
        # Prune rounds that no longer own any op (fully superseded).
        state.rounds = [r for r in state.rounds if r.ok.any()]

        cross_full = np.zeros(self._row_cap + 1, dtype=np.int32)
        cross_full[: self.n_nodes] = cross_deg
        cross_dev = jnp.asarray(cross_full)
        per_op_cross = np.zeros(state.n_ops, dtype=np.int64)
        for rnd in state.rounds:
            ch = np.asarray(
                self._fold_cross_fn(rnd.ids, rnd.member, cross_dev),
                dtype=np.int64,
            )
            per_op_cross[rnd.opidx[rnd.ok]] = ch[rnd.ok]
        return state.per_op_edges, per_op_cross, state.tm

    def _absorb_dirty(self, state: ResidentReplayState) -> None:
        """Turn queued dirty vertices into dirty ops and evict their
        resident columns (membership mass included) from every round."""
        pend, state.pending_dirty = state.pending_dirty, None
        if pend is None or pend.size == 0:
            return
        dirty_full = np.zeros(self._row_cap + 1, dtype=bool)
        dirty_full[pend[pend < self.n_nodes]] = True
        dirty_dev = jnp.asarray(dirty_full)
        if state.dirty_ops is None:
            state.dirty_ops = np.zeros(state.n_ops, dtype=bool)

        new_dirty = np.zeros(state.n_ops, dtype=bool)
        for rnd in state.rounds:
            touched = np.asarray(
                self._touched_fn(rnd.ids, rnd.foot, dirty_dev)
            ) & (rnd.opidx >= 0)
            if touched.any():
                new_dirty[rnd.opidx[touched]] = True
        new_dirty &= ~state.dirty_ops
        if not new_dirty.any():
            return
        for rnd in state.rounds:
            cols = (rnd.opidx >= 0) & new_dirty[np.clip(rnd.opidx, 0, None)]
            if not cols.any():
                continue
            removed_ok = cols & rnd.ok
            if removed_ok.any():
                # Subtract the evicted columns' per-vertex mass so the redo
                # pass can add the re-solved mass back (both int exact).
                mass = self._mass_fn(rnd.member, jnp.asarray(removed_ok))
                state.tm -= np.asarray(
                    self._scatter_rows_fn(rnd.ids, mass)
                )[: self.n_nodes].astype(np.int64)
            keep = jnp.asarray(~cols)
            rnd.member = self._drop_cols_fn(rnd.member, keep)
            rnd.foot = self._drop_cols_fn(rnd.foot, keep)
            rnd.ok &= ~cols
        state.dirty_ops |= new_dirty

    def _redo_dirty(self, ops, state: ResidentReplayState,
                    cross_deg: np.ndarray) -> None:
        """Re-solve the dirty ops on the whole (possibly updated) graph,
        capturing the fresh artifacts as new resident rounds."""
        idx = np.nonzero(state.dirty_ops)[0]
        acc = CounterAccumulator(self._row_cap)
        scratch_cross = np.zeros(state.n_ops, dtype=np.int64)
        n_rounds = len(state.rounds)
        try:
            with tracing.span("sssp.redo"):
                self._run_full_pass(
                    ops, idx, cross_deg, state.per_op_edges, scratch_cross, acc,
                    state=state,
                )
        except Exception:
            # Rounds captured before a mid-pass failure never had their
            # mass folded into tm — keeping them would double-count on a
            # retry's eviction accounting.
            del state.rounds[n_rounds:]
            raise
        state.tm += acc.total[: self.n_nodes]
        state.dirty_ops[:] = False
        self.last_redo_ops = int(idx.shape[0])

    def _resident_state(self, ops) -> ResidentReplayState:
        states: Dict = ops.__dict__.setdefault("_resident_replay", {})
        st = states.get(self)
        if st is not None and st.graph is not self.graph:
            # A store-cached replayer outlives graph revisions, so a log
            # replayed against one revision can meet the same replayer
            # adopted to another (e.g. a fresh run restarting from the
            # base graph after an earlier run grew it). Migration keeps
            # legitimately-grown states in sync (adopt_resident sets
            # state.graph to the adopted graph); anything else is stale
            # — its artifacts belong to a different structure, so start
            # cold rather than fold them.
            st = None
        if st is None:
            st = ResidentReplayState(
                graph=self.graph, pattern=self.engine.pattern, n_ops=ops.n_ops
            )
            states[self] = st
        return st

    def invalidate(self, ops, vertices) -> None:
        """Mark vertices structurally dirty for this log's resident state
        (no-op if the log has never been replayed resident here)."""
        st = ops.__dict__.get("_resident_replay", {}).get(self)
        if st is not None:
            st.mark_dirty(vertices)

    def adopt_resident(self, ops, state: ResidentReplayState,
                       dirty_vertices) -> None:
        """Adopt a resident state solved on a prior revision of this graph.

        The revision may only have *added* structure — edge inserts, and
        appended vertices (existing ids, coordinates, and edges must be
        unchanged) — and every vertex whose incident structure changed
        must be in ``dirty_vertices``. For GIS states, ops whose expansion
        footprint touches a dirty vertex are re-solved on this replayer's
        (new) graph and everything else is provably still bit-exact (see
        the footprint note in
        :func:`repro.core.traffic_batched._sssp_solve_body`; an appended
        vertex is only reachable through its dirty anchors, so it can
        never silently change a cached route). BFS states reset wholesale
        on their next replay — their artifacts are global tree properties
        — but stay adopted so later slices replay resident again.
        """
        if (state.pattern != self.engine.pattern
                or state.graph.n_nodes > self.n_nodes):
            raise ValueError("resident state is incompatible with this replayer")
        if state.n_ops != ops.n_ops:
            raise ValueError("resident state belongs to a different log")
        grown = self.n_nodes - state.graph.n_nodes
        if grown and state.tm is not None:
            # Appended vertices carry zero frontier mass until a redo pass
            # (or BFS cold re-solve) touches them.
            state.tm = np.concatenate(
                [state.tm, np.zeros(grown, dtype=state.tm.dtype)]
            )
        state.graph = self.graph
        state.mark_dirty(dirty_vertices)
        ops.__dict__.setdefault("_resident_replay", {})[self] = state

    # ------------------------------------------------------------------ run
    def replay(
        self,
        ops,
        parts: np.ndarray,
        k: int,
        resident: bool = True,
        replicated: Optional[np.ndarray] = None,
    ):
        """Replay ``ops`` against ``parts``.

        ``resident=True`` keeps/uses the log's parts-independent solve
        artifacts across calls (bit-identical results, see module
        docstring); ``resident=False`` forces a full cold solve with no
        cache reads or writes — the comparator the parity smokes use.

        ``replicated`` masks hot vertices served by local read replicas
        (see ``BatchedTrafficEngine.cross_degree``). Replica-awareness
        enters only through the host-side ``cross_deg`` input and the
        host-side finalize — the sharded compiled closures and the
        resident solve artifacts are untouched, so the hot set can churn
        between replays without a retrace or a resident re-solve.
        """
        with tracing.span("replay"):
            tracing.count("replay.ops", ops.n_ops)
            parts = np.asarray(parts, dtype=np.int64)
            cross_deg = self.engine.cross_degree(parts, replicated=replicated)
            state = self._resident_state(ops) if resident else None
            if self.engine.kind == "bfs":
                edges, cross, tm64 = self._run_bfs(ops, cross_deg, state)
            else:
                edges, cross, tm64 = self._run_sssp(ops, cross_deg, state)
            return self.engine.finalize(edges, cross, tm64, parts, k, ops.t_l, ops.t_pg,
                                        replicated=replicated)


def get_replayer(
    graph: Graph,
    pattern: str,
    mesh: Mesh,
    data_axes: Tuple[str, ...] = ("data",),
    chunk: Optional[int] = None,
    max_expansions: Optional[int] = None,
    delta_scale: Optional[float] = None,
    use_kernel: Optional[bool] = None,
) -> ShardedTrafficReplayer:
    """Replayer cache: store-lifetime for overlay graphs, graph-lifetime
    otherwise (same idiom as ``get_engine``).

    ``max_expansions`` is normalized before keying — ``None`` defers to
    the engine's authoritative default, so a replay without an override
    always lands on the same engine/replayer as the batched path. A
    store-backed graph keys on the store by (pattern, mesh, axes, engine
    params) — capacity is the store's identity — and the cached replayer
    adopts each grown graph in place, so a growth step is a cache *hit*
    and reuses every compiled closure.
    """
    mesh = auto_axes(mesh)
    key = (pattern, mesh, tuple(data_axes), chunk,
           resolve_max_expansions(max_expansions), delta_scale,
           bool(use_kernel))
    store = graph.store
    if store is not None:
        skey = ("replayer",) + key
        rep = store.caches.get(skey)
        if rep is not None:
            rep.adopt_graph(graph)
            if rep.engine._needs_rebuild:
                rep = None
        if rep is None:
            rep = ShardedTrafficReplayer(
                graph, pattern, mesh, data_axes=data_axes, chunk=chunk,
                max_expansions=max_expansions, delta_scale=delta_scale,
                use_kernel=use_kernel,
            )
            store.caches[skey] = rep
        return rep
    cache = graph.__dict__.setdefault("_traffic_replayer_cache", {})
    if key not in cache:
        cache[key] = ShardedTrafficReplayer(
            graph, pattern, mesh, data_axes=data_axes, chunk=chunk,
            max_expansions=max_expansions, delta_scale=delta_scale,
            use_kernel=use_kernel,
        )
    return cache[key]


def migrate_resident_states(
    ops,
    old_graph: Graph,
    new_graph: Graph,
    dirty_vertices,
) -> int:
    """Carry a log's resident replay states across a structural graph update
    (edge inserts, and — the Insert workload — appended vertices).

    For every replayer of ``old_graph`` holding a resident state for
    ``ops``, the state moves to the equivalent replayer of ``new_graph``
    with ``dirty_vertices`` queued for invalidation: GIS states re-solve
    only footprint-touched ops; BFS states re-solve cold on their next
    replay (global tree properties) but stay resident for the slices after
    that. Replayers live in three places — the old graph's own cache
    (storeless growth, and the warmup replay before a store existed), a
    store shared by both graphs (the overlay fast path: the replayer *is*
    the new graph's replayer, it just adopts in place), or an old store a
    compaction retired (the state re-solves on the compacted lineage's
    fresh replayer). Returns the number of states migrated.
    """
    states = ops.__dict__.get("_resident_replay")
    if not states:
        return 0
    moved = 0
    candidates = list(old_graph.__dict__.get("_traffic_replayer_cache", {}).items())
    if old_graph.store is not None:
        for skey, rep in old_graph.store.caches.items():
            if isinstance(skey, tuple) and skey and skey[0] == "replayer":
                candidates.append((skey[1:], rep))
    new_store = new_graph.store
    for key, old_rep in candidates:
        state = states.pop(old_rep, None)
        if state is None:
            continue
        if new_store is not None and old_rep.engine.store is new_store:
            new_rep = old_rep
            new_rep.adopt_graph(new_graph)
        else:
            pattern, mesh, data_axes, chunk, max_exp, delta_scale, use_kernel = key
            new_rep = get_replayer(
                new_graph, pattern, mesh, data_axes=data_axes, chunk=chunk,
                max_expansions=max_exp, delta_scale=delta_scale,
                use_kernel=use_kernel,
            )
        new_rep.adopt_resident(ops, state, dirty_vertices)
        moved += 1
    return moved


def replay_sharded(
    graph: Graph,
    log,
    mesh: Mesh,
    parts: np.ndarray,
    k: Optional[int] = None,
    data_axes: Tuple[str, ...] = ("data",),
    chunk: Optional[int] = None,
    max_expansions: Optional[int] = None,
    delta_scale: Optional[float] = None,
    use_kernel: Optional[bool] = None,
    resident: bool = True,
    replicated: Optional[np.ndarray] = None,
):
    """Replay an evaluation log sharded over ``mesh``'s data axes.

    Bit-equal to ``execute_ops(graph, log, parts, k, engine="batched")`` on
    all four traffic counters; see the module docstring. Replayers are
    cached on the graph (same idiom as ``get_engine``); with ``resident``
    (default) the log's parts-independent solve artifacts stay
    device-resident across calls, so replaying the same log against a new
    partition map costs only the parts-dependent counter fold.
    """
    k = int(np.asarray(parts).max()) + 1 if k is None else k
    replayer = get_replayer(
        graph, log.pattern, mesh, data_axes=data_axes, chunk=chunk,
        max_expansions=max_expansions, delta_scale=delta_scale,
        use_kernel=use_kernel,
    )
    return replayer.replay(log, parts, k, resident=resident, replicated=replicated)
