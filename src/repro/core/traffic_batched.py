"""Batched, JIT-compiled execution of evaluation logs (ISSUE 1 tentpole).

The scalar oracle in :mod:`repro.core.traffic` replays the log one
operation at a time. This engine compiles the whole :class:`OpLog` into
padded device arrays once and advances **all operations together**. Two
execution strategies cover the paper's three patterns:

**Linear BFS sweep (filesystem, Twitter).** A BFS op's frontier at level
``l`` is ``(Aᵀ)^l e_start`` (path multiplicity included), and every traffic
counter is *linear* in it. A filesystem op expands exactly
``L = depth(end) − depth(start)`` levels when ``start`` is a proper
ancestor of ``end`` (the filtered folder→{folder,file} universe is a tree)
and its full subtree otherwise; a Twitter op always expands 2 hops. So the
whole log collapses into closed form:

  per-op:     total[b] = (T_L+T_PG) · P[start_b, L_b],
              P[u, t]  = Σ_{l<t} (A^l deg)(u)   (subtree level-prefix
              tables, one SpMV per level; same table with ``cross_deg``
              for global traffic),
  aggregate:  tm = Σ_t (Aᵀ)^t c_t,  c_t[u] = #{ops: start=u, L>t}
              (a fold of level histograms — one SpMV per level),
              per_vertex = T_L·deg⊙tm + T_PG·(Aᵀ tm).

All ops execute in ``max_depth`` sparse passes **total** — a 1M-op log
costs the same device work as a 100-op log plus two gathers per op.

**Batched windowed SSSP (GIS).** The per-op heapq A* becomes a batched
shortest-path sweep in vertex-major layout ``g [W, chunk]``. One round
relaxes every in-edge of every window vertex for every op at once — a
min-plus gather over a capped padded in-neighbor layout with a
scatter-min spill for over-cap rows. The gather runs through
:func:`repro.kernels.frontier.frontier_relax`: the unrolled-slot XLA form
by default on every backend, the Pallas row-DMA kernel with
``use_kernel=True`` — bit-identical either way (min and float32 add are
exact and slot-order independent). On a TPU v5e the kernel is the slower
of the two at the whole-graph GIS shape (117.55 vs 85.88 ms per relax),
so it stays opt-in until a workload shows it winning. With the default
``delta_scale=None`` each round is a full frontier Bellman–Ford sweep
(minimum rounds on a dense backend); a finite ``delta_scale`` instead
gates relaxation to the op's current distance bucket of width
``Δ = delta_scale × mean edge weight`` — classic delta-stepping buckets,
the work-efficient shape for a future sparse/TPU path. An op retires as
soon as every vertex still awaiting relaxation has tentative distance
beyond its goal (then no pending update can touch its expansion set).

Two locality levers make this fast rather than merely correct:

* ops are sorted by (coarse src grid cell, straight-line src→dst
  distance), so a chunk's wavefronts are geographically coherent and
  finish together;
* each chunk runs on a **window** — the vertices inside the chunk's
  bounding box plus a margin — instead of the whole graph. Exactness is
  *verified, not assumed*: a result is accepted only if the op's A*
  ellipse provably fits inside the window (every vertex ``x`` on a
  shortest path to an expansion-set member satisfies
  ``h(src,x) ≤ f ≤ f_dst``, since road weights ≥ straight-line length, so
  ``disk(src, f_dst) ⊆ window`` suffices); rejected ops — including
  unreachable destinations — are re-solved on the full graph.

The traffic accounting set is the deterministically defined A* expansion
set (see :mod:`repro.core.traffic`): membership and tie-breaks are decided
from final float32 distances computed with the same operation order as the
scalar oracle, so the engines agree **bit-for-bit** on every counter. The
heuristic rows ``h = sqrt(dx·dx + dy·dy)`` are computed inside the solve
from the window's and the destinations' coordinates, never as a ``[W, C]``
input, and are NumPy's float32 on every backend: the squares are kept out
of a fused multiply-add, and a ``sqrt`` that is a few ulps off (a TPU's)
is corrected by an exact integer test of the neighbouring floats
(:func:`_round_sqrt`; the counter ``sssp.heuristic_corrected`` says how
often). An engine-init probe checks the compiled rows against NumPy's and
raises on a mismatch.

All jitted closures and packed layouts are cached on the graph object
(lifetime-tied, as in :mod:`repro.core.didic`) — or, for a growing graph
backed by a delta-overlay :class:`~repro.graphs.structure.GraphStore`, on
the store: device rows/edges are padded to the store capacity with an
inert sentinel tail, graph tables are jit *arguments* rather than baked
constants, and the engine adopts each grown graph by re-uploading buffers
at the frozen shapes, so growth never retraces. Per-log compilation
artifacts (ancestor levels, level histograms, difficulty order) are cached
on the OpLog, keyed by engine structure version. Device counters are int32 (no x64 on the CPU container);
cross-chunk/host accumulation is int64 — a single op would need >2³¹
traffic units to overflow, far beyond the paper's logs.

:mod:`repro.core.traffic_sharded` reuses this engine's compiled layouts
(via :meth:`BatchedTrafficEngine.build_sssp_problem` /
:meth:`~BatchedTrafficEngine.window_accept` / :meth:`~BatchedTrafficEngine.finalize`)
to replay the same log sharded over mesh data axes, bit-exactly.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.graphs.structure import Graph, padded_neighbors
from repro.kernels import resolve_interpret
from repro.kernels.frontier import frontier_relax

__all__ = ["BatchedTrafficEngine", "execute_ops_batched", "get_engine"]

_BIG_ID = np.int32(2**31 - 1)

# Engine-wide default for the A*-expansion-set truncation. Callers that
# don't care pass ``max_expansions=None`` everywhere (engine, sharded
# replayer, resident state) and resolve to this one value — the engine's
# config is authoritative end-to-end, so a non-default engine can never be
# silently paired with a default-capped replay path.
_DEFAULT_MAX_EXPANSIONS = 50_000


def resolve_max_expansions(max_expansions: Optional[int]) -> int:
    """Normalize a ``max_expansions`` override (None → engine default)."""
    return _DEFAULT_MAX_EXPANSIONS if max_expansions is None else int(max_expansions)


def _capped_gather_layout(
    s_loc: np.ndarray, r_loc: np.ndarray, w: np.ndarray, n_rows: int, cap: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Relaxation form of :func:`repro.graphs.structure.padded_neighbors`
    with a slot cap: (nbr, w_inf (+inf padded — the min-plus identity),
    spill_s, spill_r, spill_w). The unrolled relax loop pays ``cap``
    gathers per round for *every* row, so the cap + COO spill tail is what
    makes skewed degree distributions affordable."""
    pn = padded_neighbors(s_loc, r_loc, w, n_rows, cap=cap)
    w_inf = np.where(pn.mask > 0, pn.w, np.float32(np.inf))
    return pn.nbr, w_inf, pn.spill_s, pn.spill_r, pn.spill_w


def _count_window(w_real: int, w_pad: int, full: bool) -> None:
    """Book one packed op chunk's window to the tracing counters."""
    tracing.count("sssp.chunks")
    tracing.count("sssp.window_rows", w_real)
    tracing.count("sssp.window_rows_padded", w_pad)
    tracing.count("sssp.full_windows", int(full))


# ===========================================================================
# Euclidean heuristic rows, bit-equal to NumPy's float32 on every backend
# ===========================================================================
def _above_upper_mid(x_bits, t_bits):
    """``x > m²`` exactly, in int32 arithmetic, where ``m`` is the midpoint
    between the positive normal float32 ``t`` and the next float32 up.

    With ``t = M·2^e`` (``M`` the 24-bit significand), ``m = (2M+1)·2^(e−1)``
    and ``x = X·2^ex`` lies above ``m²`` iff ``X·2^j > ⌊M(M+1)/2^22⌋`` with
    ``j = ex − 2e − 22`` (``m²`` is never a float32, so there is no tie).
    For ``j`` in 0..2 both sides are integers below 2^26; ``j ≥ 3`` is
    always above and ``j < 0`` never. ``M(M+1)`` (48 bits) is assembled
    from the 12-bit halves of ``M`` so that no product exceeds 2^25.
    """
    m = (t_bits & 0x7FFFFF) | 0x800000
    mh, ml = m >> 12, m & 0xFFF
    p = 2 * mh * ml
    n22 = 4 * mh * mh + (p >> 10) + ((((p & 0x3FF) << 12) + ml * ml + m) >> 22)
    j = (x_bits >> 23) - 2 * (t_bits >> 23) + 128  # biased exponents
    mx = (x_bits & 0x7FFFFF) | 0x800000
    return (j >= 3) | ((j >= 0) & ((mx << jnp.clip(j, 0, 2)) > n22))


# Widest error of a backend's float32 ``sqrt``, in ulps, that the rounding
# step corrects. A TPU v5e's is off by up to 3 ulps, in 40 % of all float32
# values of [2⁻⁴⁰, 2⁸) (measured on the chip); XLA's CPU ``sqrt`` by none.
_SQRT_ULPS = 4


def _round_sqrt(x, s):
    """The correctly rounded float32 ``sqrt(x)`` (what NumPy returns), from
    ``s`` within ``_SQRT_ULPS`` ulps of it.

    Each candidate ``c`` from ``s − K`` to ``s + K − 1`` ulps (a positive
    float's bit pattern ± 1 is its ``nextafter``) is tested exactly: ``x``
    lies above the square of the midpoint between ``c`` and the next float
    up iff ``c`` is below the correctly rounded root, so the root is
    ``s − K`` plus the number of such candidates. Where ``sqrt`` already
    rounds correctly, as XLA's does on the CPU, the result is ``s``.

    ``x`` is ``dx² + dy²`` of map coordinate differences: zero, or far
    above the float32 subnormals (two distinct Romanian longitudes, near
    20–30°, differ by at least 2⁻¹⁹, so ``x ≥ 2⁻³⁸``; subnormals start at
    2⁻¹²⁶). A backend that flushes subnormals to zero therefore cannot
    change the result."""
    xb = jax.lax.bitcast_convert_type(x, jnp.int32)
    lo = jax.lax.bitcast_convert_type(s, jnp.int32) - _SQRT_ULPS
    below = sum(_above_upper_mid(xb, lo + i).astype(jnp.int32)
                for i in range(2 * _SQRT_ULPS))
    return jnp.where(x > 0, jax.lax.bitcast_convert_type(lo + below, jnp.float32),
                     jnp.float32(0))


def _heuristic_rows(lon_w, lat_w, dst_lon, dst_lat):
    """``[W, C]`` Euclidean heuristic ``sqrt(dx·dx + dy·dy)`` from every
    window row to every op's destination, bit-equal to NumPy's float32
    (:meth:`BatchedTrafficEngine._host_h`), and the ``[W, C]`` mask of the
    entries where the backend's own ``sqrt`` was off."""
    dx = lon_w[:, None] - dst_lon[None, :]
    dy = lat_w[:, None] - dst_lat[None, :]
    # NumPy rounds each square and then the sum. ``maximum(·, 0)``, the
    # identity on a square, keeps the compiler from contracting a square
    # and the sum into one fused multiply-add, which rounds once (XLA's CPU
    # backend does so, and then differs from NumPy in one entry of ten).
    x = jnp.maximum(dx * dx, 0.0) + jnp.maximum(dy * dy, 0.0)
    s = jnp.sqrt(x)
    h = _round_sqrt(x, s)
    return h, h != s


# ===========================================================================
# Windowed batched SSSP solve (pure function: jit caches per window shape)
# ===========================================================================
def _sssp_solve_body(
    starts,        # [C] int32 local src index
    ends,          # [C] int32 local dst index
    dst_ids,       # [C] int32 *global* dst vertex id (lex tie-break)
    valid,         # [C] bool
    deg_w,         # [W] int32 global degree, window rows
    cross_w,       # [W] int32 global cross-degree, window rows
    ids_w,         # [W] int32 global vertex ids (ascending; _BIG_ID padding)
    nbr,           # [W, D] int32 local in-neighbor ids (D capped)
    w_inf,         # [W, D] float32 edge weights (+inf where padded)
    spill_s,       # [S] int32 local senders of over-cap edges (0 padded)
    spill_r,       # [S] int32 local receivers of over-cap edges
    spill_w,       # [S] float32 weights (+inf where padded)
    lon_w,         # [W] float32 window rows' coordinates (0 padded)
    lat_w,         # [W] float32
    dst_lon,       # [C] float32 each op's destination coordinates
    dst_lat,       # [C] float32
    delta,         # f32 scalar bucket width (ignored unless finite_delta)
    max_expansions: int,
    finite_delta: bool,
    use_kernel: bool = False,
    interpret: bool = True,
):
    """Traceable solve body — shared verbatim by the single-device jit
    below and the per-shard ``shard_map`` body in
    :mod:`repro.core.traffic_sharded`, so both paths run the exact same
    float32 operations. Returns ``(member, foot, edges, cross, f_dst,
    done, rounds, corrected)``; ``rounds`` is the number of relax sweeps
    run, ``corrected`` the number of heuristic entries (real rows, valid
    ops) where the backend's ``sqrt`` needed the rounding step. The
    heuristic rows are computed here, where ``f`` needs them, from the
    ``[W]`` and ``[C]`` coordinates: no ``[W, C]`` array is an input."""
    w_nodes, c = lon_w.shape[0], starts.shape[0]
    cols = jnp.arange(c)
    inf = jnp.float32(jnp.inf)
    max_rounds = 4 * w_nodes + 16

    g0 = jnp.full((w_nodes, c), inf).at[starts, cols].set(
        jnp.where(valid, jnp.float32(0.0), inf)
    )
    need0 = jnp.zeros((w_nodes, c), bool).at[starts, cols].set(valid)
    t0 = jnp.full((c,), delta)
    done0 = ~valid

    def relax(gm):
        """One min-plus sweep over the capped in-neighbor layout + COO
        spill tail — the ``kernels/frontier`` relaxation primitive (Pallas
        kernel when ``use_kernel``, unrolled-slot XLA gather otherwise)."""
        return frontier_relax(
            gm, nbr, w_inf, spill_s, spill_r, spill_w,
            use_kernel=use_kernel, interpret=interpret,
        )

    def step(g, need, t, done):
        if finite_delta:
            # Delta-stepping: relax only needs-relax nodes in the current
            # bucket; drained buckets advance to the next nonempty one.
            in_bucket = need & (g <= t[None, :]) & (~done)[None, :]
            any_f = in_bucket.any(axis=0)
            gm = jnp.where(in_bucket, g, inf)
        else:
            # Frontier Bellman–Ford: every vertex re-offers its current
            # value; pending work is exactly "improved last round".
            gm = jnp.where(done[None, :], inf, g)
        relaxed = relax(gm)
        improved = relaxed < g
        g = jnp.minimum(g, relaxed)
        if finite_delta:
            need = (need & ~in_bucket) | improved
        else:
            need = improved
        # Retire ops whose every pending vertex is beyond the goal: no
        # remaining update can reach their expansion set.
        g_need = jnp.where(need, g, inf)
        min_need = g_need.min(axis=0)
        g_dst = g[ends, cols]
        done = done | (min_need > g_dst) | ~need.any(axis=0)
        if finite_delta:
            t = jnp.where(~any_f & ~done, min_need + delta, t)
        return g, need, t, done

    def cond(state):
        _, _, _, done, rounds = state
        return jnp.logical_and(jnp.any(~done), rounds < max_rounds)

    def body(state):
        g, need, t, done, rounds = state
        g, need, t, done = step(g, need, t, done)
        g, need, t, done = step(g, need, t, done)
        return g, need, t, done, rounds + 2

    g, _, _, done, rounds = jax.lax.while_loop(
        cond, body, (g0, need0, t0, done0, jnp.int32(0))
    )

    # Deterministic A* expansion set: (f, id) <_lex (f_dst, dst).
    h, moved = _heuristic_rows(lon_w, lat_w, dst_lon, dst_lat)
    corrected = (moved & (ids_w < _BIG_ID)[:, None] & valid[None, :]).sum(dtype=jnp.int32)
    f = g + h
    f_dst = f[ends, cols]
    member = (f < f_dst[None, :]) | (
        (f == f_dst[None, :]) & (ids_w[:, None] < dst_ids[None, :])
    )
    member = member & jnp.isfinite(f) & valid[None, :]
    # Invalidation footprint for the resident replay path: every vertex
    # with f ≤ f_dst (boundary *included*, cap *not* applied). With road
    # weights ≥ straight-line length, any inserted edge that could change
    # this op's distances-to-members, its f_dst, a membership tie-break,
    # or the max_expansions ranking has an endpoint inside this set — so
    # "footprint ∩ dirty = ∅" proves the cached solve stays bit-exact.
    foot = (f <= f_dst[None, :]) & jnp.isfinite(f) & valid[None, :]
    if w_nodes > max_expansions:
        # Keep the max_expansions lex-smallest members: stable argsort of f
        # ties by row position; rows ascend in global id, i.e. (f, id) order.
        key = jnp.where(member, f, inf)
        order = jnp.argsort(key, axis=0, stable=True)
        rank = jnp.zeros((w_nodes, c), jnp.int32).at[order, cols[None, :]].set(
            jnp.broadcast_to(jnp.arange(w_nodes, dtype=jnp.int32)[:, None], (w_nodes, c))
        )
        member = member & (rank < max_expansions)

    m = member.astype(jnp.int32)
    edges = (m * deg_w[:, None]).sum(axis=0)
    cross = (m * cross_w[:, None]).sum(axis=0)
    return member, foot, edges, cross, f_dst, done, rounds, corrected


_sssp_solve = jax.jit(
    _sssp_solve_body,
    static_argnames=("max_expansions", "finite_delta", "use_kernel", "interpret"),
)


class BatchedTrafficEngine:
    """One compiled engine per (graph, pattern); see module docstring."""

    def __init__(
        self,
        graph: Graph,
        pattern: str,
        chunk: Optional[int] = None,
        max_expansions: Optional[int] = None,
        delta_scale: Optional[float] = None,
        use_kernel: Optional[bool] = None,
    ):
        from repro.core import traffic as _t  # late: traffic imports us lazily

        self.pattern = pattern
        self.max_expansions = resolve_max_expansions(max_expansions)
        # Relaxation path: unrolled XLA gather unless the caller asks for
        # the Pallas frontier kernel. Both resolved once, here — never at
        # trace time.
        self.use_kernel = bool(use_kernel)
        self.interpret = resolve_interpret()

        if pattern in ("filesystem", "twitter"):
            self.kind = "bfs"
        elif pattern in ("gis_short", "gis_long"):
            self.kind = "sssp"
        else:
            raise ValueError(f"unknown pattern {pattern!r}")

        # Delta-overlay capacity: a store-backed graph gets device rows
        # padded to ``n_cap`` plus one dead sentinel row (index ``n_cap``)
        # and edge slots padded to ``e_cap`` with dead edges pointing at
        # the sentinel, so every compiled shape is growth-invariant. A
        # storeless graph keeps exact logical shapes (legacy behavior).
        store = graph.store
        self.store = store
        self._n_rows = (store.n_cap + 1) if store is not None else graph.n_nodes
        self._e_cap = store.e_cap if store is not None else None
        self._struct_version = 0
        self._needs_rebuild = False

        if self.kind == "bfs":
            self.chunk = chunk
            # Frozen trace-time level count. Store engines reserve one
            # extra level: filesystem growth attaches files under existing
            # folders, so future depths stay <= max folder depth + 1 and
            # the slack level (inert: zero histogram rows, saturated
            # prefixes) keeps results bit-identical to an exact-level
            # rebuild while the compiled sweep survives growth.
            if pattern == "twitter":
                self.max_levels = 2
            else:
                self.max_levels = int(graph.node_attrs["depth"].max()) + (
                    3 if store is not None else 2
                )
            self._run_fn = jax.jit(self._bfs_linear)
        else:
            self.chunk = chunk or 128
            self.delta_scale = delta_scale
            self._full_layout = None
            self.nbr_cap = None  # frozen on first structure load below

        self._load_structure(graph)
        if self.kind == "sssp":
            self._check_device_h()

    def _load_structure(self, graph: Graph) -> None:
        """(Re)load host truth + capacity-padded device buffers from
        ``graph``. Called at construction and by :meth:`adopt` after each
        growth step — a pure host rebuild + H2D refresh, no retracing."""
        from repro.core import traffic as _t

        self.graph = graph
        self.n_nodes = graph.n_nodes
        if self.pattern == "filesystem":
            s, r = _t._filtered_children_csr_edges(graph)
            self.w = None
        elif self.pattern == "twitter":
            s, r = graph.senders, graph.receivers
            self.w = None
        else:
            s, r, w = graph.undirected
            self.w = np.asarray(w, dtype=np.float32)

        self.s = np.asarray(s, dtype=np.int64)
        self.r = np.asarray(r, dtype=np.int64)
        self.deg = np.bincount(self.s, minlength=self.n_nodes).astype(np.int32)

        if self.kind == "sssp":
            self._lon = np.asarray(graph.node_attrs["lon"], dtype=np.float32)
            self._lat = np.asarray(graph.node_attrs["lat"], dtype=np.float32)
            mean_w = float(self.w.mean()) if self.w.size else 1.0
            self.mean_w = mean_w
            self.delta = (
                np.float32(np.inf)
                if self.delta_scale is None
                else np.float32(max(mean_w * self.delta_scale, 1e-6))
            )
            if self.nbr_cap is None:
                # Frozen: the cap only splits edges between the padded
                # gather and the exact COO spill, so results never depend
                # on it — refreshing it would only churn compiled shapes.
                pos_deg = self.deg[self.deg > 0]
                self.nbr_cap = max(
                    4, int(np.percentile(pos_deg, 90)) if pos_deg.size else 4
                )
            self._glob2loc = np.full(self.n_nodes, -1, dtype=np.int64)
            self._full_layout = None
        else:
            if self._e_cap is not None:
                if self.s.shape[0] > self._e_cap:
                    raise ValueError("BFS edge set exceeds store edge capacity")
                dead = np.int32(self._n_rows - 1)
                s_pad = np.full(self._e_cap, dead, dtype=np.int32)
                r_pad = np.full(self._e_cap, dead, dtype=np.int32)
                s_pad[: self.s.shape[0]] = self.s
                r_pad[: self.r.shape[0]] = self.r
                self._s_j = jnp.asarray(s_pad)
                self._r_j = jnp.asarray(r_pad)
            else:
                self._s_j = jnp.asarray(self.s, dtype=jnp.int32)
                self._r_j = jnp.asarray(self.r, dtype=jnp.int32)
            self._deg_j = jnp.asarray(self._pad_rows(self.deg))

    def _pad_rows(self, vec: np.ndarray) -> np.ndarray:
        """Zero-pad a logical per-vertex vector to the device row count."""
        if self._n_rows == vec.shape[0]:
            return vec
        out = np.zeros((self._n_rows,) + vec.shape[1:], dtype=vec.dtype)
        out[: vec.shape[0]] = vec
        return out

    def adopt(self, graph: Graph) -> None:
        """Adopt a grown graph from the same store lineage in place.

        Device buffers are re-uploaded at the frozen capacity shapes, so
        every jitted closure compiled against this engine keeps its
        trace. Sets ``_needs_rebuild`` (checked by :func:`get_engine`)
        in the off-contract case where the grown graph no longer fits
        the frozen trace parameters."""
        if graph is self.graph:
            return
        if self.store is None or graph.store is not self.store:
            raise ValueError("adopt requires a graph sharing this engine's store")
        if self.kind == "bfs" and self.pattern == "filesystem":
            required = int(graph.node_attrs["depth"].max()) + 2
            if required > self.max_levels:
                self._needs_rebuild = True
        self._struct_version += 1
        self._load_structure(graph)

    # =================================================== linear BFS patterns
    def _spmv_down(self, x: jnp.ndarray, s_j, r_j) -> jnp.ndarray:
        """(A x)(u) = Σ_{u→c} x(c) — pull child values up one level.

        Dead (capacity-padding) edges have ``s = r = `` the sentinel row,
        whose value is identically zero, so they add nothing anywhere."""
        return jnp.zeros_like(x).at[s_j].add(x[r_j])

    def _bfs_prefix_one(self, vec, s_j, r_j):
        """Level-prefix table ``[N, t+1]`` for one counter vector — the
        single-column form of :meth:`_bfs_prefix_table`. The sharded
        replayer uses it to keep the graph-pure deg column device-resident
        and rebuild only the parts-dependent cross column per replay.

        Graph tables are explicit arguments (not closed-over constants)
        so a persistent jit of this function survives overlay growth."""
        t = self.max_levels
        prefixes = [jnp.zeros_like(vec)]
        level_vec = vec
        for _ in range(t):
            prefixes.append(prefixes[-1] + level_vec)
            level_vec = self._spmv_down(level_vec, s_j, r_j)
        return jnp.stack(prefixes, axis=1)

    def _bfs_prefix_table(self, cross_deg, s_j, r_j, deg_j):
        """Level-prefix tables ``P[u, l, :]`` for deg and cross_deg
        simultaneously — ops-independent, so the sharded replayer builds it
        once and replicates it across the mesh."""
        t = self.max_levels
        vec = jnp.stack([deg_j, cross_deg], axis=1)  # [N, 2]
        prefixes = [jnp.zeros_like(vec)]
        level_vec = vec
        for _ in range(t):
            prefixes.append(prefixes[-1] + level_vec)
            level_vec = jnp.stack(
                [self._spmv_down(level_vec[:, 0], s_j, r_j),
                 self._spmv_down(level_vec[:, 1], s_j, r_j)], axis=1
            )
        return jnp.stack(prefixes, axis=1)  # [N, t+1, 2]

    def _bfs_linear(self, starts, levels, cross_deg, s_j, r_j, deg_j):
        """Closed-form multi-source level-synchronous sweep (module doc).

        Per-op values stay int32 on device (bounded by a single op's
        traffic, < 2³¹ by the module contract); the whole-log aggregate
        fold lives in :meth:`_run_bfs` in host int64, where a million-op
        log summed into one hub vertex cannot wrap.
        """
        p = self._bfs_prefix_table(cross_deg, s_j, r_j, deg_j)
        per_op = p[starts, levels]       # [n_ops, 2]
        return per_op[:, 0], per_op[:, 1]

    def bfs_log(self, ops) -> Tuple[np.ndarray, np.ndarray]:
        """:meth:`_compile_bfs_log` under the span ``bfs.compile_log``;
        books the log's expansion levels (``bfs.levels``) and the ops whose
        end lies in their start's subtree (``bfs.targets_found``)."""
        with tracing.span("bfs.compile_log"):
            levels, c_stack, found = self._compile_bfs_log(ops)
        tracing.count("bfs.levels", int(levels.sum(dtype=np.int64)))
        tracing.count("bfs.targets_found", found)
        return levels, c_stack

    def _compile_bfs_log(self, ops) -> Tuple[np.ndarray, np.ndarray, int]:
        """Per-op expansion levels, per-level start histograms and the
        number of ops that find their end (cached per engine structure
        version — growth invalidates the entry)."""
        cache = ops.__dict__.setdefault("_bfs_compile_cache", {})
        ckey = (self, self._struct_version)
        if ckey in cache:
            return cache[ckey]
        t = self.max_levels
        n_ops = ops.n_ops
        starts = ops.starts.astype(np.int64)
        if self.pattern == "twitter":
            levels = np.full(n_ops, 2, dtype=np.int64)
            found = 0
        else:
            depth = self.graph.node_attrs["depth"].astype(np.int64)
            parent = self.graph.node_attrs["parent"].astype(np.int64)
            l_raw = depth[ops.ends] - depth[starts]
            cur = ops.ends.astype(np.int64).copy()
            steps = np.maximum(l_raw, 0).copy()
            for _ in range(int(depth.max()) + 1):
                walk = steps > 0
                cur = np.where(walk & (parent[cur] >= 0), parent[cur], cur)
                steps = np.maximum(steps - 1, 0)
            is_descendant = (l_raw > 0) & (cur == starts)
            levels = np.where(is_descendant, np.minimum(l_raw, t), t)
            found = int(np.count_nonzero(is_descendant))
        # c_stack[l, u] = #ops with start u still expanding at level l (L > l).
        hist = np.zeros((t + 1, self.n_nodes), dtype=np.int32)
        np.add.at(hist, (np.minimum(levels, t) - 1, starts), 1)
        c_stack = hist[::-1].cumsum(axis=0)[::-1].copy()[:t]
        out = (levels.astype(np.int32), c_stack, found)
        cache[ckey] = out
        return out

    def _run_bfs(self, ops, cross_deg: np.ndarray):
        levels, c_stack = self.bfs_log(ops)
        edges, cross = self._run_fn(
            jnp.asarray(ops.starts.astype(np.int32)),
            jnp.asarray(levels),
            jnp.asarray(self._pad_rows(cross_deg)),
            self._s_j, self._r_j, self._deg_j,
        )
        # tm = Σ_l (Aᵀ)^l c_l, inner-to-outer fold in host int64: the whole
        # log accumulates into single vertices here, so int32 could wrap.
        # Recomputed every replay on purpose: this engine is the reference
        # loop; cross-replay frontier-mass residency is the device
        # runtime's job (see traffic_sharded._run_bfs).
        t = self.max_levels
        tm = c_stack[t - 1].astype(np.int64)
        for lvl in range(t - 2, -1, -1):
            push = np.zeros(self.n_nodes, dtype=np.int64)
            np.add.at(push, self.r, tm[self.s])
            tm = c_stack[lvl].astype(np.int64) + push
        return (
            np.asarray(edges, dtype=np.int64),
            np.asarray(cross, dtype=np.int64),
            tm,
        )

    # ====================================================== GIS batched SSSP
    def _check_device_h(self) -> None:
        """Init probe: the solve's heuristic rows, compiled for this
        backend, against NumPy's on this graph's coordinates. There is no
        other path to fall back on, so a mismatch is an error."""
        probe = np.arange(min(self.n_nodes, 64), dtype=np.int64)
        window = np.arange(min(self.n_nodes, 4096), dtype=np.int64)
        host = self._host_h(window, probe)
        dev = np.asarray(
            _device_h(jnp.asarray(self._lon[window]), jnp.asarray(self._lat[window]),
                      jnp.asarray(self._lon[probe]), jnp.asarray(self._lat[probe]))[0]
        )
        bad = int(np.sum(host.view(np.int32) != dev.view(np.int32)))
        if bad:
            raise RuntimeError(
                f"the {jax.default_backend()} backend's heuristic rows differ from "
                f"NumPy's float32 in {bad} of {host.size} probe entries"
            )

    def _host_h(self, window: np.ndarray, ends: np.ndarray) -> np.ndarray:
        dx = self._lon[window][:, None] - self._lon[ends][None, :]
        dy = self._lat[window][:, None] - self._lat[ends][None, :]
        return np.sqrt(dx * dx + dy * dy)  # [W, C]

    def _compile_sssp_log(self, ops) -> np.ndarray:
        """Difficulty order: (coarse src cell, straight-line distance)."""
        cache = ops.__dict__.setdefault("_sssp_compile_cache", {})
        ckey = (self, self._struct_version)
        if ckey in cache:
            return cache[ckey]
        hd = np.hypot(
            self._lon[ops.starts].astype(np.float64) - self._lon[ops.ends],
            self._lat[ops.starts].astype(np.float64) - self._lat[ops.ends],
        )
        lon_span = max(float(self._lon.max() - self._lon.min()), 1e-9)
        lat_span = max(float(self._lat.max() - self._lat.min()), 1e-9)
        cx = np.clip(((self._lon[ops.starts] - self._lon.min()) / lon_span * 8), 0, 7).astype(np.int64)
        cy = np.clip(((self._lat[ops.starts] - self._lat.min()) / lat_span * 8), 0, 7).astype(np.int64)
        order = np.lexsort((hd, cx * 8 + cy))
        cache[ckey] = order
        return order

    def _sssp_window(
        self, srcs: np.ndarray, dsts: np.ndarray, full: bool
    ) -> Tuple[np.ndarray, Tuple[float, float, float, float]]:
        if full:
            return np.arange(self.n_nodes, dtype=np.int64), (
                -np.inf, np.inf, -np.inf, np.inf
            )
        pts_lon = np.concatenate([self._lon[srcs], self._lon[dsts]]).astype(np.float64)
        pts_lat = np.concatenate([self._lat[srcs], self._lat[dsts]]).astype(np.float64)
        h_max = float(
            np.hypot(self._lon[srcs].astype(np.float64) - self._lon[dsts],
                     self._lat[srcs].astype(np.float64) - self._lat[dsts]).max()
        )
        margin = 1.15 * h_max + 6.0 * self.mean_w + 0.01
        lo_x, hi_x = pts_lon.min() - margin, pts_lon.max() + margin
        lo_y, hi_y = pts_lat.min() - margin, pts_lat.max() + margin
        mask = (
            (self._lon >= lo_x) & (self._lon <= hi_x)
            & (self._lat >= lo_y) & (self._lat <= hi_y)
        )
        return np.nonzero(mask)[0], (lo_x, hi_x, lo_y, hi_y)

    def ensure_full_layout(self):
        """Whole-graph gather layout ``(w_pad, nbr, w_inf, sp_s, sp_r,
        sp_w, ids_w, deg_w, lon_w, lat_w)`` — parts/ops independent, built once and
        shared by the single-device redo pass and the sharded replayer's
        replicated device-resident copy."""
        if self._full_layout is None:
            self.build_sssp_problem(
                np.zeros(1, np.int64), np.zeros(1, np.int64),
                np.zeros(1, bool), np.zeros(self.n_nodes, np.int32), full=True,
            )
        return self._full_layout

    def full_per_op(self, srcs: np.ndarray, dsts: np.ndarray, valid: np.ndarray):
        """Per-op columns ``(loc_src, loc_dst, dst_ids, dst_lon, dst_lat)``
        for the whole-graph window — the slim form of
        ``build_sssp_problem(full=True)`` for callers that already hold
        the shared layout (:meth:`ensure_full_layout`): no O(N) window
        enumeration or cross_w rebuild per chunk.
        """
        w_pad = self.ensure_full_layout()[0]
        with tracing.span("sssp.window_build"):
            _count_window(self.n_nodes, w_pad, True)
            loc_src = np.where(valid, srcs, 0).astype(np.int32)
            loc_dst = np.where(valid, dsts, 0).astype(np.int32)
            dst_safe = np.where(valid, dsts, 0)
            with tracing.span("sssp.heuristic"):
                dst_lon, dst_lat = self._lon[dst_safe], self._lat[dst_safe]
        return loc_src, loc_dst, dst_safe.astype(np.int32), dst_lon, dst_lat

    @tracing.span("sssp.window_build")
    def build_sssp_problem(
        self,
        srcs: np.ndarray,
        dsts: np.ndarray,
        valid: np.ndarray,
        cross_deg: np.ndarray,
        full: bool,
    ):
        """Host-side packing of one op chunk into a solver problem.

        Returns ``(args, window, w_real, box, full)`` where ``args`` is the
        positional-argument tuple of :func:`_sssp_solve_body` up to and
        including ``dst_lat`` (everything shape-dependent), all host
        arrays. ``full`` is returned because a near-full window is
        promoted to the whole graph here.
        """
        with tracing.span("sssp.window_select"):
            window, box = self._sssp_window(srcs[valid], dsts[valid], full)
            if not full and window.shape[0] > 0.6 * self.n_nodes:
                # Near-full window: run on the whole graph outright — cheaper
                # than risking a second (redo) pass for rejected ops.
                full = True
                window, box = self._sssp_window(srcs, dsts, True)
        w_real = window.shape[0]
        if full and self._full_layout is not None:
            # The whole-graph layout is parts/ops independent — built once.
            w_pad, nbr, w_inf, sp_s, sp_r, sp_w, ids_w, deg_w, lon_w, lat_w = self._full_layout
        else:
            with tracing.span("sssp.gather_layout"):
                # Pad to a {2^k, 3·2^k} size grid: bounded jit-cache variants
                # with ≤ 33 % padding waste (pure 2^k padding wastes up to 2×).
                p2 = max(64, 1 << int(np.ceil(np.log2(max(w_real, 1)))))
                w_pad = 3 * p2 // 4 if w_real <= 3 * p2 // 4 else p2
                self._glob2loc[window] = np.arange(w_real)
                if full:
                    es, er, ew = self.s, self.r, self.w
                else:
                    e_mask = (self._glob2loc[self.s] >= 0) & (self._glob2loc[self.r] >= 0)
                    es, er, ew = self.s[e_mask], self.r[e_mask], self.w[e_mask]
                nbr, w_inf, sp_s, sp_r, sp_w = _capped_gather_layout(
                    self._glob2loc[es], self._glob2loc[er], ew, w_pad, self.nbr_cap
                )
                s_pad = 0 if sp_s.shape[0] == 0 else max(
                    64, 1 << int(np.ceil(np.log2(sp_s.shape[0])))
                )
                if s_pad:
                    fill = s_pad - sp_s.shape[0]
                    sp_s = np.concatenate([sp_s, np.zeros(fill, np.int32)])
                    sp_r = np.concatenate([sp_r, np.zeros(fill, np.int32)])
                    sp_w = np.concatenate([sp_w, np.full(fill, np.inf, np.float32)])
                ids_w = np.full(w_pad, _BIG_ID, dtype=np.int32)
                ids_w[:w_real] = window.astype(np.int32)
                deg_w = np.zeros(w_pad, dtype=np.int32)
                deg_w[:w_real] = self.deg[window]
                lon_w = np.zeros(w_pad, dtype=np.float32)
                lon_w[:w_real] = self._lon[window]
                lat_w = np.zeros(w_pad, dtype=np.float32)
                lat_w[:w_real] = self._lat[window]
                self._glob2loc[window] = -1  # restore the scratch map
                if full:
                    self._full_layout = (w_pad, nbr, w_inf, sp_s, sp_r, sp_w, ids_w, deg_w,
                                         lon_w, lat_w)
        if valid.any():
            _count_window(w_real, w_pad, full)

        cross_w = np.zeros(w_pad, dtype=np.int32)
        cross_w[:w_real] = cross_deg[window]

        if full:
            loc_src = np.where(valid, srcs, 0).astype(np.int32)
            loc_dst = np.where(valid, dsts, 0).astype(np.int32)
        else:
            self._glob2loc[window] = np.arange(w_real)
            loc_src = np.where(valid, self._glob2loc[srcs], 0).astype(np.int32)
            loc_dst = np.where(valid, self._glob2loc[dsts], 0).astype(np.int32)
            self._glob2loc[window] = -1  # restore the scratch map
        dst_safe = np.where(valid, dsts, 0)
        with tracing.span("sssp.heuristic"):
            dst_lon, dst_lat = self._lon[dst_safe], self._lat[dst_safe]

        args = (
            loc_src, loc_dst, dst_safe.astype(np.int32),
            valid, deg_w, cross_w, ids_w,
            nbr, w_inf, sp_s, sp_r, sp_w, lon_w, lat_w, dst_lon, dst_lat,
        )
        return args, window, w_real, box, full

    def window_accept(
        self,
        srcs: np.ndarray,
        dsts: np.ndarray,
        valid: np.ndarray,
        f_dst: np.ndarray,
        box,
        full: bool,
    ) -> np.ndarray:
        """Exactness gate: accept only ops whose A* ellipse provably fits
        the window — disk(src, f_dst) ∪ disk(dst, f_dst) inside the box
        (with a small safety factor over float32 rounding). Host-side in
        float64 on purpose: a float32 false-accept would silently break
        the bit-exactness contract, a false-reject only costs a redo."""
        if full:
            return valid.copy()
        lo_x, hi_x, lo_y, hi_y = box
        rad = np.asarray(f_dst, dtype=np.float64) * 1.00001 + 1e-6
        sx = self._lon[srcs].astype(np.float64)
        sy = self._lat[srcs].astype(np.float64)
        tx = self._lon[dsts].astype(np.float64)
        ty = self._lat[dsts].astype(np.float64)
        return (
            valid & np.isfinite(f_dst)
            & (sx - rad >= lo_x) & (sx + rad <= hi_x)
            & (sy - rad >= lo_y) & (sy + rad <= hi_y)
            & (tx - rad >= lo_x) & (tx + rad <= hi_x)
            & (ty - rad >= lo_y) & (ty + rad <= hi_y)
        )

    def _solve_sssp_chunk(
        self,
        srcs: np.ndarray,
        dsts: np.ndarray,
        valid: np.ndarray,
        cross_deg: np.ndarray,
        full: bool,
    ):
        """Solve one op chunk on its locality window; returns host arrays
        (member [W, C] bool over window rows, edges/cross [C], ok [C])."""
        args, window, w_real, box, full = self.build_sssp_problem(
            srcs, dsts, valid, cross_deg, full
        )
        with tracing.span("sssp.stack"):
            dev_args = [jnp.asarray(a) for a in args]
        with tracing.span("sssp.solve"):
            member, _foot, edges, cross, f_dst, done, rounds, corrected = jax.device_get(_sssp_solve(
                *dev_args,
                jnp.float32(self.delta),
                max_expansions=self.max_expansions,
                finite_delta=self.delta_scale is not None,
                use_kernel=self.use_kernel,
                interpret=self.interpret,
            ))
        tracing.count("sssp.op_solves", int(valid.sum()))
        tracing.count("sssp.relax_rounds", int(rounds))
        tracing.count("sssp.heuristic_corrected", int(corrected))
        member = np.asarray(member)
        edges = np.asarray(edges, dtype=np.int64)
        cross = np.asarray(cross, dtype=np.int64)
        f_dst = np.asarray(f_dst, dtype=np.float64)
        if not np.asarray(done).all():
            # The while_loop's max_rounds backstop tripped (pathological Δ
            # or graph): distances may be under-relaxed — never silently
            # return wrong counters.
            raise RuntimeError(
                "batched SSSP hit its round cap before all ops settled; "
                "raise delta_scale (or use delta_scale=None)"
            )
        with tracing.span("sssp.accept"):
            ok = self.window_accept(srcs, dsts, valid, f_dst, box, full)
        return window, w_real, member, edges, cross, ok

    def _run_sssp(self, ops, cross_deg: np.ndarray):
        with tracing.span("sssp.order"):
            order = self._compile_sssp_log(ops)
        n_ops = ops.n_ops
        chunk = self.chunk
        per_op_edges = np.zeros(n_ops, dtype=np.int64)
        per_op_cross = np.zeros(n_ops, dtype=np.int64)
        tm64 = np.zeros(self.n_nodes, dtype=np.int64)
        redo: List[np.ndarray] = []

        def run_pass(op_idx: np.ndarray, full: bool) -> None:
            for lo in range(0, op_idx.shape[0], chunk):
                idx = op_idx[lo:lo + chunk]
                pad = chunk - idx.shape[0]
                srcs = np.concatenate([ops.starts[idx], np.zeros(pad, np.int64)])
                dsts = np.concatenate([ops.ends[idx], np.zeros(pad, np.int64)])
                valid = np.concatenate([np.ones(idx.shape[0], bool), np.zeros(pad, bool)])
                window, w_real, member, edges, cross, ok = self._solve_sssp_chunk(
                    srcs, dsts, valid, cross_deg, full
                )
                accepted = idx[ok[:idx.shape[0]]]
                per_op_edges[accepted] = edges[:idx.shape[0]][ok[:idx.shape[0]]]
                per_op_cross[accepted] = cross[:idx.shape[0]][ok[:idx.shape[0]]]
                tm64[window] += member[:w_real][:, ok].sum(axis=1)
                if not full:
                    rejected = idx[~ok[:idx.shape[0]]]
                    if rejected.size:
                        redo.append(rejected)

        run_pass(order, full=False)
        tracing.count("sssp.redo_ops", sum(r.shape[0] for r in redo))
        if redo:
            with tracing.span("sssp.redo"):
                run_pass(np.concatenate(redo), full=True)
        return per_op_edges, per_op_cross, tm64

    # ------------------------------------------------------------------ run
    @tracing.span("fold.cross_degree")
    def cross_degree(
        self, parts: np.ndarray, replicated: Optional[np.ndarray] = None
    ) -> np.ndarray:
        """Per-vertex count of out-edges crossing a partition boundary.

        An edge into a replicated vertex is served from the local replica,
        so it never crosses: ``cross(u, v) = (parts[u] != parts[v]) and
        not replicated[v]``. The mask is applied *here*, on the host — the
        compiled BFS/SSSP closures consume ``cross_deg`` as a plain array
        input, so replica-awareness never retraces them.
        """
        tracing.count("fold.edges", self.s.shape[0])
        parts = np.asarray(parts, dtype=np.int64)
        crossing = parts[self.s] != parts[self.r]
        if replicated is not None:
            crossing &= ~np.asarray(replicated, dtype=bool)[self.r]
        return np.bincount(
            self.s, weights=crossing, minlength=self.n_nodes
        ).astype(np.int32)

    @tracing.span("fold.finalize")
    def finalize(
        self,
        edges: np.ndarray,
        cross: np.ndarray,
        tm64: np.ndarray,
        parts: np.ndarray,
        k: int,
        t_l: int,
        t_pg: int,
        replicated: Optional[np.ndarray] = None,
    ):
        """Aggregate counters from the total frontier mass (host, int64).

        Shared by the single-device run and the sharded replayer: both
        reduce to the same (per-op edges/cross, per-vertex mass) triple, so
        finalizing identically keeps them bit-equal by construction.

        With ``replicated``, the potentially-global action of a step into
        a replicated vertex books to the *reading* partition (the replica
        is local) while per-vertex attribution is unchanged — totals are
        conserved, only partition attribution moves.
        """
        from repro.core.traffic import TrafficResult

        tracing.count("fold.edges", self.s.shape[0])
        parts = np.asarray(parts, dtype=np.int64)
        deg64 = self.deg.astype(np.int64)
        pv = t_l * deg64 * tm64
        tpg_push = np.zeros(self.n_nodes, dtype=np.int64)
        np.add.at(tpg_push, self.r, tm64[self.s])
        pv += t_pg * tpg_push
        per_partition = np.zeros(k, dtype=np.int64)
        if replicated is None:
            np.add.at(per_partition, parts, pv)
        else:
            rep = np.asarray(replicated, dtype=bool)
            # t_l of every step books to the sender's partition; t_pg books
            # to the receiver's unless the receiver is replicated, in which
            # case it books back to the sender (local replica read).
            rep_out_deg = np.bincount(
                self.s, weights=rep[self.r], minlength=self.n_nodes
            ).astype(np.int64)
            sender_side = (t_l * deg64 + t_pg * rep_out_deg) * tm64
            receiver_side = t_pg * np.where(rep, 0, tpg_push)
            np.add.at(per_partition, parts, sender_side + receiver_side)
        return TrafficResult(
            per_op_total=edges * (t_l + t_pg),
            per_op_global=cross,
            per_partition=per_partition,
            per_vertex=pv,
        )

    def run(
        self,
        ops,
        parts: np.ndarray,
        k: int,
        t_l: int,
        t_pg: int,
        replicated: Optional[np.ndarray] = None,
    ):
        with tracing.span("replay"):
            tracing.count("replay.ops", ops.n_ops)
            parts = np.asarray(parts, dtype=np.int64)
            cross_deg = self.cross_degree(parts, replicated=replicated)

            if self.kind == "bfs":
                edges, cross, tm64 = self._run_bfs(ops, cross_deg)
            else:
                edges, cross, tm64 = self._run_sssp(ops, cross_deg)
            return self.finalize(edges, cross, tm64, parts, k, t_l, t_pg,
                                 replicated=replicated)


@jax.jit
def _device_h(lon_w, lat_w, dst_lon, dst_lat):
    """The solve's heuristic rows and rounding mask in a program of their
    own, for the engine-init probe (:meth:`BatchedTrafficEngine._check_device_h`)
    only: a replay computes them inside the solve."""
    return _heuristic_rows(lon_w, lat_w, dst_lon, dst_lat)


def get_engine(
    graph: Graph,
    pattern: str,
    chunk: Optional[int] = None,
    max_expansions: Optional[int] = None,
    delta_scale: Optional[float] = None,
    use_kernel: Optional[bool] = None,
) -> BatchedTrafficEngine:
    """Engine cache: store-lifetime for overlay graphs, graph-lifetime
    otherwise (same idiom as didic.make_spmm).

    ``max_expansions`` and ``use_kernel`` are normalized before keying,
    so ``None`` and an explicit default resolve to the *same* engine — the engine's value is
    authoritative for every path (batched, sharded, redo, resident).
    For a store-backed graph the engine is keyed on the
    :class:`~repro.graphs.structure.GraphStore` by engine parameters
    (capacity is the store's identity) and *adopts* each grown graph in
    place, so compiled closures survive growth.
    """
    key = (pattern, chunk, resolve_max_expansions(max_expansions),
           delta_scale, bool(use_kernel))
    store = graph.store
    if store is not None:
        skey = ("engine",) + key
        eng = store.caches.get(skey)
        if eng is not None:
            eng.adopt(graph)
            if eng._needs_rebuild:
                eng = None
        if eng is None:
            eng = BatchedTrafficEngine(
                graph, pattern, chunk=chunk,
                max_expansions=max_expansions, delta_scale=delta_scale,
                use_kernel=use_kernel,
            )
            store.caches[skey] = eng
        return eng
    cache = graph.__dict__.setdefault("_traffic_engine_cache", {})
    if key not in cache:
        cache[key] = BatchedTrafficEngine(
            graph, pattern, chunk=chunk,
            max_expansions=max_expansions, delta_scale=delta_scale,
            use_kernel=use_kernel,
        )
    return cache[key]


def execute_ops_batched(
    graph: Graph,
    ops,
    parts: np.ndarray,
    k: int,
    chunk: Optional[int] = None,
    max_expansions: Optional[int] = None,
    delta_scale: Optional[float] = None,
    use_kernel: Optional[bool] = None,
    replicated: Optional[np.ndarray] = None,
):
    engine = get_engine(
        graph, ops.pattern, chunk=chunk,
        max_expansions=max_expansions, delta_scale=delta_scale,
        use_kernel=use_kernel,
    )
    return engine.run(ops, parts, k, t_l=ops.t_l, t_pg=ops.t_pg,
                      replicated=replicated)
