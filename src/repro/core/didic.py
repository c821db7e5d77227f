"""DiDiC — Distributed Diffusive Clustering (paper §4.1.3), TPU-native.

The thesis presents DiDiC vertex-at-a-time (Fig. 4.2). The algorithm is a
pair of coupled diffusion systems per partition ``c``:

  secondary (disturbance, Eq. 4.7):
      y_e(c) = wt(e)·α(e)·( l_u(c)/b_u(c) − l_v(c)/b_v(c) )
      l_u ← l_u − Σ_e y_e ,  b_u(c) = 10 if u ∈ π_c else 1
  primary (Eq. 4.6):
      x_e(c) = wt(e)·α(e)·( w_u(c) − w_v(c) )
      w_u ← w_u + l_u − Σ_e x_e
  assignment (Eq. 4.8):  π(v) = argmax_c w_v(c)

**Hardware adaptation (DESIGN.md §2)**: one inner step over *all* k systems
is a sparse-matrix product. With the symmetrized edge list and the per-edge
coefficient ``c_e = wt(e)·α(e)``:

      Σ_e x_e  =  deg_c ⊙ W  −  A_c @ W        (A_c = weighted adjacency)

so a DiDiC step is ``W ← W + L − deg_c⊙W + A_c@W`` on an ``N×k`` load
matrix — a segment-sum (oracle path) or a 128×128 block-sparse SpMM on the
MXU (``repro.kernels.bsr_spmm`` path). Flow scale α uses Metropolis weights
``α(e) = 1/(1 + max(D_u, D_v))`` (D = weighted degree), which bounds the
per-vertex outflow below 1 and keeps both systems stable on any graph.

**Synchronous-vectorization adaptations.** The thesis's algorithm runs
asynchronously, one vertex at a time, on a JVM. A literal synchronous
whole-graph translation has four failure modes, each observed and fixed here
(all validated against planted-community graphs and the paper's own
datasets; see EXPERIMENTS.md):

1. *Mass drift* — each system's primary mass grows by its secondary mass
   per primary step, so with a random start the heaviest system wins argmax
   everywhere. Fix: fresh per-member secondary seeds each iteration
   (Eq. 4.5 applied per iteration) + a column-common rescale of ``w``.
2. *Winner-take-all absorption* — per-member seeding alone lets locally
   dominant systems absorb everything (the classic label-propagation
   collapse). Fix: per-system balance scalars β_c fitted each iteration so
   argmax yields near-equal sizes — exactly Bubble-FOS/C's ScaleBalance
   operation from the same disturbed-diffusion literature DiDiC cites.
3. *Self-pinning / parity oscillation* — a vertex's own drain spike pins it
   to its current system; on bipartite structures (trees!) synchronous
   updates flip in lock-step forever. Fix: assign by the *neighborhood-
   diffused* load (removing the self-spike) and commit each vertex's new
   label with probability ``commit_prob`` (stochastic asynchrony, which is
   what the distributed algorithm does naturally).
4. *Kernel-width freezing* — assignment domains freeze once they reach the
   diffusion kernel's width, stranding the cut far above optimum on trees.
   Fix: anneal the assignment-smoothing depth (a 50 %-lazy random walk
   whose per-step transfer is degree-independent) from 1 to
   ``smooth_cap`` steps, doubling every ``smooth_double_every`` iterations —
   domains coarsen until the cut stabilizes.

With these, reduced-scale reproductions land in the paper's bands
(edge cut @ k=2/4 — GIS ≈0.1 %/2 % vs paper 1.9 %/3.2 %; Twitter ≈24 %/38 %
vs paper 25 %/37 %; filesystem ≈1–6 % vs paper 2.4 %/3.6 %), while the
un-adapted literal form stalls at random-level cuts (~50 %/75 %).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import Partial

from repro.graphs.structure import Graph

__all__ = ["DidicConfig", "DidicState", "didic_partition", "didic_refine", "make_spmm"]

_BENEFIT = 10.0     # b_u(c) for members of π_c (paper Eq. 4.7)
_INIT_LOAD = 100.0  # initial load per vertex in its own system (Eq. 4.5)


@dataclasses.dataclass(frozen=True)
class DidicConfig:
    """DiDiC hyper-parameters (paper defaults: T=100 initial, T=1 repair)."""

    k: int = 4
    iterations: int = 100        # T
    primary_steps: int = 11      # ψ
    secondary_steps: int = 9     # ρ
    smooth_cap: int = 64         # max assignment-smoothing depth
    smooth_double_every: int = 10
    commit_prob: float = 0.9     # stochastic-asynchrony commit probability
    balance_iters: int = 8       # ScaleBalance fitting iterations
    balance_exp: float = 0.25    # ScaleBalance damping exponent
    use_kernel: bool = False     # BSR SpMM Pallas path instead of segment_sum
    block_size: int = 128


@dataclasses.dataclass
class DidicState:
    """Carried diffusion state — checkpointable alongside model state."""

    w: jax.Array      # [N, k] primary loads
    l: jax.Array      # [N, k] secondary loads
    parts: jax.Array  # [N] int32 current assignment
    beta: jax.Array   # [k] balance scalars


def _edge_coefficients(graph: Graph) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Symmetrized edges + Metropolis-scaled coefficients + coeff degree.

    Cached on the graph (like the BELL packing in
    :meth:`Graph.to_block_ell`): the coefficient matrix depends only on
    structure, so repartition/refine cycles on a static graph never pay the
    symmetrize + scale pass twice.
    """
    cached = graph.__dict__.get("_didic_coeff_cache")
    if cached is not None:
        return cached
    s, r, wt = graph.undirected
    deg = graph.weighted_degree
    alpha = 1.0 / (1.0 + np.maximum(deg[s], deg[r]))
    ce = (wt * alpha).astype(np.float32)
    degc = np.zeros(graph.n_nodes, dtype=np.float64)
    np.add.at(degc, s, ce)
    out = (s.astype(np.int32), r.astype(np.int32), ce, degc.astype(np.float32))
    graph.__dict__["_didic_coeff_cache"] = out
    return out


def _spmm_segment(ce: jax.Array, s: jax.Array, r: jax.Array, x: jax.Array) -> jax.Array:
    """A_c @ X via gather + segment_sum over the symmetrized COO edges."""
    contrib = ce[:, None] * jnp.take(x, r, axis=0)
    return jax.ops.segment_sum(contrib, s, num_segments=x.shape[0])


def make_spmm(graph: Graph, config: DidicConfig) -> Tuple[Partial, jax.Array]:
    """Return (spmm(X) -> A_c @ X, degc) for the DiDiC coefficient matrix.

    ``spmm`` is a :class:`jax.tree_util.Partial` whose arguments are the
    graph's tables, so the jitted step takes them as inputs; as constants
    they would make each compiled step, and its persistent-cache entry,
    grow with the graph. Cached *on the graph object* (lifetime-tied — an
    id()-keyed global cache would alias recycled addresses) so repeated
    partition/refine calls reuse the device tables.
    """
    cache = graph.__dict__.setdefault("_didic_spmm_cache", {})
    cache_key = (config.use_kernel, config.block_size)
    if cache_key in cache:
        return cache[cache_key]
    _SPMM_CACHE = cache  # write-through alias used below
    s, r, ce, degc = _edge_coefficients(graph)
    if config.use_kernel:
        from repro.kernels.bsr_spmm import ops as bsr_ops

        coeff_graph = Graph(
            n_nodes=graph.n_nodes, senders=s, receivers=r, edge_weight=ce, name="didic_coeff"
        )
        bell = coeff_graph.to_block_ell(block_size=config.block_size, undirected=False)
        kernel_mm = bsr_ops.make_bell_matmul(bell)

        def spmm_fn(x: jax.Array) -> jax.Array:
            pad = bell.padded_rows - x.shape[0]
            xp = jnp.pad(x, ((0, pad), (0, 0)))
            return kernel_mm(xp)[: x.shape[0]]

        _SPMM_CACHE[cache_key] = (Partial(spmm_fn), jnp.asarray(degc))
        return _SPMM_CACHE[cache_key]
    tables = (jnp.asarray(ce), jnp.asarray(s), jnp.asarray(r))
    _SPMM_CACHE[cache_key] = (Partial(_spmm_segment, *tables), jnp.asarray(degc))
    return _SPMM_CACHE[cache_key]


_STEP_CACHE: dict = {}


def _make_step(spmm: Partial, degc: jax.Array, config: DidicConfig,
               live: Optional[jax.Array] = None, n_live: Optional[int] = None):
    """The single-iteration function for ``spmm``'s graph.

    The jitted step is shared per config: ``spmm`` (a
    :class:`jax.tree_util.Partial` over the graph's tables), ``degc`` and
    ``live`` are its arguments, so no graph array becomes a compiled
    constant. ``live`` (bool, one per row) marks the ``n_live`` rows that
    are vertices: a mesh layout pads each shard's block with rows that are
    none. Without them every row is a vertex.
    """
    if live is None:
        n_live = degc.shape[0]
        live = jnp.ones(n_live, dtype=bool)
    jitted = _STEP_CACHE.get(config)
    if jitted is None:
        jitted = _STEP_CACHE[config] = _jit_step(config)
    return functools.partial(jitted, spmm=spmm, degc=degc, live=live, n_live=n_live)


def _jit_step(config: DidicConfig):
    @functools.partial(jax.jit, static_argnames="n_live")
    def step(w, l, parts, beta, key, smooth_steps, *, spmm, degc, live, n_live):
        return _step_body(config, spmm, degc, live, n_live, w, l, parts, beta, key,
                          smooth_steps)

    return step


def _step_body(config: DidicConfig, spmm: Callable, degc, live, n_live, w, l, parts, beta,
               key, smooth_steps):
    """One DiDiC iteration, traced inside each path's jitted step.

    Rows outside ``live`` (a mesh layout's block padding, a store's spare
    capacity) are no vertices: they carry no load, keep their part, and
    count neither in the rescale nor in ScaleBalance's sizes and target,
    which they would bias towards system 0 far beyond float32 rounding.
    ``n_live`` counts the live rows: an int where the graph's shape fixes
    it, a traced float where one program serves a growing graph.
    """
    k = config.k
    n_rows = w.shape[0]
    livef = live.astype(w.dtype)
    safe_deg = jnp.maximum(degc, 1e-6)
    onehot = (
        parts[:, None] == jnp.arange(k, dtype=parts.dtype)[None, :]
    ).astype(w.dtype) * livef[:, None]
    # Fresh per-member secondary seed (Eq. 4.5 each iteration; fix #1),
    # with an ε-floor: a system that loses all members would otherwise
    # seed zero load forever and stay dead — the ε keeps every system
    # faintly alive so the ScaleBalance scalars can revive it (matters
    # on community-free graphs, where partitions otherwise collapse).
    l = (_INIT_LOAD * onehot + 0.01) * livef[:, None]
    benefit = jnp.where(onehot > 0, _BENEFIT, 1.0).astype(w.dtype)

    def secondary(l, _):
        lb = l / benefit
        return l - degc[:, None] * lb + spmm(lb), None

    def primary(carry, _):
        w, l = carry
        l, _ = jax.lax.scan(secondary, l, None, length=config.secondary_steps)
        w_new = w + l - degc[:, None] * w + spmm(w)
        return (w_new, l), None

    (w, l), _ = jax.lax.scan(primary, (w, l), None, length=config.primary_steps)
    # Column-common rescale over the live rows' mean (fix #1).
    mean = jnp.where(live[:, None], w, 0.0).sum() / (n_live * k)
    w = w / jnp.maximum(mean, 1e-6)

    # Annealed lazy-random-walk assignment smoothing (fixes #3, #4).
    def smooth_body(_, x):
        return 0.5 * x + 0.5 * spmm(x) / safe_deg[:, None]

    smoothed = jax.lax.fori_loop(0, smooth_steps, smooth_body, w)

    # ScaleBalance (fix #2): fit β so argmax sizes approach N/k.
    tgt = n_live / k

    def bal(_, beta):
        p = jnp.argmax(smoothed * beta[None, :], axis=1)
        sizes = jnp.bincount(jnp.where(live, p, k), length=k + 1)[:k].astype(w.dtype)
        return jnp.clip(
            beta * (tgt / jnp.maximum(sizes, 1.0)) ** config.balance_exp, 1e-3, 1e3
        )

    beta = jax.lax.fori_loop(0, config.balance_iters, bal, beta)
    new_parts = jnp.argmax(smoothed * beta[None, :], axis=1).astype(jnp.int32)
    commit = jax.random.bernoulli(key, config.commit_prob, (n_rows,))
    parts = jnp.where(commit & live, new_parts, parts)
    return w, l, parts, beta


# ===========================================================================
# Capacity-overlay path (ISSUE 8): store-backed graphs run refine through a
# module-level jitted step whose inputs — coefficient tables, diffusion
# state, live extent — are all *arguments* padded to the store's capacity.
# Nothing graph-owned is closed over, so one compiled program serves every
# grown graph sharing a capacity: growth slices retrace nothing.
# ===========================================================================
_OVERLAY_STEP_CACHE: dict = {}


def _overlay_tables(graph: Graph):
    """Capacity-padded DiDiC coefficient tables for a store-backed graph.

    Dead rows/edges are *inert by construction*: padded edges point at the
    sentinel row ``n_cap`` with coefficient 0, dead rows have zero
    coefficient degree, and the diffusion state carries exact zeros there
    — so every SpMM fold leaves dead rows identically 0 and the live
    prefix computes the same values at any capacity.
    """
    store = graph.store
    s, r, ce, degc = _edge_coefficients(graph)
    n_rows = store.n_cap + 1
    e_pad = 2 * store.e_cap  # undirected symmetrization ≤ 2·e_cap edges
    if s.shape[0] > e_pad:
        raise ValueError(
            f"graph has {s.shape[0]} symmetrized edges but the store caps "
            f"the overlay at {e_pad}"
        )
    dead = np.int32(n_rows - 1)
    s_p = np.full(e_pad, dead, dtype=np.int32)
    r_p = np.full(e_pad, dead, dtype=np.int32)
    ce_p = np.zeros(e_pad, dtype=np.float32)
    dg_p = np.zeros(n_rows, dtype=np.float32)
    s_p[: s.shape[0]] = s
    r_p[: r.shape[0]] = r
    ce_p[: ce.shape[0]] = ce
    dg_p[: degc.shape[0]] = degc
    return jnp.asarray(s_p), jnp.asarray(r_p), jnp.asarray(ce_p), jnp.asarray(dg_p)


def _make_overlay_step(config: DidicConfig):
    """Jitted overlay iteration with the graph passed as arguments.

    Module-level cache keyed by config (the legacy step hangs off the
    graph-owned spmm closure instead, which is exactly what forces a
    retrace per grown graph). It traces :func:`_step_body` with the rows
    past the live extent masked, so the live prefix sees the same
    *algorithm* as the legacy step — the padded float sums reassociate,
    so values are close but not
    bit-identical to the legacy path; both the host and device services
    route store-backed maintenance through here, which keeps their
    host-vs-device parity contract exact.
    """
    step = _OVERLAY_STEP_CACHE.get(config)
    if step is not None:
        return step

    @jax.jit
    def step(w, l, parts, beta, key, smooth_steps, s, r, ce, degc, live_n):
        n_rows = w.shape[0]

        def spmm(x):
            contrib = ce[:, None] * jnp.take(x, r, axis=0)
            return jax.ops.segment_sum(contrib, s, num_segments=n_rows)

        live = jnp.arange(n_rows, dtype=jnp.int32) < live_n
        return _step_body(config, spmm, degc, live, live_n.astype(w.dtype), w, l, parts, beta,
                          key, smooth_steps)

    _OVERLAY_STEP_CACHE[config] = step
    return step


def _overlay_refine(
    graph: Graph,
    parts: np.ndarray,
    config: DidicConfig,
    state: Optional[DidicState],
    iterations: int,
    seed: int,
) -> Tuple[np.ndarray, DidicState]:
    """Refine a store-backed graph through the capacity-overlay step.

    Tables are cached on the store keyed by the graph's structural
    extents, so growth re-pads host-side but never retraces; state
    tensors are capacity-shaped (reseeded when the capacity changed,
    e.g. across a compaction)."""
    store = graph.store
    extents = (graph.n_nodes, graph.n_edges)
    ent = store.caches.get(("didic_tables",))
    if ent is None or ent[0] != extents:
        ent = (extents, _overlay_tables(graph))
        store.caches[("didic_tables",)] = ent
    s_j, r_j, ce_j, degc_j = ent[1]
    n, n_rows = graph.n_nodes, store.n_cap + 1
    parts_pad = np.zeros(n_rows, dtype=np.int32)
    parts_pad[:n] = np.asarray(parts, dtype=np.int32)
    parts_j = jnp.asarray(parts_pad)
    if state is None or state.w.shape[0] != n_rows:
        live = np.arange(n_rows) < n
        onehot = (
            parts_pad[:, None] == np.arange(config.k, dtype=np.int32)[None, :]
        ) & live[:, None]
        load = jnp.asarray(_INIT_LOAD * onehot.astype(np.float32))
        state = DidicState(
            w=load, l=load, parts=parts_j, beta=jnp.ones((config.k,), jnp.float32)
        )
    else:
        state = DidicState(w=state.w, l=state.l, parts=parts_j, beta=state.beta)
    step = _make_overlay_step(config)
    schedule = _smooth_schedule(config, iterations, start_wide=True)
    key = jax.random.PRNGKey(seed)
    w, l, p, beta = state.w, state.l, state.parts, state.beta
    live_n = jnp.int32(n)
    for it in range(iterations):
        key, sub = jax.random.split(key)
        w, l, p, beta = step(
            w, l, p, beta, sub, jnp.int32(schedule[it]),
            s_j, r_j, ce_j, degc_j, live_n,
        )
    return np.asarray(p)[:n].copy(), DidicState(w=w, l=l, parts=p, beta=beta)


def _init_state(n: int, k: int, parts0: jax.Array) -> DidicState:
    onehot = (parts0[:, None] == jnp.arange(k, dtype=parts0.dtype)[None, :]).astype(jnp.float32)
    load = _INIT_LOAD * onehot
    return DidicState(
        w=load, l=load, parts=parts0.astype(jnp.int32), beta=jnp.ones((k,), jnp.float32)
    )


def _smooth_schedule(config: DidicConfig, iterations: int, start_wide: bool) -> np.ndarray:
    if start_wide:
        return np.full(iterations, config.smooth_cap, dtype=np.int32)
    sched = np.minimum(
        1 << (np.arange(iterations) // max(config.smooth_double_every, 1)),
        config.smooth_cap,
    )
    return sched.astype(np.int32)


def _run_iterations(
    state: DidicState,
    spmm: Callable,
    degc: jax.Array,
    config: DidicConfig,
    iterations: int,
    seed: int,
    start_wide: bool = False,
) -> DidicState:
    step = _make_step(spmm, degc, config)
    schedule = _smooth_schedule(config, iterations, start_wide)
    key = jax.random.PRNGKey(seed)
    w, l, parts, beta = state.w, state.l, state.parts, state.beta
    for it in range(iterations):
        key, sub = jax.random.split(key)
        w, l, parts, beta = step(w, l, parts, beta, sub, jnp.int32(schedule[it]))
    return DidicState(w=w, l=l, parts=parts, beta=beta)


def didic_partition(
    graph: Graph,
    config: DidicConfig,
    seed: int = 0,
    init_parts: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, DidicState]:
    """Partition ``graph`` into ``config.k`` parts from a random start.

    Matches the paper's evaluation setup: random initial assignment, then
    ``config.iterations`` DiDiC iterations (100 for the static experiment).
    Returns (parts[N] int32 on host, final DidicState).
    """
    if init_parts is None:
        rng = np.random.default_rng(seed)
        init_parts = rng.integers(0, config.k, size=graph.n_nodes)
    parts0 = jnp.asarray(np.asarray(init_parts, dtype=np.int32))
    spmm, degc = make_spmm(graph, config)
    state = _init_state(graph.n_nodes, config.k, parts0)
    state = _run_iterations(state, spmm, degc, config, config.iterations, seed)
    return np.asarray(state.parts), state


def didic_refine(
    graph: Graph,
    parts: np.ndarray,
    config: DidicConfig,
    state: Optional[DidicState] = None,
    iterations: int = 1,
    seed: int = 0,
    pinned: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, DidicState]:
    """Repair/maintain an existing partitioning (paper Stress/Dynamic exps).

    Seeds loads from ``parts`` (the degraded assignment); one iteration is
    the paper's maintenance budget. Runs at full smoothing width so the
    repair sees existing large-scale structure instead of re-coarsening,
    and commits deterministically (``commit_prob=1``): stochastic
    asynchrony exists to break synchronous oscillation across *many*
    iterations, but within the paper's one-iteration maintenance budget it
    only strands a random ~10 % of damaged vertices unrepaired.

    ``pinned`` vertices (the placement layer's replicated hot set) keep
    their incoming assignment: diffusion runs unchanged — the pin is a
    host-side restore on the returned map, *outside* every compiled step,
    so pinning neither retraces the overlay closure nor perturbs the
    diffusion numerics of unpinned vertices. The next refine re-seeds the
    carried state's assignment from the input map (the input always
    wins), so the restored pins propagate instead of fighting the state.

    Store-backed graphs (a :class:`~repro.graphs.structure.GraphStore`
    attached) route through the capacity-overlay step instead: same
    algorithm on capacity-padded state, compiled once per (config,
    capacity) so maintenance after a growth slice retraces nothing. The
    BSR-kernel path keeps the legacy per-graph packing (its block layout
    is extent-shaped).
    """
    config = dataclasses.replace(config, commit_prob=1.0)
    pinned, before = _capture_pins(parts, pinned)
    if graph.store is not None and not config.use_kernel:
        out, state = _overlay_refine(graph, parts, config, state, iterations, seed)
        return _restore_pins(out, pinned, before), state
    parts_j = jnp.asarray(np.asarray(parts, dtype=np.int32))
    spmm, degc = make_spmm(graph, config)
    if state is None:
        state = _init_state(graph.n_nodes, config.k, parts_j)
    else:
        state = DidicState(w=state.w, l=state.l, parts=parts_j, beta=state.beta)
    state = _run_iterations(state, spmm, degc, config, iterations, seed, start_wide=True)
    return _restore_pins(np.asarray(state.parts), pinned, before), state


def _capture_pins(
    parts: np.ndarray, pinned: Optional[np.ndarray]
) -> Tuple[Optional[np.ndarray], Optional[np.ndarray]]:
    """Snapshot pinned vertices' assignments before a refine pass."""
    if pinned is None:
        return None, None
    pinned = np.asarray(pinned, dtype=np.int64)
    if pinned.size == 0:
        return None, None
    return pinned, np.asarray(parts)[pinned].copy()


def _restore_pins(
    new_parts: np.ndarray,
    pinned: Optional[np.ndarray],
    before: Optional[np.ndarray],
) -> np.ndarray:
    """Re-apply pinned assignments to a refined map (host-side, after
    every compiled step has run — empty pin set is an exact no-op)."""
    if pinned is None:
        return new_parts
    out = np.asarray(new_parts).copy()
    out[pinned] = before
    return out
