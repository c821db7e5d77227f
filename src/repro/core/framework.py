"""The paper's partitioning-framework abstractions (§3.1, Fig. 3.1).

Four components compose the runtime:

* :class:`InsertPartitioner`    — allocates entities to partitions at write
  time (policies: random / fewest-vertices / least-traffic, §6.4),
* :class:`RuntimeLogger`        — per-partition ``InstanceInfo`` metrics
  (vertices, edges, local vs global traffic — §5.2), per-vertex traffic
  accumulation (the hot-vertex selection signal), and service-health
  counters,
* :class:`RuntimePartitioner`   — re-partitions at runtime (wraps DiDiC),
* :class:`MigrationScheduler`   — decides *when* migration runs and emits
  migration commands (vertex→partition deltas).

:class:`PartitionedGraphService` is the emulator-style facade (§5.3.2): one
logical graph + a placement, serving the same measurements as the
thesis's ``PGraphDatabaseServiceEmulator``. The distributed runtime
(`repro.distributed.placement`) consumes the same partition map to place
GNN shards on mesh devices — the framework is shared between the paper
reproduction and the large-scale training path.

**Placement: ownership + read replicas.** Where the thesis assigns every
vertex to exactly one partition, the service holds a
:class:`repro.core.placement.Placement`: an *owner array* (the classic
``parts`` map, still exposed as :attr:`PartitionedGraphService.parts`)
plus a fixed-capacity *exception table* of hot vertices replicated
read-only on every partition. Routing rules:

* **Reads** of a replicated vertex are served by the local replica at the
  reading partition — a traversal step into it is not global traffic, and
  its potentially-global action books to the *reader* (see
  ``_ScalarCounters.step`` / ``BatchedTrafficEngine.cross_degree``).
* **Writes** — partition moves, structural inserts, deletes — always
  resolve the owner, never a replica (the ``placement/single-owner``
  repro-lint rule guards this), and :meth:`apply_dynamism` *invalidates*
  the replicas of every written vertex, bumping the placement's
  ``replica_epoch``.
* **Maintenance** pins exception vertices out of DiDiC diffusion so a
  refine pass cannot thrash a vertex the traffic log proved hot; the hot
  set itself is chosen from the logger's accumulated per-vertex traffic
  with promotion hysteresis (:meth:`PartitionedGraphService.refresh_placement`).

The exception table is padded to a static capacity, so everything derived
from it keeps its shape and compiled closures never retrace when the hot
set churns. An *empty* table (capacity 0, the default) is bit-identical
to the single-assignment model on all four traffic counters.

**Engine dispatch.** Every component runs behind one interface on either
the host reference engines or the mesh-native device engines: construct
the service with a ``mesh`` and ``run_ops`` routes through
:func:`repro.core.traffic_sharded.replay_sharded`, ``maintain`` through
:func:`repro.core.didic_distributed.didic_refine_distributed` (unless
``maintenance="shared"`` pins the bit-parity single-device DiDiC), and
:class:`InsertPartitioner` generates dynamism with the device scan of
:mod:`repro.core.dynamic_runtime`. Without a mesh the host paths run —
same cycle, same seeds, same results where bit-parity is contracted.
"""

from __future__ import annotations

import dataclasses
import time as _time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core import metrics, tracing
from repro.core.didic import DidicConfig, DidicState, didic_partition, didic_refine
from repro.core.dynamism import DynamismLog, apply_dynamism, generate_dynamism
from repro.core.placement import Placement
from repro.core.traffic import OpLog, TrafficResult, execute_ops, generate_ops
from repro.graphs.structure import Graph

__all__ = [
    "InstanceInfo",
    "InsertPartitioner",
    "RuntimeLogger",
    "RuntimePartitioner",
    "MigrationScheduler",
    "PartitionedGraphService",
]


@dataclasses.dataclass
class InstanceInfo:
    """Per-partition runtime metrics (paper §5.2)."""

    n_vertices: int = 0
    n_edges: int = 0
    local_traffic: int = 0
    global_traffic: int = 0


class InsertPartitioner:
    """Insert-Partitioning component: allocate new entities to partitions.

    Per-call randomness comes from children spawned off one
    :class:`np.random.SeedSequence`: the i-th ``allocate`` of two
    partitioners built with the same seed is identical, and streams from
    *different* base seeds never collide. (The old ``self._seed += 1``
    made call #1 of ``seed=0`` alias call #0 of ``seed=1``.)

    ``engine="device"`` generates the sequential policies with the
    bit-identical :func:`jax.lax.scan` path of
    :mod:`repro.core.dynamic_runtime`.
    """

    def __init__(self, method: str = "random", k: int = 4, seed: int = 0,
                 engine: str = "host"):
        self.method = method
        self.k = k
        self.engine = engine
        self._seeds = np.random.SeedSequence(seed)

    def allocate(
        self,
        parts: np.ndarray,
        amount: float,
        vertex_traffic: Optional[np.ndarray] = None,
        insert_rate: float = 0.0,
        graph: Optional[Graph] = None,
    ) -> DynamismLog:
        """Allocate one dynamism slice; ``insert_rate`` of the units
        allocate *new* vertices (with incident edges sampled on ``graph``,
        required then) instead of moving existing ones — the paper's
        write-time Insert workload."""
        (stream,) = self._seeds.spawn(1)
        return generate_dynamism(
            parts, amount, self.method, self.k,
            vertex_traffic=vertex_traffic, seed=stream, engine=self.engine,
            insert_rate=insert_rate, graph=graph,
        )

    # -- RNG state (snapshot/restore) ----------------------------------------
    def rng_state(self) -> Tuple:
        """Serializable SeedSequence position: ``(entropy, spawn_key,
        n_children_spawned)``. Restoring it reproduces the remaining
        ``allocate`` stream exactly — the property crash recovery needs to
        regenerate post-snapshot slices bit-identically."""
        ss = self._seeds
        return (ss.entropy, tuple(int(x) for x in ss.spawn_key),
                int(ss.n_children_spawned))

    def set_rng_state(self, state: Tuple) -> None:
        entropy, spawn_key, n_spawned = state
        self._seeds = np.random.SeedSequence(
            entropy, spawn_key=tuple(int(x) for x in spawn_key),
            n_children_spawned=int(n_spawned),
        )

    def advance(self, n: int = 1) -> None:
        """Discard ``n`` allocation draws (used when a journaled log stands
        in for this partitioner's draw, keeping later draws aligned)."""
        self._seeds.spawn(int(n))


class RuntimeLogger:
    """Runtime-Logging component: accumulates InstanceInfo per partition,
    plus the service-health counters of the fault-tolerance layer
    (degraded replays, maintenance retries, recovery time) and the online
    front-end's latency subsystem (per-op-class queue-wait/service-time
    samples on the server's deterministic simulated clock — integer ticks,
    never wall-clock reads, which repro-lint would reject)."""

    def __init__(self, k: int):
        self.k = k
        self.reset()

    def reset(self) -> None:
        self.infos: List[InstanceInfo] = [InstanceInfo() for _ in range(self.k)]
        # A reset must also clear the degradation aggregate: the scheduler
        # judges should_migrate against percent_global(), and a stale
        # pre-reset value would let a freshly reset service trip migration
        # on degradation it never served.
        self._last_percent_global = 0.0
        self.degraded_replays = 0
        self.degraded_ops = 0
        self.maintenance_retries = 0
        self.maintenance_retry_time_s = 0.0
        self.recoveries = 0
        self.recovery_time_s = 0.0
        # Device-resident replay-state footprint (bytes) of the owning
        # service, refreshed after each sharded replay — the observability
        # hook for the ROADMAP resident-memory ceiling.
        self.resident_state_bytes = 0
        # Accumulated per-vertex served traffic across observations — the
        # hot-vertex selection signal for the placement exception table.
        # Growable: observations from a grown graph extend it.
        self.vertex_traffic = np.zeros(0, dtype=np.int64)
        # Latency subsystem. Samples are Python ints (simulated-clock
        # ticks), accumulated in unbounded Python arithmetic so
        # long-horizon counters cannot wrap (the int64-overflow bug class);
        # SLO budgets survive reset — they are configuration, not state.
        self.slo_violations = 0
        self._latency: Dict[str, Dict[str, List[int]]] = {}
        if not hasattr(self, "_slo_budgets"):
            self._slo_budgets: Dict[str, int] = {}

    def observe_structure(self, graph: Graph, parts: np.ndarray) -> None:
        counts = metrics.partition_counts(graph, parts, self.k)
        for i in range(self.k):
            self.infos[i].n_vertices = int(counts["vertices"][i])
            self.infos[i].n_edges = int(counts["edges"][i])

    def observe_traffic(self, result: TrafficResult) -> None:
        """Attribute served traffic per partition, split local vs global
        (§5.2). Global actions are attributed proportionally to each
        partition's served share (the emulator counts a cross-partition
        action on both ends) by largest-remainder apportionment: exact
        integer quotas rounded so that ``local + global == served`` holds
        per partition AND the summed global attribution equals the
        measured global total exactly (plain floor division dropped up to
        k−1 global units per observation)."""
        total = int(result.per_op_total.sum())
        global_total = int(result.per_op_global.sum())
        served = np.asarray(result.per_partition, dtype=np.int64)[: self.k]
        if total > 0 and global_total > 0:
            quota_num = global_total * served
            g = quota_num // total
            rem = quota_num - g * total
            short = global_total - int(g.sum())
            if short > 0:
                # Largest fractional remainder first; ties break on the
                # lowest partition index (stable sort of -rem).
                order = np.argsort(-rem, kind="stable")
                g[order[:short]] += 1
        else:
            g = np.zeros(self.k, dtype=np.int64)
        for i in range(self.k):
            self.infos[i].global_traffic += int(g[i])
            self.infos[i].local_traffic += int(served[i]) - int(g[i])
        pv = np.asarray(result.per_vertex, dtype=np.int64)
        if pv.shape[0] > self.vertex_traffic.shape[0]:
            self.vertex_traffic = np.concatenate([
                self.vertex_traffic,
                np.zeros(pv.shape[0] - self.vertex_traffic.shape[0], np.int64),
            ])
        self.vertex_traffic[: pv.shape[0]] += pv
        # store aggregate for degradation detection
        self._last_percent_global = result.percent_global

    def percent_global(self) -> float:
        return getattr(self, "_last_percent_global", 0.0)

    # -- fault-tolerance health metrics -------------------------------------
    def record_degraded(self, n_ops: int) -> None:
        """One replay served through the degraded (shared-engine) path."""
        self.degraded_replays += 1
        self.degraded_ops += int(n_ops)

    def record_maintenance_retries(self, retries: int, elapsed_s: float) -> None:
        self.maintenance_retries += int(retries)
        self.maintenance_retry_time_s += float(elapsed_s)

    def record_recovery(self, elapsed_s: float) -> None:
        self.recoveries += 1
        self.recovery_time_s += float(elapsed_s)

    # -- latency subsystem (online front-end) --------------------------------
    def set_slo(self, op_class: str, budget_ticks: int) -> None:
        """Set a per-op-class SLO budget: an op violates when its total
        latency (queue wait + service time, in simulated-clock ticks)
        exceeds the budget."""
        self._slo_budgets[op_class] = int(budget_ticks)

    def record_latency(self, op_class: str, queue_wait: int,
                       service_time: int) -> None:
        """Record one served op's latency sample (simulated-clock ticks)."""
        wait, service = int(queue_wait), int(service_time)
        bucket = self._latency.setdefault(op_class, {"wait": [], "service": []})
        bucket["wait"].append(wait)
        bucket["service"].append(service)
        budget = self._slo_budgets.get(op_class)
        if budget is not None and wait + service > budget:
            self.slo_violations += 1

    @staticmethod
    def _percentile(samples: List[int], q: float) -> int:
        """Nearest-rank percentile: ``sorted[ceil(q/100 * n) - 1]``.

        Exact on integer tick samples — no interpolation, so p50 of a
        single sample is that sample and tied values report the tie."""
        n = len(samples)
        if n == 0:
            raise ValueError("percentile of empty sample set")
        rank = max(1, -(-int(q * n) // 100))  # ceil(q*n/100), floor 1
        return sorted(samples)[rank - 1]

    def latency_report(self) -> Dict[str, Dict[str, float]]:
        """Per-op-class latency summary on the simulated clock (ticks)."""
        report: Dict[str, Dict[str, float]] = {}
        for cls, bucket in sorted(self._latency.items()):
            waits, services = bucket["wait"], bucket["service"]
            totals = [w + s for w, s in zip(waits, services)]
            n = len(waits)
            report[cls] = {
                "count": n,
                "queue_wait_p50": self._percentile(waits, 50),
                "queue_wait_p95": self._percentile(waits, 95),
                "queue_wait_p99": self._percentile(waits, 99),
                "queue_wait_max": max(waits),
                "queue_wait_mean": sum(waits) / n,
                "total_p50": self._percentile(totals, 50),
                "total_p95": self._percentile(totals, 95),
                "total_p99": self._percentile(totals, 99),
                "total_max": max(totals),
                "total_mean": sum(totals) / n,
                "service_mean": sum(services) / n,
            }
            budget = self._slo_budgets.get(cls)
            if budget is not None:
                report[cls]["slo_budget"] = budget
        return report

    def health_report(self) -> Dict[str, float]:
        return {
            "degraded_replays": self.degraded_replays,
            "degraded_ops": self.degraded_ops,
            "maintenance_retries": self.maintenance_retries,
            "maintenance_retry_time_s": self.maintenance_retry_time_s,
            "recoveries": self.recoveries,
            "recovery_time_s": self.recovery_time_s,
            "slo_violations": self.slo_violations,
            "resident_state_bytes": self.resident_state_bytes,
        }

    def load_balance_cv(self) -> Dict[str, float]:
        return {
            "vertices": metrics.coefficient_of_variation(
                np.array([i.n_vertices for i in self.infos])
            ),
            "edges": metrics.coefficient_of_variation(np.array([i.n_edges for i in self.infos])),
            # Balance is judged on *served* traffic — local and global
            # attribution together, i.e. exactly the per-partition units
            # of the TrafficResult(s) observed so far.
            "traffic": metrics.coefficient_of_variation(
                np.array([i.local_traffic + i.global_traffic for i in self.infos])
            ),
        }


class RuntimePartitioner:
    """Runtime-Partitioning component: DiDiC initial + maintenance passes.

    With a ``mesh``, both passes run the truly-distributed DiDiC of
    :mod:`repro.core.didic_distributed`: shard-resident loads, halo-exchange
    SpMM, and a carried sharded :class:`DidicState` so intermittent
    maintenance keeps its diffusion state on the mesh between slices.
    Without one, the single-device reference runs (state carried the same
    way). The two produce the same algorithm but different float32
    reduction orders — callers needing bit-parity with the host path pin
    ``mesh=None``.
    """

    def __init__(self, config: DidicConfig, mesh=None,
                 data_axes: Tuple[str, ...] = ("data",)):
        self.config = config
        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        self.state: Optional[DidicState] = None

    def initial(self, graph: Graph, seed: int = 0) -> np.ndarray:
        if self.mesh is not None:
            from repro.core.didic_distributed import didic_partition_distributed

            parts, _ = didic_partition_distributed(
                graph, self.config, self.mesh, self.data_axes, seed=seed
            )
            self.state = None  # sharded maintenance re-seeds from parts
            return parts
        parts, self.state = didic_partition(graph, self.config, seed=seed)
        return parts

    def maintain(self, graph: Graph, parts: np.ndarray, iterations: int = 1,
                 pinned: Optional[np.ndarray] = None) -> np.ndarray:
        """One maintenance refinement; ``pinned`` vertices (the placement
        exception table) keep their assignment — diffusion must not thrash
        a vertex the traffic log proved hot."""
        if self.mesh is not None:
            from repro.core.didic_distributed import didic_refine_distributed

            parts, self.state = didic_refine_distributed(
                graph, parts, self.config, self.mesh, self.data_axes,
                state=self.state, iterations=iterations, pinned=pinned,
            )
            return parts
        parts, self.state = didic_refine(
            graph, parts, self.config, state=self.state, iterations=iterations,
            pinned=pinned,
        )
        return parts


@dataclasses.dataclass
class MigrationCommand:
    vertices: np.ndarray
    target: int


class MigrationScheduler:
    """Migration-Scheduler component.

    Decides when the Partition-Mapping produced by runtime partitioning is
    applied. Policy: migrate when the fraction of vertices wanting to move
    exceeds ``min_move_fraction`` AND the observed global-traffic share has
    degraded ``degradation_factor``× over the **post-maintenance baseline**
    (or on an explicit interval — the paper's Dynamic experiment uses a
    fixed interval).

    The baseline moves only at well-defined points: the first measurement
    establishes it, and every maintenance pass resets it
    (:meth:`record_maintenance`). The old behaviour min-ratcheted it on
    *every* :meth:`should_migrate` call, so one lucky low slice — traffic
    noise, a transiently favourable map — dragged the baseline below
    anything the graph can sustain and every later slice read as
    "degraded": the service migrated permanently until the next
    maintenance reset (and forever, for callers that migrate outside the
    maintenance cycle). Improvements worth keeping as the reference are
    recorded explicitly via :meth:`record_maintenance`.
    """

    def __init__(self, min_move_fraction: float = 0.002, degradation_factor: float = 1.25):
        self.min_move_fraction = min_move_fraction
        self.degradation_factor = degradation_factor
        self.baseline_percent_global = np.inf
        self.history: List[Dict] = []

    def should_migrate(self, percent_global: float) -> bool:
        if not np.isfinite(self.baseline_percent_global):
            # First-ever measurement: nothing to compare against yet.
            self.baseline_percent_global = float(percent_global)
            return False
        return percent_global > self.baseline_percent_global * self.degradation_factor

    def record_maintenance(self, percent_global: float) -> None:
        """Reset the degradation baseline to a post-maintenance measurement.

        Callers (the dynamic-experiment runtime) invoke this with the
        traffic share measured right after a maintenance pass, so
        :meth:`should_migrate` judges degradation relative to what the
        *current* graph can achieve, not the first-ever measurement.
        """
        self.baseline_percent_global = float(percent_global)

    def plan(
        self, old_parts: np.ndarray, new_parts: np.ndarray, step: int = 0
    ) -> List[MigrationCommand]:
        """Group the parts delta into per-target migration commands.

        ``step`` is the caller's logical step/epoch counter — history is
        keyed by it (a wall-clock stamp made runs unreplayable). Grouping
        is one stable sort + split instead of a per-target scan.
        """
        moved = np.nonzero(old_parts != new_parts)[0]
        if moved.shape[0] < self.min_move_fraction * old_parts.shape[0]:
            return []
        tgt = np.asarray(new_parts)[moved]
        order = np.argsort(tgt, kind="stable")
        uniq, starts = np.unique(tgt[order], return_index=True)
        cmds = [
            MigrationCommand(vertices=vs, target=int(t))
            for t, vs in zip(uniq, np.split(moved[order], starts[1:]))
        ]
        self.history.append({"step": int(step), "n_moved": int(moved.shape[0])})
        return cmds

    @staticmethod
    def apply(parts: np.ndarray, cmds: List[MigrationCommand]) -> np.ndarray:
        out = parts.copy()
        for c in cmds:
            out[c.vertices] = c.target
        return out


class PartitionedGraphService:
    """Emulator-style partitioned graph database (paper §5.3.2).

    One logical graph, a partition map, and the measurement machinery.
    Drives the Static / Insert / Stress / Dynamic experiments and is reused
    by the distributed placement layer.

    ``mesh`` selects the device engines for every leg (sharded traffic
    replay + mesh DiDiC maintenance); ``maintenance`` refines that choice:

    * ``"auto"``    — sharded DiDiC when a mesh is present,
    * ``"sharded"`` — require the mesh DiDiC (error without a mesh),
    * ``"shared"``  — keep the single-device DiDiC even on a mesh, so a
      device-engine run stays bit-identical to the host reference loop
      (the sharded DiDiC sums float32 in a different order).
    """

    def __init__(
        self,
        graph: Graph,
        k: int,
        didic: Optional[DidicConfig] = None,
        *,
        mesh=None,
        data_axes: Tuple[str, ...] = ("data",),
        maintenance: str = "auto",
        exception_capacity: int = 0,
    ):
        if maintenance not in ("auto", "sharded", "shared"):
            raise ValueError(f"unknown maintenance mode {maintenance!r}")
        if maintenance == "sharded" and mesh is None:
            raise ValueError("maintenance='sharded' requires a mesh")
        self.graph = graph
        self.k = k
        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        # Placement = owner array + fixed-capacity exception table of
        # replicated hot vertices (module docstring). ``parts`` stays the
        # public name for the owner array; capacity 0 (the default) is
        # bit-identical to the pre-placement single-assignment service.
        self.placement = Placement(
            owner=np.zeros(graph.n_nodes, dtype=np.int32),
            capacity=int(exception_capacity),
        )
        # Evaluation logs served so far, keyed by content fingerprint (the
        # same identity contract as ``get_replayer``'s cache): structural
        # dynamism must migrate their device-resident replay state onto
        # the updated graph, and a regenerated-but-equal log must land on
        # the original's resident state, not allocate a second one. LRU —
        # logs beyond ``max_resident_logs`` have their device-resident
        # replay artifacts evicted so a long-running service's memory is
        # bounded by the working set, not its history.
        self._replayed_logs: "OrderedDict[str, OpLog]" = OrderedDict()
        self.max_resident_logs = 8
        # Fault-tolerance layer (repro.core.fault / repro.core.recovery):
        # an attached FaultPlan injects deterministic shard failures,
        # maintenance timeouts, and crashes; failed_shards (explicit marks
        # union the plan's schedule) degrade sharded replay to the shared
        # engine; a DynamismJournal makes apply_dynamism a write-ahead,
        # exactly-once (fingerprint-keyed) operation; retry_policy bounds
        # maintenance retries. All optional — a bare service runs exactly
        # as before.
        self.fault_plan = None
        self.journal = None
        self.retry_policy = None
        self.failed_shards: set = set()
        # Fingerprints of journal-managed logs already applied, LRU-bounded
        # (idempotency window for journal replay after recovery).
        self._applied_dynamism: "OrderedDict[str, None]" = OrderedDict()
        self.max_applied_fingerprints = 256
        self.logger = RuntimeLogger(k)
        maint_mesh = mesh if maintenance in ("auto", "sharded") else None
        self.runtime = RuntimePartitioner(
            didic or DidicConfig(k=k), mesh=maint_mesh, data_axes=self.data_axes
        )
        self.scheduler = MigrationScheduler()

    @property
    def engine(self) -> str:
        """Which engine family serves this service: ``host`` or ``device``."""
        return "device" if self.mesh is not None else "host"

    # -- placement ----------------------------------------------------------
    @property
    def parts(self) -> np.ndarray:
        """The owner array of the service placement.

        Kept as the public partition-map interface: every consumer of the
        single-assignment model (engines, scheduler, snapshots, the
        distributed placement layer) reads and replaces whole owner maps
        through this property. In-place element writes would bypass
        replica invalidation — route vertex moves through
        :meth:`apply_dynamism` or :meth:`commit_migration` instead (the
        ``placement/single-owner`` lint rule flags violations).
        """
        return self.placement.owner

    @parts.setter
    def parts(self, value: np.ndarray) -> None:
        self.placement.replace_owner(np.asarray(value))

    def refresh_placement(self, hysteresis: float = 1.25) -> np.ndarray:
        """Re-select the exception table from accumulated per-vertex
        traffic (promotion with hysteresis — see
        :func:`repro.core.partitioners.select_hot_vertices`). Returns the
        new hot-vertex array. No-op on a capacity-0 placement.
        """
        from repro.core.partitioners import select_hot_vertices

        if self.placement.capacity == 0:
            return self.placement.hot_vertices()
        hot = select_hot_vertices(
            self.logger.vertex_traffic, self.placement.capacity,
            current_hot=self.placement.hot_vertices(), hysteresis=hysteresis,
        )
        self.placement.set_hot(hot)
        return self.placement.hot_vertices()

    # -- partitioning -------------------------------------------------------
    def partition_with(self, parts: np.ndarray) -> "PartitionedGraphService":
        assert parts.shape[0] == self.graph.n_nodes
        self.parts = parts.astype(np.int32)
        self.logger.observe_structure(self.graph, self.parts)
        return self

    def partition_didic(self, seed: int = 0) -> "PartitionedGraphService":
        return self.partition_with(self.runtime.initial(self.graph, seed=seed))

    def _maintain_attempt(self, fn):
        """Run one maintenance computation under the fault plan.

        An injected :class:`~repro.core.fault.MaintenanceTimeout` fires
        *before* the deterministic DiDiC computation, so a retried attempt
        reproduces the uninterrupted result bit-for-bit; retries back off
        under the service's :class:`~repro.core.fault.RetryPolicy` and a
        spent budget raises
        :class:`~repro.core.fault.RecoveryDeadlineExceeded`. Retry counts
        and elapsed retry time land in the logger's health metrics.
        """
        if self.fault_plan is None:
            return fn()
        from repro.core.fault import MaintenanceTimeout, RetryPolicy

        policy = self.retry_policy or RetryPolicy()
        t0 = _time.perf_counter()
        attempt = 0
        while True:
            try:
                self.fault_plan.fire("maintain")
                out = fn()
            except MaintenanceTimeout:
                attempt += 1
                policy.wait(attempt, _time.perf_counter() - t0)
                continue
            if attempt:
                self.logger.record_maintenance_retries(
                    attempt, _time.perf_counter() - t0
                )
            return out

    def _repair(self, parts: np.ndarray, iterations: int) -> np.ndarray:
        """DiDiC maintenance from ``parts``, until the new map is on the
        host; counts the iterations, their sparse products and their
        smoothing steps (a repair smooths at the full width, ``smooth_cap``)."""
        c = self.runtime.config
        tracing.count("didic.iterations", iterations)
        tracing.count("didic.spmms", iterations * (
            c.primary_steps * (c.secondary_steps + 1) + c.smooth_cap))
        tracing.count("didic.smooth_steps", iterations * c.smooth_cap)
        with tracing.span("didic.repair"):
            return self._maintain_attempt(
                lambda: self.runtime.maintain(self.graph, parts,
                                              iterations=iterations,
                                              pinned=self.placement.hot_vertices())
            )

    def maintain(self, iterations: int = 1) -> None:
        self.parts = self._repair(self.parts, iterations)
        self.logger.observe_structure(self.graph, self.parts)

    def propose_maintenance(self, iterations: int = 1,
                            parts: Optional[np.ndarray] = None) -> np.ndarray:
        """Run a maintenance refinement and return the proposed map
        without adopting it.

        ``parts`` defaults to the served map; the online front-end passes
        its background round's working copy so a multi-tick budgeted
        round diffuses from its own intermediate map while the service
        keeps serving the committed one. Advances ``runtime.state`` — a
        caller that may discard the proposal snapshots the state first
        and hands it to :meth:`commit_migration` for rollback.
        """
        return self._repair(self.parts if parts is None else parts, iterations)

    @tracing.span("migrate.commit")
    def commit_migration(self, scheduler: MigrationScheduler,
                         new_parts: np.ndarray, step: int,
                         prev_state=None) -> int:
        """Adopt a proposed map through the Migration-Scheduler.

        The scheduler turns the delta into per-target migration commands
        (recorded against the logical ``step``) and applies them. Returns
        the number of migrated vertices — the dynamic experiment's
        migration-volume metric.

        If the scheduler rejects a non-trivial plan (below its move
        threshold), the partitioner's diffusion state is rolled back to
        ``prev_state``: keeping state from a refinement that was never
        adopted would make later maintenance diffuse from a map the
        service never served.
        """
        cmds = scheduler.plan(self.parts, new_parts.astype(np.int32), step=step)
        if not cmds and (self.parts != new_parts).any():
            self.runtime.state = prev_state
            return 0
        self.parts = scheduler.apply(self.parts, cmds)
        if cmds and self.placement.n_hot:
            # A migration is an ownership write: replicas of moved
            # vertices are stale and must drop. (Pinned maintenance never
            # proposes such moves, but migration commands can originate
            # elsewhere.)
            self.placement.invalidate(
                np.concatenate([c.vertices for c in cmds])
            )
        self.logger.observe_structure(self.graph, self.parts)
        moved = int(sum(c.vertices.shape[0] for c in cmds))
        tracing.count("migrate.moves", moved)
        return moved

    def maintain_migrate(self, scheduler: MigrationScheduler, step: int,
                         iterations: int = 1) -> int:
        """Stop-the-world maintenance pass applied through the
        Migration-Scheduler: propose then commit in one call (the dynamic
        experiment's per-slice cycle). The online front-end uses the two
        halves separately to spread the proposal over budgeted background
        ticks (:class:`repro.core.online.BackgroundMaintenance`)."""
        prev_state = self.runtime.state
        new_parts = self.propose_maintenance(iterations=iterations)
        return self.commit_migration(scheduler, new_parts, step,
                                     prev_state=prev_state)

    # -- workload -----------------------------------------------------------
    def run_ops(self, ops: OpLog, engine: str = "auto",
                resident: bool = True) -> TrafficResult:
        """Replay an evaluation log.

        ``engine``: ``auto`` (sharded when the service has a mesh, else
        the batched single-device engine) | ``sharded`` | ``batched`` |
        ``scalar``. All engines are bit-equal on every counter.

        ``resident`` (sharded path only) keeps the log's parts-independent
        solve artifacts device-resident across replays
        (:class:`repro.core.traffic_sharded.ResidentReplayState`), so
        repeated replays of one log against an evolving partition map —
        the dynamic experiment's measurement loop — reduce to the
        partition-dependent counter fold. ``resident=False`` forces a full
        cold solve (the bit-equality comparator). Equal-content logs share
        one resident state (:meth:`_register_log`).

        **Degraded mode.** When any mesh shard is marked failed —
        explicitly (:meth:`mark_shard_failed`) or by the attached fault
        plan's schedule — the sharded replay falls back to the shared
        single-device batched engine for the whole log. The fallback is
        bit-equal on all four counters (the sharded engine's exactness
        contract), so a degraded measurement is still a valid one; the
        ops whose home shard failed are counted in the logger's
        ``degraded_ops`` and each fallback replay in ``degraded_replays``.
        """
        if self.fault_plan is not None:
            self.fault_plan.fire("replay")
        if engine == "sharded" and self.mesh is None:
            raise ValueError("engine='sharded' requires a service mesh")
        replicated = self.placement.replicated_mask()
        if engine == "sharded" or (engine == "auto" and self.mesh is not None):
            failed = self._currently_failed_shards()
            if failed:
                result = execute_ops(self.graph, ops, self.parts, self.k,
                                     engine="batched", replicated=replicated)
                self.logger.record_degraded(self._degraded_op_count(ops, failed))
            else:
                from repro.core.traffic_sharded import replay_sharded  # lazy: jax mesh

                ops = self._register_log(ops)
                result = replay_sharded(
                    self.graph, ops, self.mesh, self.parts, self.k,
                    data_axes=self.data_axes, resident=resident,
                    replicated=replicated,
                )
                self.logger.resident_state_bytes = self._resident_state_bytes()
        else:
            result = execute_ops(self.graph, ops, self.parts, self.k, engine=engine,
                                 replicated=replicated)
        self.logger.observe_traffic(result)
        return result

    def _resident_state_bytes(self) -> int:
        """Sum the device-resident replay-state footprint across the
        service's registered evaluation logs (all replayers)."""
        total = 0
        for ops in self._replayed_logs.values():
            for state in ops.__dict__.get("_resident_replay", {}).values():
                total += state.state_bytes()
        return total

    # -- shard health --------------------------------------------------------
    def mark_shard_failed(self, shard: int) -> None:
        """Mark a mesh data shard unavailable; sharded replay degrades to
        the shared engine until :meth:`mark_shard_recovered`."""
        self.failed_shards.add(int(shard))

    def mark_shard_recovered(self, shard: int) -> None:
        self.failed_shards.discard(int(shard))

    def _currently_failed_shards(self) -> set:
        failed = set(self.failed_shards)
        if self.fault_plan is not None:
            failed |= set(self.fault_plan.failed_shards())
        return failed

    def _degraded_op_count(self, ops: OpLog, failed: set) -> int:
        """Ops whose home shard (contiguous split, the sharded replay's
        layout) is down — the measurement the degraded path re-serves."""
        from repro.distributed.counters import data_shard_count  # lazy: jax

        shards = data_shard_count(self.mesh, self.data_axes)
        b = -(-max(ops.n_ops, 1) // shards)
        return sum(
            max(0, min(ops.n_ops, (s + 1) * b) - min(ops.n_ops, s * b))
            for s in failed if 0 <= s < shards
        )

    def _register_log(self, ops: OpLog) -> OpLog:
        """Register an evaluation log in the resident-replay working set.

        Dedupe is by content fingerprint: a regenerated-but-equal log
        resolves to the first-seen object (whose device-resident solve
        state it then reuses — a second object would silently double the
        device footprint). The registry is LRU-bounded; evicted logs have
        their resident replay states dropped so long-running services do
        not leak device memory across an unbounded log history.
        """
        fp = ops.fingerprint()
        cached = self._replayed_logs.get(fp)
        if cached is not None:
            self._replayed_logs.move_to_end(fp)
            return cached
        self._replayed_logs[fp] = ops
        while len(self._replayed_logs) > self.max_resident_logs:
            _, evicted = self._replayed_logs.popitem(last=False)
            evicted.__dict__.pop("_resident_replay", None)
        return ops

    def make_ops(self, n_ops: int = 10_000, seed: int = 0, pattern: Optional[str] = None) -> OpLog:
        return generate_ops(self.graph, n_ops=n_ops, seed=seed, pattern=pattern)

    # -- dynamism -----------------------------------------------------------
    @tracing.span("dynamism.apply")
    def apply_dynamism(self, log: DynamismLog) -> None:
        """Apply a dynamism slice: partition moves, edge inserts, and —
        for vertex-growth logs — new vertices.

        A structural log rebuilds the service graph via
        :meth:`~repro.graphs.structure.Graph.with_vertices` /
        :meth:`~repro.graphs.structure.Graph.with_edges` and migrates the
        device-resident replay state of every served evaluation log onto
        the new graph, marking the log's dirty vertices so only the ops
        whose expansion footprint they touch are re-solved on the next
        replay (pure-move logs never dirty graph-pure artifacts). New
        vertices join ``parts`` on the partition the log allocated them.

        The application is atomic: every validation — shape/bounds checks
        in the graph rebuild, the admissibility check — runs *before* any
        service state mutates, so a rejected log leaves ``parts``,
        ``graph``, and the logger exactly as they were.

        **Write-ahead journal.** With a
        :class:`~repro.core.recovery.DynamismJournal` attached, application
        is journaled and *exactly-once per log fingerprint*: the intent
        (full log payload) is written before any validation, the commit
        mark after every mutation succeeded, and a log whose fingerprint
        was already applied on this service is a no-op — which is what
        lets crash recovery replay the journal (or regenerate the same
        slice) without double-applying. A validation failure marks the
        entry aborted; an injected crash leaves it pending for the
        recovery driver to replay or roll back
        (:func:`repro.core.recovery.replay_journal`).
        """
        journal, plan = self.journal, self.fault_plan
        fp = None
        if journal is not None:
            fp = log.fingerprint()
            if fp in self._applied_dynamism:
                self._applied_dynamism.move_to_end(fp)
                return
            journal.begin(log, fp)
        try:
            if plan is not None:
                plan.fire("apply:pre_validate")
            self._apply_dynamism_checked(log)
        except BaseException as e:
            from repro.core.fault import SimulatedCrash

            # A crash "kills the process" mid-apply: the entry stays
            # pending in the (durable) journal for recovery to resolve.
            # Any real validation error is a clean rejection: aborted.
            if journal is not None and not isinstance(e, SimulatedCrash):
                journal.abort(fp)
            raise
        if journal is not None:
            journal.commit(fp)
            self._applied_dynamism[fp] = None
            while len(self._applied_dynamism) > self.max_applied_fingerprints:
                self._applied_dynamism.popitem(last=False)
        if plan is not None:
            plan.fire("apply:post_commit")

    def _apply_dynamism_checked(self, log: DynamismLog) -> None:
        """Validate-then-commit application body (journal-agnostic)."""
        tracing.count("dynamism.moves", log.units - log.n_new_vertices)
        plan = self.fault_plan
        if not log.structural:
            new_parts = apply_dynamism(self.parts, log)
            if plan is not None:
                plan.fire("apply:pre_commit")
            self.parts = new_parts
            # A partition move is an ownership write: replicas of moved
            # vertices are invalidated (single-owner write rule).
            self.placement.invalidate(log.vertices)
            self.logger.observe_structure(self.graph, self.parts)
            return
        old_graph = self.graph
        # -- validate (no mutation yet) ------------------------------------
        # Structural growth runs on the delta-overlay store: attach one at
        # default headroom on first growth (idempotent — attaching changes
        # no graph content), and surface the imminent amortized rebuild as
        # a crash site when this log overflows the delta region.
        store = old_graph.ensure_store()
        n_new_edges = (
            0 if log.insert_senders is None
            else int(np.asarray(log.insert_senders).shape[0])
        )
        if plan is not None and store.would_overflow(
            old_graph, log.n_new_vertices, n_new_edges
        ):
            plan.fire("apply:compact")
        if log.n_new_vertices:
            if log.base_nodes is not None and log.base_nodes != old_graph.n_nodes:
                raise ValueError(
                    f"vertex-growth log grows a base of {log.base_nodes} "
                    f"vertices but the service graph has {old_graph.n_nodes}"
                )
            new_graph = old_graph.with_vertices(  # validates shapes + bounds
                log.n_new_vertices, log.insert_attrs,
                log.insert_senders, log.insert_receivers, log.insert_weights,
            )
        else:
            new_graph = old_graph.with_edges(  # validates shapes + bounds
                log.insert_senders, log.insert_receivers, log.insert_weights
            )
        self._check_insert_admissible(log)
        new_parts = apply_dynamism(self.parts, log)
        if plan is not None:
            plan.fire("apply:pre_commit")
        # -- commit (nothing below may raise) ------------------------------
        self.parts = new_parts
        self.graph = new_graph
        # Writes route through ownership: moved vertices and vertices whose
        # structure this log touches (insert endpoints, growth anchors)
        # drop their read replicas.
        self.placement.invalidate(
            np.concatenate([
                np.asarray(log.vertices, dtype=np.int64), log.dirty_vertices(),
            ])
        )
        if log.n_new_vertices:
            # Carried diffusion state is per-vertex; growth invalidates it.
            # The next maintenance pass re-seeds from the (grown) parts.
            self.runtime.state = None
        if self.mesh is not None:
            from repro.core.traffic_sharded import migrate_resident_states

            dirty = log.dirty_vertices()
            for ops in self._replayed_logs.values():
                migrate_resident_states(ops, old_graph, self.graph, dirty)
        self.logger.observe_structure(self.graph, self.parts)

    def prepare_growth(self) -> None:
        """Arm the service for vertex growth (the delta-overlay layer).

        Attaches a :class:`~repro.graphs.structure.GraphStore` at default
        headroom and prewarms the capacity-shaped single-device
        maintenance closure with a throwaway refine, so the one-time
        traces land in the warmup slice instead of leaking into the
        steady state the recompile sentinel audits. Idempotent, and cheap
        after the first call. The traffic engines need no explicit
        prewarm — their next replay replaces the extent-shaped trace with
        the capacity-shaped one — but maintenance's first natural call
        sits mid-schedule, which would otherwise count as a steady-state
        retrace.
        """
        if self.graph.store is None:
            self.graph.ensure_store()
        if self.runtime.mesh is None:
            # Discarded: only runs to trace the overlay DiDiC step and
            # populate the store-cached coefficient tables.
            didic_refine(
                self.graph, self.parts, self.runtime.config,
                state=None, iterations=1, seed=0,
            )
        else:
            # Same idea for sharded maintenance: trace the capacity-shaped
            # mesh program (store-lineage-cached) during warmup.
            from repro.core.didic_distributed import didic_refine_distributed

            didic_refine_distributed(
                self.graph, self.parts, self.runtime.config,
                self.runtime.mesh, self.runtime.data_axes,
                state=None, iterations=1, seed=0,
            )

    def _check_insert_admissible(self, log: DynamismLog) -> None:
        """Reject edge inserts lighter than the straight-line distance.

        On coordinate graphs the whole GIS measurement stack — the A*
        heuristic, the window-acceptance proof, and the resident path's
        footprint invalidation ("any changed route has an endpoint inside
        the old f ≤ f_dst set") — relies on weights ≥ Euclidean length.
        An underweight insert would silently break the bit-identical
        contract instead of failing loudly, so it is refused here. Runs
        before :meth:`apply_dynamism` mutates anything, so new vertices'
        coordinates come from the *log's* attribute rows, not the (still
        un-grown) service graph.
        """
        attrs = self.graph.node_attrs
        if "lon" not in attrs or "lat" not in attrs:
            return
        s = np.asarray(log.insert_senders, dtype=np.int64)
        r = np.asarray(log.insert_receivers, dtype=np.int64)
        w = (np.ones(s.shape[0], dtype=np.float32)
             if log.insert_weights is None
             else np.asarray(log.insert_weights, dtype=np.float32))
        lon = np.asarray(attrs["lon"], dtype=np.float64)
        lat = np.asarray(attrs["lat"], dtype=np.float64)
        if log.n_new_vertices:
            if "lon" not in log.insert_attrs or "lat" not in log.insert_attrs:
                raise ValueError(
                    "vertex growth on a coordinate graph requires lon/lat "
                    "rows in the log's insert_attrs"
                )
            # Compare against the coordinates as they will be *stored*
            # (graph dtype), so admissibility matches the grown graph.
            lon = np.concatenate([lon, np.asarray(
                log.insert_attrs["lon"], dtype=attrs["lon"].dtype
            ).astype(np.float64)])
            lat = np.concatenate([lat, np.asarray(
                log.insert_attrs["lat"], dtype=attrs["lat"].dtype
            ).astype(np.float64)])
        dist = np.hypot(lon[s] - lon[r], lat[s] - lat[r])
        # float32 storage may round the weight to just under the float64
        # distance; allow that rounding, nothing more.
        short = w.astype(np.float64) < dist * (1.0 - 1e-6)
        if short.any():
            i = int(np.nonzero(short)[0][0])
            raise ValueError(
                "structural insert weight below straight-line length "
                f"(edge {int(s[i])}→{int(r[i])}: w={float(w[i]):g} < "
                f"{float(dist[i]):g}) — inadmissible for the GIS heuristic "
                "and the resident footprint invariant"
            )

    # -- reporting ----------------------------------------------------------
    def report(self) -> Dict[str, float]:
        return metrics.partition_report(self.graph, self.parts, self.k)
