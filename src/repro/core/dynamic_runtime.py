"""Device-resident dynamic-experiment runtime (ISSUE 3 tentpole).

The paper's maintenance experiments (§6.4, §7.5–7.6) run one cycle per
5 % dynamism slice:

    dynamism slice  →  (intermittent) DiDiC maintenance  →  traffic replay

and the replayed per-vertex traffic feeds the *next* slice's
``least_traffic`` insert policy. Until this PR the whole cycle lived in
host numpy loops (``core/dynamism.py`` + ``benchmarks/paper_tables.py``)
even though every leg already had a device implementation. This module
fuses the legs into one mesh-native pipeline:

* **Dynamism generation on device** — the sequential
  ``fewest_vertices`` / ``least_traffic`` oracles become a single
  :func:`jax.lax.scan` over move units (:func:`scan_dynamism_targets`).
  Targets are **bit-identical** to the host oracle in
  :mod:`repro.core.dynamism` (which stays as the reference): integer
  argmin ties break identically, and the ``least_traffic`` totals — exact
  integers that the oracle carries in float64 — are carried on device as
  **base-2²⁰ int32 digit pairs** (the device has no x64), so every
  update and every lexicographic argmin is exact. This is the same
  int32-device / int64-host split as :mod:`repro.distributed.counters`,
  and unlike an ``enable_x64`` escape hatch it runs unchanged on TPU.
* **Maintenance on the mesh** — :class:`~repro.core.framework.RuntimePartitioner`
  routes ``maintain`` through
  :func:`repro.core.didic_distributed.didic_refine_distributed`, whose
  diffusion state (``w``/``l``/``beta`` and the padded partition map)
  stays sharded across the whole slice schedule.
* **Traffic on the mesh** — measurement goes through
  :func:`repro.core.traffic_sharded.replay_sharded` (bit-equal to the
  batched engine), and its ``per_vertex`` counters close the loop into
  the next slice's insert policy.

:class:`DynamicExperimentRuntime` drives the cycle on top of a
:class:`~repro.core.framework.PartitionedGraphService`; the service's
``mesh`` decides host vs device for every leg behind the same interface.

The maintenance leg is decomposed (ISSUE 9): the service exposes
``propose_maintenance`` (run refinement iterations on a working map,
carrying the resumable DiDiC state) and ``commit_migration`` (adopt a
proposal through the Migration-Scheduler) separately, and
``maintain_migrate`` — which this runtime still calls, bit-identically —
is their stop-the-world composition. The online front-end
(:mod:`repro.core.online`) uses the halves to run the same maintenance
as *background* work, budgeted iterations interleaved between admission
batches, while the service keeps serving the committed map.

Parity contract: with ``maintenance="shared"`` (both engines calling the
same single-device DiDiC refine) the device runtime reproduces the
host-loop reference **bit-identically** on all four traffic counters for
a full slice schedule — asserted on a forced 8-device CPU mesh in
``tests/test_dynamic_runtime.py``. With ``maintenance="sharded"`` the
halo-exchange DiDiC is float32-sum-order different from the
single-device refine (same algorithm, different reduction association),
so that mode trades bit-parity for mesh scalability and is validated by
quality tests instead.

Resident replay state (ISSUE 4 tentpole)
----------------------------------------
Each replay the cycle issues goes through
:class:`repro.core.traffic_sharded.ResidentReplayState`, which keeps one
log's solve artifacts device-resident across every slice of a dynamic
run. Its lifecycle splits three ways:

* **graph-pure** (solved once per (graph, log), reused for every slice):
  GIS window membership/footprint masks ``[S, W, C]`` with their window
  ids, per-op edge counts, BFS per-op expansion levels and the per-vertex
  frontier mass ``tm`` — none of these read the partition map.
* **parts-dependent** (recomputed every slice from the current map):
  cross-degree, the per-op cross counters (an integer
  ``member × cross_w`` fold over the resident masks — order-free, hence
  bit-identical to the cold solve), and the finalize-side
  per-partition/per-vertex attribution.
* **slice-dirty** (invalidated by a slice's *structural* inserts): a
  :class:`~repro.core.dynamism.DynamismLog` that inserts edges — or, for
  the Insert workload (``insert_rate > 0``), whole new vertices — dirties
  exactly the vertices it touches; ops whose expansion footprint
  intersects that set are re-solved through the replicated whole-graph
  redo layout on the next replay, and everything else stays resident
  (migrated onto the grown graph by
  :func:`repro.core.traffic_sharded.migrate_resident_states`).
  Pure partition moves dirty nothing.

Zero-recompile growth (ISSUE 8 tentpole)
----------------------------------------
Vertex growth used to be the cycle's dominant cost — not compute, but
recompilation: every ``with_vertices`` changed ``N`` and retraced the
replay, scan, and maintenance closures (~1–3.5 s/slice). With a
:class:`~repro.graphs.structure.GraphStore` attached (see
:meth:`~repro.core.framework.PartitionedGraphService.prepare_growth`,
called automatically on the first growing slice), every compiled shape is
sized to the store's *capacity* instead of the current extents:

* the dynamism scans here pad their unit buffers to the capacity-sized
  slice (``pad_units`` in :func:`_unroll_blocks` — dead units ride the
  existing tail mask, so targets are bit-identical at any pad);
* the replay engines pad their gather tables to ``n_cap``/``e_cap`` with
  an inert sentinel row and **adopt** grown graphs in place
  (:meth:`repro.core.traffic_batched.BatchedTrafficEngine.adopt`), their
  closures rekeyed by store rather than graph identity;
* maintenance folds live-vertex masks into capacity-padded diffusion
  state (:mod:`repro.core.didic`).

Growth then reuses every compiled program until the delta region fills,
at which point one amortized compaction re-sizes the capacity (an
explicit ``compactions`` counter — the only post-warmup retrace allowed,
and the sentinel schedule is provisioned to need none). The recompile
sentinel (:mod:`repro.analysis.recompile`) asserts the steady state:
zero retraces after slice 1 on the 20×5 % growth schedule.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.core.framework import (
    InsertPartitioner,
    MigrationScheduler,
    PartitionedGraphService,
)
from repro.core.traffic import OpLog, TrafficResult

__all__ = [
    "scan_dynamism_targets",
    "SliceRecord",
    "DynamicRunResult",
    "DynamicExperimentRuntime",
]

# least_traffic totals are exact integers; the device carries them as two
# int32 digits in base 2**_DIGIT_BITS. Per-vertex and per-partition totals
# must stay below 2**(31 + _DIGIT_BITS) = 2**51 — the same ceiling as
# float64 integer exactness (2**53), so the host oracle and the device scan
# agree wherever either is defined.
_DIGIT_BITS = 20
_DIGIT = np.int32(1 << _DIGIT_BITS)
_VALUE_CEIL = 1 << (31 + _DIGIT_BITS)

# Move units processed per lax.scan step. The sequential oracles are pure
# dispatch overhead on CPU (~10 µs/unit at unroll 1 — every step is one
# tiny argmin + two scatters behind a while-loop trip); unrolling amortizes
# the dispatch over _SCAN_UNROLL units while keeping the *sequence* of
# (argmin, update) operations — and therefore every target — bit-identical.
# The tail is masked: dead sub-steps add 0 and leave the carry untouched.
#
# The scans deliberately carry NO [N]-sized partition map. A unit only ever
# reads ``cur[v]`` for its own mover, and that value is either the mover's
# *initial* partition or the target of its previous move — an index into
# the targets emitted so far. Previous-occurrence indices are a pure
# function of the mover sequence, computed vectorized on the host
# (:func:`_unroll_blocks`), so the device state is just the k-sized
# counters plus the [units] target buffer: per-unit work is O(k + unroll),
# independent of graph size.
_SCAN_UNROLL = 8


def _split_digits(x64: np.ndarray):
    """int64 ≥ 0 → (hi, lo) int32 digits with ``x = hi·2²⁰ + lo``."""
    hi = (x64 >> _DIGIT_BITS).astype(np.int32)
    lo = (x64 & (int(_DIGIT) - 1)).astype(np.int32)
    return hi, lo


def _unroll_blocks(movers: np.ndarray, parts: np.ndarray,
                   extra: Tuple[np.ndarray, ...] = (),
                   insert: Optional[np.ndarray] = None,
                   pad_units: int = 0) -> np.ndarray:
    """Host-side block prep for the unrolled scans.

    Returns one packed int32 array ``[T/U, 5 + len(extra), U]`` — a
    *single* device transfer per call (per-call transfer count dominates
    the dynamic cycle's insert leg at real slice sizes). Rows per block:
    ``src0`` (each mover's initial partition), ``prev_in`` (in-block
    offset of the mover's previous move, −1 if none), ``prev_out`` (its
    absolute index when in an earlier block, −1 otherwise), ``live`` (the
    tail mask), ``is_insert`` (vertex-allocation units — no source to
    decrement, and their mover slot is the attachment anchor, not a moved
    vertex), then any ``extra`` per-unit rows (the least-traffic digits).

    ``pad_units`` pins the padded unit count (rounded up to a whole
    block): store-backed graphs pass the capacity-sized slice size so the
    packed shape — and hence the scan's compiled program — is identical
    for every slice between compactions, even as ``|V|`` (and with it the
    live unit count) grows. Padded units ride the existing tail-mask
    mechanism (``live=0``, ``prev_out=-1``), which leaves the carry
    untouched, so the emitted targets are bit-identical at any pad.
    """
    u = _SCAN_UNROLL
    movers = np.asarray(movers, dtype=np.int64)
    units = movers.shape[0]
    # prev[j] = latest j' < j with movers[j'] == movers[j], else -1
    # (stable sort groups occurrences of one mover in index order). Insert
    # units never move their anchor, so they take unique pseudo-ids: they
    # link to nothing and later moves of the anchor skip past them.
    movers_eff = movers
    if insert is not None and insert.any():
        movers_eff = movers.copy()
        movers_eff[insert] = -1 - np.arange(int(insert.sum()), dtype=np.int64)
    order = np.lexsort((np.arange(units), movers_eff))
    sm = movers_eff[order]
    prev = np.full(units, -1, dtype=np.int64)
    if units > 1:
        same = sm[1:] == sm[:-1]
        prev[order[1:][same]] = order[:-1][same]
    j0s = (np.arange(units) // u) * u
    in_block = prev >= j0s
    prev_in = np.where(in_block, prev - j0s, -1)
    prev_out = np.where(~in_block & (prev >= 0), prev, -1)

    rows = (
        np.asarray(parts, dtype=np.int64)[movers], prev_in, prev_out,
        np.ones(units, dtype=np.int64),
        np.zeros(units, dtype=np.int64) if insert is None
        else insert.astype(np.int64),
    ) + tuple(extra)
    total = -(-max(units, int(pad_units)) // u) * u
    packed = np.zeros((len(rows), total), dtype=np.int32)
    packed[2, units:] = -1  # padded prev_out must stay "none"
    for i, row in enumerate(rows):
        packed[i, :units] = row
    return packed.reshape(len(rows), -1, u).transpose(1, 0, 2)


def _block_src(buf, blk, ts, j):
    """The mover's current partition as the sequential oracle sees it:
    its previous move's target (this block: a few scalar selects; earlier
    blocks: one read of the target buffer), else its initial partition."""
    src = jnp.where(blk[2, j] >= 0, buf[jnp.maximum(blk[2, j], 0)], blk[0, j])
    for jp in range(j):
        src = jnp.where(blk[1, j] == jp, ts[jp], src)
    return src


@jax.jit
def _fewest_vertices_scan(counts0, packed):
    """Sequential fewest-vertices oracle, ``_SCAN_UNROLL`` units per step.

    ``jnp.argmin`` and ``np.argmin`` both return the *first* minimum, so
    the tie-breaks — the only freedom in the policy — match the host loop
    exactly; counts are integers, so everything else is exact arithmetic.
    A dead (tail-mask) sub-step adds 0 to the counts, so the live prefix
    sees the exact sequential state. Insert units (blk row 4) allocate a
    new vertex: the target gains one, no source loses one.
    """
    n_pad = packed.shape[0] * _SCAN_UNROLL
    buf0 = jnp.zeros((max(n_pad, _SCAN_UNROLL),), jnp.int32)

    def step(carry, blk):
        counts, buf, base = carry
        ts = []
        for j in range(_SCAN_UNROLL):
            src = _block_src(buf, blk, ts, j)
            t = jnp.argmin(counts).astype(jnp.int32)
            inc = blk[3, j]  # live mask as 0/1
            dec = inc * (1 - blk[4, j])  # moves decrement their source
            counts = counts.at[src].add(-dec).at[t].add(inc)
            ts.append(t)
        buf = jax.lax.dynamic_update_slice(buf, jnp.stack(ts), (base,))
        return (counts, buf, base + _SCAN_UNROLL), None

    (_, buf, _), _ = jax.lax.scan(
        step, (counts0, buf0, jnp.int32(0)), packed
    )
    return buf[:n_pad]


@jax.jit
def _least_traffic_scan(tr_hi0, tr_lo0, packed):
    """Sequential least-traffic oracle, unrolled, in digit arithmetic.

    Per-partition traffic is ``hi·2²⁰ + lo`` with ``0 ≤ lo < 2²⁰`` (the
    carry is normalized every sub-step), so lexicographic ``(hi, lo)``
    order equals numeric order and the first-lex-min below reproduces
    ``np.argmin`` over the oracle's float64 totals bit-for-bit. Dead
    sub-steps move 0 traffic, so the normalization is a no-op there.
    ``packed`` rows 5/6 carry the movers' traffic digits (host-gathered —
    every scan input is [units]-sized, never [N]-sized); insert units'
    digits are zeroed on the host (a new vertex has no observed traffic),
    which makes their whole sub-step a traffic no-op — exactly the host
    oracle's behaviour.
    """

    def lex_argmin(hi, lo):
        m_hi = jnp.min(hi)
        cand = hi == m_hi
        m_lo = jnp.min(jnp.where(cand, lo, jnp.int32(_DIGIT)))
        return jnp.argmax(cand & (lo == m_lo)).astype(jnp.int32)

    n_pad = packed.shape[0] * _SCAN_UNROLL
    buf0 = jnp.zeros((max(n_pad, _SCAN_UNROLL),), jnp.int32)

    def step(carry, blk):
        hi, lo, buf, base = carry
        ts = []
        for j in range(_SCAN_UNROLL):
            src = _block_src(buf, blk, ts, j)
            t = lex_argmin(hi, lo)
            inc = blk[3, j]  # live mask as 0/1
            d_hi, d_lo = blk[5, j] * inc, blk[6, j] * inc
            lo = lo.at[src].add(-d_lo).at[t].add(d_lo)
            hi = hi.at[src].add(-d_hi).at[t].add(d_hi)
            carry_d = jnp.floor_divide(lo, _DIGIT)  # ∈ {-1, 0, 1} by construction
            lo = lo - carry_d * _DIGIT
            hi = hi + carry_d
            ts.append(t)
        buf = jax.lax.dynamic_update_slice(buf, jnp.stack(ts), (base,))
        return (hi, lo, buf, base + _SCAN_UNROLL), None

    (_, _, buf, _), _ = jax.lax.scan(
        step, (tr_hi0, tr_lo0, buf0, jnp.int32(0)), packed
    )
    return buf[:n_pad]


def scan_dynamism_targets(
    parts: np.ndarray,
    movers: np.ndarray,
    method: str,
    k: int,
    vertex_traffic: Optional[np.ndarray] = None,
    insert_mask: Optional[np.ndarray] = None,
    pad_units: int = 0,
) -> np.ndarray:
    """Device-scan targets for a mover sequence — bit-identical to the
    sequential host oracle in :func:`repro.core.dynamism.generate_dynamism`.

    ``insert_mask`` flags vertex-allocation units (the Insert workload):
    their slot in ``movers`` is the attachment anchor, the policy treats
    them as a pure addition to the chosen target (no source decrement, no
    traffic carried — a new vertex has none observed yet).

    ``pad_units`` fixes the padded scan length (see :func:`_unroll_blocks`):
    the generator passes the capacity-sized slice size for store-backed
    graphs so growth never changes the compiled scan shape.

    ``least_traffic`` requires integer-valued, non-negative
    ``vertex_traffic`` with per-partition totals below 2⁵¹ (always true
    for :attr:`TrafficResult.per_vertex` int64 counts); anything else
    raises rather than silently degrading exactness.
    """
    movers = np.asarray(movers)
    units = int(movers.shape[0])
    if insert_mask is not None:
        insert_mask = np.asarray(insert_mask, dtype=bool)
        if insert_mask.shape[0] != units:
            raise ValueError("insert_mask must be one flag per unit")
    if method == "fewest_vertices":
        counts0 = np.bincount(parts, minlength=k).astype(np.int32)
        targets = _fewest_vertices_scan(
            jnp.asarray(counts0),
            jnp.asarray(_unroll_blocks(movers, parts, insert=insert_mask,
                                       pad_units=pad_units)),
        )
        return np.asarray(targets, dtype=np.int32)[:units]
    if method != "least_traffic":
        raise ValueError(f"no device scan for insert method {method!r}")
    if vertex_traffic is None:
        raise ValueError("least_traffic requires vertex_traffic")
    vt = np.asarray(vertex_traffic)
    vt64 = np.asarray(np.rint(vt), dtype=np.int64)
    if not np.array_equal(vt64.astype(vt.dtype, copy=False), vt):
        raise ValueError(
            "device least_traffic needs integer-valued vertex_traffic "
            "(use engine='host' for fractional estimates)"
        )
    if vt64.min(initial=0) < 0 or float(vt64.sum(dtype=np.float64)) >= _VALUE_CEIL:
        raise ValueError(
            "vertex_traffic outside the exact int32-digit range [0, 2**51)"
        )
    tr0 = np.zeros(k, dtype=np.int64)
    np.add.at(tr0, np.asarray(parts, dtype=np.int64), vt64)
    tr_hi0, tr_lo0 = _split_digits(tr0)
    vt_unit = vt64[movers.astype(np.int64)]
    if insert_mask is not None:
        vt_unit = np.where(insert_mask, np.int64(0), vt_unit)
    vt_hi, vt_lo = _split_digits(vt_unit)
    targets = _least_traffic_scan(
        jnp.asarray(tr_hi0), jnp.asarray(tr_lo0),
        jnp.asarray(_unroll_blocks(movers, parts, extra=(vt_hi, vt_lo),
                                   insert=insert_mask, pad_units=pad_units)),
    )
    return np.asarray(targets, dtype=np.int32)[:units]


# ===========================================================================
# The experiment driver
# ===========================================================================
@dataclasses.dataclass
class SliceRecord:
    """Per-slice measurements of the dynamic experiment."""

    index: int
    units: int
    percent_global: float                      # after (any) maintenance
    maintained: bool
    migrated: int                              # vertices moved by migration
    damaged_percent_global: Optional[float] = None
    inserted: int = 0                          # new vertices allocated


@dataclasses.dataclass
class DynamicRunResult:
    baseline: TrafficResult     # traffic on the starting partitioning
    records: List[SliceRecord]
    final: TrafficResult        # traffic after the last slice
    parts: np.ndarray           # final partition map


class DynamicExperimentRuntime:
    """Drive the Dynamic/Stress experiment cycle on a graph service.

    The service decides the engine: constructed with a ``mesh``, every leg
    runs on it (sharded replay, device-scan dynamism, mesh DiDiC per the
    service's ``maintenance`` mode); without one, the host reference path
    runs. Either way the cycle, seeds, and migration policy are identical,
    which is what makes the host-vs-device parity test meaningful.
    """

    def __init__(
        self,
        service: PartitionedGraphService,
        insert_method: str = "random",
        seed: int = 0,
        scheduler: Optional[MigrationScheduler] = None,
    ):
        self.service = service
        self.insert = InsertPartitioner(
            insert_method, service.k, seed=seed, engine=service.engine
        )
        # The paper's Dynamic experiment migrates on a fixed interval, so
        # the default scheduler applies every planned move.
        self.scheduler = scheduler or MigrationScheduler(min_move_fraction=0.0)
        # Per-run loop state, exposed so the recovery driver
        # (repro.core.recovery) can snapshot mid-run and resume a fresh
        # runtime at an arbitrary slice boundary.
        self._baseline: Optional[TrafficResult] = None
        self._result: Optional[TrafficResult] = None
        self._records: List[SliceRecord] = []

    # -- incremental interface (one slice at a time) -------------------------
    @property
    def last_result(self) -> Optional[TrafficResult]:
        """The latest traffic measurement (feeds the next slice's
        ``least_traffic`` policy); set by :meth:`begin` / :meth:`run_slice`
        and restored from snapshot on recovery."""
        return self._result

    def begin(self, ops: OpLog) -> TrafficResult:
        """Measure the baseline and arm the per-slice loop."""
        svc = self.service
        if svc.fault_plan is not None:
            svc.fault_plan.begin_slice(svc.fault_plan.BASELINE)
        self._baseline = self._result = svc.run_ops(ops)
        self._records = []
        return self._baseline

    @tracing.span("slice")
    def run_slice(
        self,
        i: int,
        ops: OpLog,
        amount: float,
        maintain_every: int = 1,
        iterations: int = 1,
        measure_damaged: bool = False,
        insert_rate: float = 0.0,
        log=None,
    ) -> Tuple[SliceRecord, TrafficResult]:
        """Run one slice of the cycle: dynamism → maintenance → replay.

        ``log`` replaces the insert partitioner's draw for this slice (the
        recovery driver passes a journal-committed log here when resuming
        past a post-commit crash); the partitioner still advances one
        spawn so later slices draw the same streams as an uninterrupted
        run. A crash mid-slice leaves the loop state untouched up to the
        faulted call — re-running the same ``i`` after restore reproduces
        the slice exactly (the fault plan never re-fires a crash).
        """
        svc = self.service
        if svc.fault_plan is not None:
            svc.fault_plan.begin_slice(i)
        if insert_rate > 0.0 and svc.graph.store is None:
            # First growth slice on a storeless graph: attach the
            # capacity store and prewarm the overlay closures now, so the
            # one-time traces land in this (warmup) slice rather than
            # leaking into the steady state the sentinel audits.
            svc.prepare_growth()
        if log is None:
            log = self.insert.allocate(
                svc.parts, amount, vertex_traffic=self._result.per_vertex,
                insert_rate=insert_rate, graph=svc.graph,
            )
        else:
            self.insert.advance(1)
        svc.apply_dynamism(log)
        damaged_pg = (
            svc.run_ops(ops).percent_global if measure_damaged else None
        )
        maintained = (i + 1) % maintain_every == 0
        migrated = 0
        if maintained:
            migrated = svc.maintain_migrate(
                self.scheduler, step=i, iterations=iterations
            )
        result = svc.run_ops(ops)
        if maintained:
            # The degradation check must be judged against what the
            # current graph can achieve, not the first-ever quality
            # (which a long run can never get back to).
            self.scheduler.record_maintenance(result.percent_global)
        self._result = result
        record = SliceRecord(
            index=i,
            units=log.units,
            percent_global=result.percent_global,
            maintained=maintained,
            migrated=migrated,
            damaged_percent_global=damaged_pg,
            inserted=log.n_new_vertices,
        )
        self._records.append(record)
        return record, result

    def result(self) -> DynamicRunResult:
        """Package the loop state accumulated so far."""
        return DynamicRunResult(
            baseline=self._baseline,
            records=list(self._records),
            final=self._result,
            parts=self.service.parts.copy(),
        )

    def run(
        self,
        ops: OpLog,
        n_slices: int,
        amount: float,
        maintain_every: int = 1,
        iterations: int = 1,
        measure_damaged: bool = False,
        insert_rate: float = 0.0,
        on_slice: Optional[Callable[[int, TrafficResult], None]] = None,
    ) -> DynamicRunResult:
        """Run ``n_slices`` slices of ``amount`` dynamism each.

        Per slice: generate+apply a dynamism log (seeded from the insert
        partitioner's spawned stream, fed by the latest per-vertex
        traffic), maintain every ``maintain_every``-th slice (DiDiC
        ``iterations`` + migration via the scheduler), then replay ``ops``
        for the slice's traffic measurement. ``measure_damaged`` adds a
        pre-maintenance measurement (the Stress experiment's
        ``damaged_pg``). ``insert_rate`` makes that fraction of each
        slice's units *allocate new vertices* (with incident edges) on the
        service's current graph — the paper's Insert workload — so the
        graph, the partition map, and the per-vertex traffic feed all grow
        across slices. ``on_slice`` sees every post-maintenance
        :class:`TrafficResult` — the parity test uses it to compare all
        four counters per slice without bloating the records.

        This is :meth:`begin` + ``n_slices`` × :meth:`run_slice` — the
        incremental interface the recovery driver uses; the composition is
        bit-identical to the former monolithic loop.
        """
        self.begin(ops)
        for i in range(n_slices):
            _, result = self.run_slice(
                i, ops, amount,
                maintain_every=maintain_every, iterations=iterations,
                measure_damaged=measure_damaged, insert_rate=insert_rate,
            )
            if on_slice is not None:
                on_slice(i, result)
        return self.result()
