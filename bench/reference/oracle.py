"""Plain reference for the traffic counters (arXiv:1301.5121 §6.2).

Every traversal step ``u → v`` of an op costs ``t_l`` local actions at
``u`` and ``t_pg`` potentially-global actions at ``v``; it is global when
``parts[u] != parts[v]``. The four counters are per op total and global
traffic, traffic served per partition, and traffic per vertex.

* Twitter friend-of-a-friend: two hops of out-edges from the start, with
  path multiplicity (a vertex reached twice expands twice).
* GIS route: the A* expansion set under the Euclidean heuristic, defined
  from final float32 distances ``g`` as
  ``{u : g(u) <= g(dst), (g(u) + h(u, dst), u) < (g(dst), dst)}``, cut to
  its ``max_expansions`` smallest entries; each member expands all of its
  undirected edges.

:func:`twitter_counters_scalar` and :func:`gis_counters` follow the
program's scalar oracle op by op; :func:`twitter_counters` is the same
count, vectorized. The controls: ``precision="bfloat16"`` for routes, and
``pg_at="sender"`` (the receiver's share booked at the sender) for 2-hops.
"""

from __future__ import annotations

import heapq
import multiprocessing
import struct
from array import array
from concurrent.futures import ProcessPoolExecutor
from typing import Dict, Tuple

import numpy as np

COUNTERS = ("per_op_total", "per_op_global", "per_partition", "per_vertex")


def _fold(op, u, v, parts, k, n_ops, n_nodes, t_l, t_pg,
          pg_at: str = "receiver") -> Dict[str, np.ndarray]:
    """The four counters of the traversal steps ``(op, u → v)``.

    ``pg_at="sender"`` is the control: the potentially-global action booked
    where the step starts instead of at its receiver."""
    pu, pv = parts[u], parts[v]
    w = v if pg_at == "receiver" else u
    return {
        "per_op_total": np.bincount(op, minlength=n_ops).astype(np.int64) * (t_l + t_pg),
        "per_op_global": np.bincount(op, weights=(pu != pv), minlength=n_ops).astype(np.int64),
        "per_partition": (t_l * np.bincount(pu, minlength=k)
                          + t_pg * np.bincount(parts[w], minlength=k)).astype(np.int64),
        "per_vertex": (t_l * np.bincount(u, minlength=n_nodes)
                       + t_pg * np.bincount(w, minlength=n_nodes)).astype(np.int64),
    }


def _expand(indptr, indices, nodes, ops):
    """Every out-edge of ``nodes[i]``, tagged with ``ops[i]``."""
    counts = indptr[nodes + 1] - indptr[nodes]
    total = int(counts.sum())
    first = np.repeat(indptr[nodes] - (np.cumsum(counts) - counts), counts)
    eidx = first + np.arange(total)
    return np.repeat(ops, counts), np.repeat(nodes, counts), indices[eidx]


def twitter_counters(indptr, indices, parts, k: int, starts, t_l: int = 2,
                     t_pg: int = 1, pg_at: str = "receiver") -> Dict[str, np.ndarray]:
    """Two-hop out-expansion of every op at once."""
    parts = np.asarray(parts, dtype=np.int64)
    starts = np.asarray(starts, dtype=np.int64)
    n_ops, n_nodes = starts.shape[0], parts.shape[0]
    op1, u1, v1 = _expand(indptr, indices, starts, np.arange(n_ops))
    op2, u2, v2 = _expand(indptr, indices, v1, op1)
    return _fold(np.concatenate([op1, op2]), np.concatenate([u1, u2]),
                 np.concatenate([v1, v2]), parts, k, n_ops, n_nodes, t_l, t_pg, pg_at)


def twitter_counters_scalar(indptr, indices, parts, k: int, starts, t_l: int = 2,
                            t_pg: int = 1) -> Dict[str, np.ndarray]:
    """One op at a time, one step at a time."""
    parts = np.asarray(parts, dtype=np.int64)
    n_nodes = parts.shape[0]
    out = {name: np.zeros(n, dtype=np.int64) for name, n in
           zip(COUNTERS, (len(starts), len(starts), k, n_nodes))}
    for i, s in enumerate(starts):
        frontier = [int(s)]
        for _hop in range(2):
            children = []
            for u in frontier:
                for e in range(indptr[u], indptr[u + 1]):
                    v = int(indices[e])
                    _step(out, i, u, v, parts, t_l, t_pg)
                    children.append(v)
            frontier = children
    return out


def _step(out, i, u, v, parts, t_l, t_pg) -> None:
    out["per_op_total"][i] += t_l + t_pg
    if parts[u] != parts[v]:
        out["per_op_global"][i] += 1
    out["per_partition"][parts[u]] += t_l
    out["per_partition"][parts[v]] += t_pg
    out["per_vertex"][u] += t_l
    out["per_vertex"][v] += t_pg


def _bf16(x: float) -> float:
    """Round a float to bfloat16 (nearest, ties to even)."""
    (b,) = struct.unpack("<I", struct.pack("<f", x))
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return struct.unpack("<f", struct.pack("<I", b))[0]


class GisRoutes:
    """Route counters over one undirected road graph.

    Distances follow the program's scalar oracle: Dijkstra settles every
    vertex with ``g <= g(dst)``, each candidate ``g(u) + w`` rounded to
    float32 (``precision="bfloat16"``: to bfloat16, weights and heuristic
    too). Python floats hold float32 values exactly, and rounding a double
    sum of two float32 values gives the float32 sum.
    """

    def __init__(self, und_indptr, und_indices, und_w, lon, lat, precision: str = "float32"):
        if precision not in ("float32", "bfloat16"):
            raise ValueError(f"unknown precision {precision!r}")
        self.precision = precision
        self.indptr_np = np.asarray(und_indptr, dtype=np.int64)
        self.indices_np = np.asarray(und_indices, dtype=np.int64)
        self.indptr = self.indptr_np.tolist()
        self.indices = self.indices_np.tolist()
        w = np.asarray(und_w, dtype=np.float32)
        self.w = [_bf16(x) for x in w.tolist()] if precision == "bfloat16" else w.tolist()
        self.lon = np.asarray(lon, dtype=np.float32)
        self.lat = np.asarray(lat, dtype=np.float32)

    def _settle(self, src: int, dst: int):
        indptr, indices, w = self.indptr, self.indices, self.w
        bf16 = self.precision == "bfloat16"
        buf = array("f", [0.0])
        dist: Dict[int, float] = {}
        tentative = {src: 0.0}
        heap = [(0.0, src)]
        g_dst = None
        pop, push = heapq.heappop, heapq.heappush
        while heap:
            gu, u = pop(heap)
            if u in dist:
                continue
            if g_dst is not None and gu > g_dst:
                break
            dist[u] = gu
            if u == dst:
                g_dst = gu
            for e in range(indptr[u], indptr[u + 1]):
                v = indices[e]
                if v in dist:
                    continue
                if bf16:
                    cand = _bf16(gu + w[e])
                else:
                    buf[0] = gu + w[e]
                    cand = buf[0]
                known = tentative.get(v)
                if known is None or cand < known:
                    tentative[v] = cand
                    push(heap, (cand, v))
        return dist, g_dst

    def expansion(self, src: int, dst: int, max_expansions: int = 50_000) -> np.ndarray:
        """The op's A* expansion set, in ascending (f, id) order when cut."""
        dist, g_dst = self._settle(int(src), int(dst))
        us = np.fromiter(dist.keys(), dtype=np.int64, count=len(dist))
        gs = np.fromiter(dist.values(), dtype=np.float32, count=len(dist))
        dx = self.lon[us] - self.lon[dst]
        dy = self.lat[us] - self.lat[dst]
        fs = gs + np.sqrt(dx * dx + dy * dy)
        if self.precision == "bfloat16":
            fs = np.asarray([_bf16(x) for x in fs.tolist()], dtype=np.float32)
        f_dst = np.float32(np.inf) if g_dst is None else np.float32(g_dst)
        member = (fs < f_dst) | ((fs == f_dst) & (us < dst))
        us, fs = us[member], fs[member]
        if us.shape[0] > max_expansions:
            order = np.lexsort((us, fs))[:max_expansions]
            us = us[order]
        return us

    def counters(self, parts, k: int, starts, ends, t_l: int = 8, t_pg: int = 1,
                 max_expansions: int = 50_000, workers: int = 1) -> Dict[str, np.ndarray]:
        """The four counters of the routes ``starts[i] → ends[i]``; with
        ``workers`` > 1 the routes are searched in that many processes."""
        parts = np.asarray(parts, dtype=np.int64)
        jobs = [(int(s), int(e), max_expansions) for s, e in zip(starts, ends)]
        if workers > 1 and len(jobs) > 1:
            ctx = multiprocessing.get_context("spawn")
            init = (self.indptr_np, self.indices_np, np.asarray(self.w, np.float32),
                    self.lon, self.lat, self.precision)
            with ProcessPoolExecutor(min(workers, len(jobs)), mp_context=ctx,
                                     initializer=_init_worker, initargs=init) as pool:
                sets = list(pool.map(_worker_expansion, jobs))
        else:
            sets = [self.expansion(*job) for job in jobs]
        ops = np.repeat(np.arange(len(sets)), [x.shape[0] for x in sets])
        nodes = np.concatenate(sets) if sets else np.zeros(0, np.int64)
        op, u, v = _expand(self.indptr_np, self.indices_np, nodes, ops)
        return _fold(op, u, v, parts, k, len(sets), parts.shape[0], t_l, t_pg)


_WORKER_ROUTES = None


def _init_worker(indptr, indices, w, lon, lat, precision):
    """A worker process's routes (bfloat16 weights round to themselves)."""
    global _WORKER_ROUTES
    _WORKER_ROUTES = GisRoutes(indptr, indices, w, lon, lat, precision)


def _worker_expansion(job):
    return _WORKER_ROUTES.expansion(*job)


def mismatches(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]) -> Tuple[int, int]:
    """(entries that differ, entries compared) over the four counters."""
    bad = total = 0
    for name in COUNTERS:
        a = np.asarray(got[name], dtype=np.int64)
        b = np.asarray(want[name], dtype=np.int64)
        total += b.shape[0]
        if a.shape != b.shape:
            bad += b.shape[0]
        else:
            bad += int((a != b).sum())
    return bad, total
