"""The paper's Twitter and Romania GIS graphs (arXiv:1301.5121 §6.2).

Copies of ``twitter_social`` and ``gis_romania`` from
``repro.graphs.generators``: the same draws in the same order, so a seed
gives the same graph as the program's generator. They return plain arrays
(:class:`EdgeList`); the harness builds the program's graph object from them.
``symmetrize`` and ``csr`` are the plain views the reference traverses.

Given ``n_edges``, a generator keeps exactly that many edges: the source's
edge count, which the program's generators reach only on average
(Twitter) or overshoot (GIS). The trim draws from the same stream after
every other draw, so without it the graph is the program's.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import numpy as np

# (name, longitude, latitude, relative size) of the five cities of §6.2.2.
CITIES = (
    ("bucharest", 26.10, 44.43, 0.40),
    ("iasi", 27.60, 47.16, 0.15),
    ("galati", 28.05, 45.43, 0.12),
    ("timisoara", 21.23, 45.76, 0.18),
    ("constanta", 28.63, 44.18, 0.15),
)


@dataclasses.dataclass
class EdgeList:
    n_nodes: int
    senders: np.ndarray   # [E] int32
    receivers: np.ndarray  # [E] int32
    weights: np.ndarray   # [E] float32
    attrs: Dict[str, np.ndarray]


def check_size(e: EdgeList, config: dict) -> EdgeList:
    """``e`` as built, once its counts are the configuration's."""
    want = (config["n_nodes"], config.get("n_edges"))
    have = (e.n_nodes, e.senders.shape[0] if want[1] is not None else None)
    if have != want:
        raise ValueError(f"graph has (vertices, edges) {have}, the configuration states {want}")
    return e


def _too_few(have: int, n_edges: int) -> ValueError:
    return ValueError(f"the generator made {have} edges, fewer than the {n_edges} asked for")


def twitter(n_nodes: int, seed: int, n_edges: int = None) -> EdgeList:
    """Preferential-attachment "follows" graph at the paper's |E|/|V|.

    The crawl has 611,643 vertices and 851,799 edges; the mean out-degree
    is their ratio at every ``n_nodes``, and out-degrees are capped at 64.
    ``n_edges`` keeps a uniform random subset of that many edges.
    """
    rng = np.random.default_rng(seed)
    n = int(n_nodes)
    avg_out = 851_799 / 611_643
    n_seed = 8
    p = 1.0 / (1.0 + avg_out)
    outs = np.minimum(rng.geometric(p, size=n) - 1, 64)
    outs[:n_seed] = 0
    total_e = int(outs.sum())

    senders = np.repeat(np.arange(n, dtype=np.int64), outs)
    receivers = np.empty(total_e, dtype=np.int64)
    pool_arr = np.array(list(rng.integers(0, n_seed, size=16)), dtype=np.int64)
    pool_len = pool_arr.shape[0]
    pos = 0
    chunk = max(1024, n // 256)
    buf = np.empty(max(total_e * 2 + 32, 1024), dtype=np.int64)
    buf[:pool_len] = pool_arr
    for start in range(n_seed, n, chunk):
        stop = min(start + chunk, n)
        m = int(outs[start:stop].sum())
        if m == 0:
            continue
        # 3 of 4 targets by in-degree (endpoint pool), 1 of 4 uniform.
        pref = rng.random(m) < 0.75
        tgt = np.where(
            pref,
            buf[rng.integers(0, max(pool_len, 1), size=m)],
            rng.integers(0, stop, size=m),
        )
        receivers[pos:pos + m] = tgt
        buf[pool_len:pool_len + m] = tgt
        pool_len += m
        pos += m
    receivers = receivers[:pos]
    senders = senders[:pos]
    keep = senders != receivers
    senders, receivers = senders[keep], receivers[keep]
    if n_edges is not None:
        if senders.shape[0] < n_edges:
            raise _too_few(senders.shape[0], n_edges)
        kept = np.sort(rng.choice(senders.shape[0], size=n_edges, replace=False))
        senders, receivers = senders[kept], receivers[kept]
    return EdgeList(
        n_nodes=n,
        senders=senders.astype(np.int32),
        receivers=receivers.astype(np.int32),
        weights=np.ones(senders.shape[0], dtype=np.float32),
        attrs={},
    )


def gis(n_nodes: int, seed: int, city_fraction: float = 0.62, n_edges: int = None) -> EdgeList:
    """Road network: city blobs, highway corridors, rural background, and
    grid-bucket nearest-neighbour roads weighted by Euclidean length.

    Each city vertex links to up to 3 of its shortest candidate roads and
    each rural vertex to up to 2, and a chain joins the highway vertices.
    ``n_edges`` keeps the chain and, of the roads, every vertex's shortest
    first, then its second shortest, and so on, a random subset of the
    last rank that fits, so that the graph has exactly ``n_edges`` edges.
    """
    rng = np.random.default_rng(seed)
    n = int(n_nodes)
    sizes = np.array([c[3] for c in CITIES])
    cxy = np.array([[c[1], c[2]] for c in CITIES])
    n_city = int(n * city_fraction)
    n_rural = n - n_city
    city_of = rng.choice(len(CITIES), size=n_city, p=sizes / sizes.sum())
    city_pts = cxy[city_of] + rng.normal(0.0, 0.08, size=(n_city, 2))

    n_hw = n_rural // 2
    a = rng.integers(0, len(CITIES), size=n_hw)
    b = (a + 1 + rng.integers(0, len(CITIES) - 1, size=n_hw)) % len(CITIES)
    t = rng.random(n_hw)[:, None]
    hw_pts = cxy[a] * (1 - t) + cxy[b] * t + rng.normal(0, 0.05, size=(n_hw, 2))
    bg_pts = np.stack(
        [rng.uniform(20.0, 30.0, n_rural - n_hw), rng.uniform(43.5, 48.2, n_rural - n_hw)],
        axis=1,
    )
    xy = np.concatenate([city_pts, hw_pts, bg_pts], axis=0)
    is_city = np.zeros(n, dtype=bool)
    is_city[:n_city] = True

    cell = 0.05
    gx = np.floor((xy[:, 0] - 19.5) / cell).astype(np.int64)
    gy = np.floor((xy[:, 1] - 43.0) / cell).astype(np.int64)
    ncols = int(gx.max()) + 2
    cell_id = gy * ncols + gx
    order = np.argsort(cell_id, kind="stable")
    sorted_cells = cell_id[order]

    ks = np.where(is_city, 3, 2)
    senders, receivers, weights = [], [], []
    for dx in (-1, 0, 1):
        for dy in (-1, 0, 1):
            tgt_cell = (gy + dy) * ncols + (gx + dx)
            lo = np.searchsorted(sorted_cells, tgt_cell, side="left")
            hi = np.searchsorted(sorted_cells, tgt_cell, side="right")
            width = hi - lo
            has = width > 0
            if not has.any():
                continue
            pick = lo + (rng.integers(0, 1 << 30, size=n) % np.maximum(width, 1))
            cand = order[np.minimum(pick, order.shape[0] - 1)]
            ok = has & (cand != np.arange(n))
            src = np.nonzero(ok)[0]
            dst = cand[ok]
            d = np.linalg.norm(xy[src] - xy[dst], axis=1).astype(np.float32)
            keep = d < 0.15
            senders.append(src[keep])
            receivers.append(dst[keep])
            weights.append(d[keep])

    s = np.concatenate(senders)
    r = np.concatenate(receivers)
    w = np.concatenate(weights)
    order2 = np.lexsort((w, s))
    s, r, w = s[order2], r[order2], w[order2]
    rank = np.zeros(s.shape[0], dtype=np.int64)
    if s.shape[0]:
        newrow = np.concatenate([[True], s[1:] != s[:-1]])
        idx = np.arange(s.shape[0])
        rank = idx - np.maximum.accumulate(np.where(newrow, idx, 0))
    keep = rank < ks[s]
    s, r, w, rank = s[keep], r[keep], w[keep], rank[keep]

    hw_idx = np.arange(n_city, n_city + n_hw)
    cs = cr = np.zeros(0, dtype=np.int64)
    cd = np.zeros(0, dtype=np.float32)
    if n_hw > 1:
        hw_order = hw_idx[np.argsort(a * 10 + t[:, 0])]
        cs, cr = hw_order[:-1], hw_order[1:]
        cd = np.linalg.norm(xy[cs] - xy[cr], axis=1).astype(np.float32)
        ok = cd < 1.0
        cs, cr, cd = cs[ok], cr[ok], cd[ok]
    if n_edges is not None:
        roads = n_edges - cs.shape[0]
        if roads > s.shape[0] or roads < 0:
            raise _too_few(s.shape[0] + cs.shape[0], n_edges)
        kept = np.sort(np.argsort(rank + rng.random(s.shape[0]), kind="stable")[:roads])
        s, r, w = s[kept], r[kept], w[kept]
    s = np.concatenate([s, cs])
    r = np.concatenate([r, cr])
    w = np.concatenate([w, cd])

    w = np.maximum(w, 1e-4).astype(np.float32)
    return EdgeList(
        n_nodes=n,
        senders=s.astype(np.int32),
        receivers=r.astype(np.int32),
        weights=w,
        attrs={
            "lon": xy[:, 0].astype(np.float32),
            "lat": xy[:, 1].astype(np.float32),
            "is_city": is_city,
            "city_id": np.concatenate(
                [city_of, np.full(n_rural, -1, dtype=np.int64)]
            ).astype(np.int16),
        },
    )


def _coalesce(s, r, w, n) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Sort by (sender, receiver) and merge duplicate edges, summing weights."""
    key = s.astype(np.int64) * n + r
    order = np.argsort(key, kind="stable")
    key, s, r, w = key[order], s[order], r[order], w[order]
    uniq, inv = np.unique(key, return_inverse=True)
    merged = np.zeros(uniq.shape[0], dtype=np.float32)
    np.add.at(merged, inv, w)
    first = np.searchsorted(key, uniq)
    return s[first].astype(np.int64), r[first].astype(np.int64), merged


def symmetrize(g: EdgeList) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Undirected, loop-free, coalesced edge set with both directions.

    A vertex pair's weight is summed over every edge between the two, in
    either direction, and then mirrored.
    """
    s = g.senders.astype(np.int64)
    r = g.receivers.astype(np.int64)
    keep = s != r
    lo, hi, w = _coalesce(np.minimum(s, r)[keep], np.maximum(s, r)[keep],
                          g.weights[keep].astype(np.float32), g.n_nodes)
    return _coalesce(np.concatenate([lo, hi]), np.concatenate([hi, lo]),
                     np.concatenate([w, w]), g.n_nodes)


def csr(senders, receivers, weights, n_nodes: int):
    """(indptr, indices, weights) grouped by sender, edge order kept."""
    order = np.argsort(senders, kind="stable")
    counts = np.bincount(senders, minlength=n_nodes)
    indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    return indptr, np.asarray(receivers)[order].astype(np.int64), np.asarray(weights)[order]
