"""The paper's access patterns and dynamism (arXiv:1301.5121 §6.2, §6.4).

Copies of ``_gen_twitter`` / ``_gen_gis`` (``repro.core.traffic``) and of
the ``random`` insert method of ``generate_dynamism``
(``repro.core.dynamism``). Each takes a ``numpy.random.Generator`` rather
than a seed, so that consecutive logs of one run come from one stream; a
fresh ``default_rng(seed)`` gives what the program's function gives for
``seed``.
"""

from __future__ import annotations

import numpy as np

from bench.reference.graphs import CITIES


def twitter_cdf(out_degree: np.ndarray) -> np.ndarray:
    """Cumulative start distribution: in proportion to out-degree (§6.2.3)."""
    p = (out_degree + 1e-9).astype(np.float64)
    p /= p.sum()
    cdf = p.cumsum()
    cdf /= cdf[-1]
    return cdf


def twitter_starts(cdf: np.ndarray, n_ops: int, rng) -> np.ndarray:
    """Friend-of-a-friend starts; the draws of ``rng.choice(n, n_ops, p=p)``."""
    return cdf.searchsorted(rng.random(n_ops), side="right").astype(np.int64)


def city_distance(lon: np.ndarray, lat: np.ndarray) -> np.ndarray:
    lon = lon.astype(np.float64)
    lat = lat.astype(np.float64)
    cxy = np.array([[c[1], c[2]] for c in CITIES])
    return np.min(
        np.sqrt((lon[:, None] - cxy[None, :, 0]) ** 2 + (lat[:, None] - cxy[None, :, 1]) ** 2),
        axis=1,
    )


def walk_lengths(n_ops: int, rng) -> np.ndarray:
    """Lengths of ``short`` routes' random walks: exponential, mean 11."""
    return np.maximum(rng.exponential(11.0, size=n_ops).astype(np.int64), 1)


def gis_routes(lon, lat, und_indptr, und_indices, n_ops: int, rng,
               variant: str = "short", start_p=None, lengths=None):
    """Route (start, end) pairs (§6.2.2): starts near cities; ``short`` ends
    by a random walk of exponential length (mean 11), ``long`` ends near a
    city. ``start_p`` is the start distribution, when already computed;
    ``lengths`` are the walks' lengths, when given (else drawn from
    ``rng`` after the starts)."""
    if start_p is None:
        start_p = np.exp(-city_distance(lon, lat) / 0.15)
        start_p /= start_p.sum()
    n = lon.shape[0]
    starts = rng.choice(n, size=n_ops, p=start_p)
    if variant == "long":
        ends = rng.choice(n, size=n_ops, p=start_p)
        return starts.astype(np.int64), ends.astype(np.int64)
    if lengths is None:
        lengths = walk_lengths(n_ops, rng)
    ends = starts.copy()
    for step in range(int(lengths.max())):
        act = lengths > step
        deg = und_indptr[ends + 1] - und_indptr[ends]
        ok = act & (deg > 0)
        pick = und_indptr[ends[ok]] + (rng.integers(0, 1 << 30, size=int(ok.sum())) % deg[ok])
        ends[ok] = und_indices[pick]
    return starts.astype(np.int64), ends.astype(np.int64)


def random_moves(n_nodes: int, amount: float, k: int, rng):
    """One dynamism slice of ``round(amount·n)`` partition moves to uniform
    targets (the ``random`` insert method). Returns (vertices, targets)."""
    units = int(round(amount * n_nodes))
    movers = rng.integers(0, n_nodes, size=units)
    targets = rng.integers(0, k, size=units).astype(np.int32)
    return movers.astype(np.int64), targets


def apply_moves(parts: np.ndarray, vertices: np.ndarray, targets: np.ndarray) -> np.ndarray:
    """Partition map after a slice: the last move of a vertex wins."""
    out = np.array(parts, copy=True)
    out[vertices] = targets
    return out
