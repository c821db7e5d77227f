"""The Romania road network and its A* routes (arXiv:1301.5121 §6.2.2).

* ``build``: the road-network copy at the configuration's ``n_nodes`` and
  ``n_edges``, from ``graph_seed``.
* ``logs``: fresh route logs of ``log_ops`` ops (``gis_short`` or
  ``gis_long`` by the configuration's ``pattern``), starts near cities,
  drawn from the run's traffic stream, so no route log repeats in a run.
  The walk lengths of a run's ``j``-th log are the same for every seed
  (drawn from the mix's ``[sizes_seed, j]``): the seed picks the routes,
  not how far they walk.
* ``Reference``: the four counters of the routes' A* expansion sets, from
  float32 Dijkstra searches on all cores but one; its control runs the
  same searches in bfloat16.
* ``TINY``: the CPU rehearsal size.
"""

from __future__ import annotations

import itertools
import os

import numpy as np

from bench.reference import graphs, oplogs, oracle

WORKERS = max(1, (os.cpu_count() or 2) - 1)
TINY = {"n_nodes": 3000, "n_edges": None}


def build(config: dict) -> graphs.EdgeList:
    e = graphs.gis(config["n_nodes"], config["graph_seed"], n_edges=config.get("n_edges"))
    return graphs.check_size(e, config)


def _undirected_csr(edges: graphs.EdgeList):
    s, r, w = graphs.symmetrize(edges)
    return graphs.csr(s, r, w, edges.n_nodes)


def logs(config: dict, edges: graphs.EdgeList, mix: dict, rng):
    indptr, indices, _ = _undirected_csr(edges)
    lon, lat = edges.attrs["lon"], edges.attrs["lat"]
    p = np.exp(-oplogs.city_distance(lon, lat) / 0.15)
    p /= p.sum()
    variant = config["pattern"].split("_")[1]
    n_ops = config["log_ops"]
    for j in itertools.count():
        lengths = oplogs.walk_lengths(n_ops, np.random.default_rng([mix["sizes_seed"], j]))
        yield oplogs.gis_routes(lon, lat, indptr, indices, n_ops, rng, variant, start_p=p,
                                lengths=lengths)


class Reference:
    def __init__(self, config: dict, edges: graphs.EdgeList, control: bool = False):
        self.config = config
        indptr, indices, wts = _undirected_csr(edges)
        self.routes = oracle.GisRoutes(indptr, indices, wts, edges.attrs["lon"],
                                       edges.attrs["lat"],
                                       precision="bfloat16" if control else "float32")

    def counters(self, parts, starts, ends):
        c = self.config
        return self.routes.counters(parts, c["k"], starts, ends, c["t_l"], c["t_pg"],
                                    c["max_expansions"], workers=WORKERS)
