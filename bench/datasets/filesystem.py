"""The file-system tree and its target searches (arXiv:1301.5121 §6.2.1).

* ``build``: the tree copy (``bench/reference/filesystem.py``) at the
  configuration's ``n_nodes`` and ``n_edges``, from ``graph_seed``; every
  edge joins a vertex pair no other edge joins.
* ``logs``: fresh logs of ``log_ops`` target searches, ends in proportion
  to degree among files and folders, starts a random ancestor folder,
  drawn from the run's traffic stream.
* ``Reference``: the four counters of target searches, each stopping after
  the level at which it finds its end. The counters are integers, so the
  control breaks that stated guarantee instead of dropping a precision:
  it searches every subtree to the bottom, whatever the end.
* ``TINY``: the CPU rehearsal size, at the paper's edges per vertex.
"""

from __future__ import annotations

from bench.reference import filesystem as fs
from bench.reference import graphs

TINY = {"n_nodes": 8003, "n_edges": 14361, "log_ops": 500}


def build(config: dict) -> graphs.EdgeList:
    e = fs.tree(config["n_nodes"], config["graph_seed"], config.get("n_edges"))
    if fs.distinct_pairs(e) != e.senders.shape[0]:
        raise ValueError(f"{e.senders.shape[0]} edges join only {fs.distinct_pairs(e)} "
                         "distinct vertex pairs")
    return graphs.check_size(e, config)


def logs(config: dict, edges: graphs.EdgeList, mix: dict, rng):
    a = edges.attrs
    deg = fs.degree(edges)
    while True:
        yield fs.target_searches(a["node_type"], a["parent"], a["depth"], deg,
                                 config["log_ops"], rng)


class Reference:
    def __init__(self, config: dict, edges: graphs.EdgeList, control: bool = False):
        self.config = config
        self.indptr, self.indices = fs.search_csr(edges)
        self.max_levels = int(edges.attrs["depth"].max()) + 2
        self.control = control

    def counters(self, parts, starts, ends):
        c = self.config
        if self.control:
            ends = [-1] * len(starts)
        return fs.target_search_counters(self.indptr, self.indices, parts, c["k"], starts, ends,
                                         self.max_levels, c["t_l"], c["t_pg"])
