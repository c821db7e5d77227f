"""The Twitter crawl and its friend-of-a-friend pattern (arXiv:1301.5121 §6.2.3).

* ``build``: the preferential-attachment copy of the crawl at the
  configuration's ``n_nodes`` and ``n_edges``, from ``graph_seed``.
* ``logs``: fresh 2-hop logs of ``log_ops`` ops, starts in proportion to
  out-degree, drawn from the run's traffic stream.
* ``Reference``: the four counters of 2-hop out-expansions. The counters
  are integers, so the control breaks a stated guarantee instead of
  dropping a precision: each step's potentially-global action is booked at
  its sender, not its receiver.
* ``TINY``: the CPU rehearsal size.
"""

from __future__ import annotations

import numpy as np

from bench.reference import graphs, oplogs, oracle

TINY = {"n_nodes": 5000, "n_edges": None, "log_ops": 1000}


def build(config: dict) -> graphs.EdgeList:
    e = graphs.twitter(config["n_nodes"], config["graph_seed"], config.get("n_edges"))
    return graphs.check_size(e, config)


def logs(config: dict, edges: graphs.EdgeList, mix: dict, rng):
    n_ops = config["log_ops"]
    cdf = oplogs.twitter_cdf(np.bincount(edges.senders, minlength=edges.n_nodes))
    while True:
        yield oplogs.twitter_starts(cdf, n_ops, rng), np.full(n_ops, -1, np.int64)


class Reference:
    def __init__(self, config: dict, edges: graphs.EdgeList, control: bool = False):
        self.config = config
        self.indptr, self.indices, _ = graphs.csr(edges.senders, edges.receivers,
                                                  edges.weights, edges.n_nodes)
        self.pg_at = "sender" if control else "receiver"

    def counters(self, parts, starts, ends):
        c = self.config
        return oracle.twitter_counters(self.indptr, self.indices, parts, c["k"], starts,
                                       c["t_l"], c["t_pg"], pg_at=self.pg_at)
