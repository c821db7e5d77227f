"""Set-up shared by every driver: data, service, partition, host spans."""

from __future__ import annotations

from typing import Dict

import numpy as np

from bench.drivers import plugin
from bench.reference import oracle

_STREAMS = {"traffic": 2, "partition": 3, "dynamism": 4, "check": 5}


def rng_for(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed) % 2**64, _STREAMS[stream]]))


def int_seed(seed: int, stream: str, bound: int = 2**31) -> int:
    return int(rng_for(seed, stream).integers(bound))


def counters(result) -> Dict[str, np.ndarray]:
    return {name: np.asarray(getattr(result, name), dtype=np.int64) for name in oracle.COUNTERS}


def check_entry(value, limit, ok: bool) -> dict:
    return {"value": value, "limit": limit, "ok": bool(ok)}


class Driver:
    def __init__(self, config: dict, mix: dict, seed: int, spans, devices):
        self.config = {**config, **{k: mix[k] for k in ("pattern", "log_ops") if k in mix}}
        self.mix, self.seed, self.spans, self.devices = mix, int(seed), spans, devices
        self.dataset = plugin("datasets", self.config["dataset"])
        self.samples: Dict[str, object] = {}
        self.attempted = 0
        self.failed = 0
        self._refs = {}

    def build_service(self):
        from repro.core.didic import DidicConfig
        from repro.core.framework import PartitionedGraphService
        from repro.graphs.structure import Graph
        from repro.launch.mesh import make_replay_mesh

        c, d = self.config, self.config["didic"]
        didic = DidicConfig(
            k=c["k"], iterations=c["didic_iterations"], primary_steps=d["primary_steps"],
            secondary_steps=d["secondary_steps"], smooth_cap=d["smooth_cap"],
            smooth_double_every=d["smooth_double_every"], commit_prob=d["commit_prob"],
            balance_iters=d["balance_iters"], balance_exp=d["balance_exp"],
        )
        e = self.edges = self.dataset.build(c)
        graph = Graph(n_nodes=e.n_nodes, senders=e.senders, receivers=e.receivers,
                      edge_weight=e.weights, node_attrs=dict(e.attrs), name=c["dataset"])
        svc = PartitionedGraphService(graph, c["k"], didic=didic,
                                      mesh=make_replay_mesh(len(self.devices)))
        with self.spans("partition"):
            plugin("partitioners", c["partitioner"]).partition(
                svc, graph, c, int_seed(self.seed, "partition"))
        self.svc = svc
        return svc

    def op_source(self):
        """Fresh (starts, ends) logs of the configuration's pattern, drawn
        from the traffic stream."""
        return self.dataset.logs(self.config, self.edges, self.mix, rng_for(self.seed, "traffic"))

    def oplog(self, starts, ends):
        from repro.core.traffic import OpLog

        c = self.config
        return OpLog(c["pattern"], np.asarray(starts, np.int64), np.asarray(ends, np.int64),
                     t_l=c["t_l"], t_pg=c["t_pg"])

    def span_engine(self) -> None:
        """Host spans on the replay engine's host-side layers."""
        from repro.core.traffic_sharded import get_replayer

        eng = get_replayer(self.svc.graph, self.config["pattern"], self.svc.mesh).engine
        self.spans.wrap(eng, "cross_degree", "fold.cross_degree")
        self.spans.wrap(eng, "finalize", "fold.finalize")
        if eng.kind == "sssp":
            self.spans.wrap(eng, "build_sssp_problem", "engine.window_build")
            self.spans.wrap(eng, "window_accept", "engine.window_accept")

    def reference(self, control: bool = False):
        """The dataset's plain reference, or with ``control`` its control."""
        if control not in self._refs:
            self._refs[control] = self.dataset.Reference(self.config, self.edges, control=control)
        return self._refs[control]

    def release(self) -> None:
        """Drop every handle on the program's state (graph, engines, device
        arrays) before the reference runs."""
        for name in ("svc", "runtime", "ops"):
            self.__dict__.pop(name, None)
