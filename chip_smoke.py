"""Run the partitioned graph database on a TPU at the paper's scale.

    python chip_smoke.py              # one chip: the paper's lifecycle
    python chip_smoke.py --chips 4    # the mesh path only, on four chips

With no option, one process drives the paper's lifecycle through
:class:`~repro.core.framework.PartitionedGraphService` on a one-device
mesh, for ``filesystem``, ``twitter`` and ``gis`` at ``scale=1.0`` and
``k=4`` (configs from ``repro.configs.paper_didic``):

1. DiDiC initial partition (``partition_didic``, cut to T=2, below);
2. replay of each dataset's log (``filesystem``, ``twitter``,
   ``gis_short``, ``gis_long``), cold and then resident, which must agree;
3. a 64-op sub-log of each pattern replayed on the chip and through the
   scalar oracle, which must agree on all four counters;
4. one 5 % dynamism slice, ``maintain(1)``, then a resident replay that
   must equal a cold one;
5. a few hundred ``OnlineServer`` ticks of ``uniform`` twitter arrivals,
   which must equal ``offline_replay`` of the served epochs.

``--chips 4`` runs only what exists across chips, and what it is compared
with: ``replay_sharded`` on a four-device mesh, cold and then resident,
against the one-device batched engine (bit-exact, four patterns), and
DiDiC with ``maintenance="sharded"`` (halo exchange) against ``"shared"``.

Each phase prints one JSON line: wall seconds, the part of them spent
compiling, and what it checked. The last line of standard output is
``{"ok": true, "device": {...}}``; it is printed only when every check
passed. Without a TPU the script stops before any phase and exits 1.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"
COUNTERS = ("per_op_total", "per_op_global", "per_partition", "per_vertex")
PATTERNS = {
    "filesystem": ("filesystem",),
    "twitter": ("twitter",),
    "gis": ("gis_short", "gis_long"),
}
# The paper's deployment size; there is no option to run smaller.
SCALE = 1.0
# Cuts forced by the run's time limit; the graphs stay at SCALE.
# - DiDiC initial partition: T=2 of the paper's 100 iterations. Each
#   iteration is 110 or more SpMMs (gather + segment_sum over up to 4.2 M
#   edges); at T=100 the filesystem partition alone did not finish in
#   1,100 s on one TPU v5e chip. Maintenance runs its full iteration.
# - GIS logs: 128 ops (one engine chunk) of the paper config's 300.
# - Scalar-oracle sub-logs: 64 ops, but 8 for gis_long. The oracle
#   settles vertices one Python step at a time, about 7 s per paper-scale
#   gis_long op on the chip machine's host.
DIDIC_T = 2
GIS_OPS = 128
SUB_LOG = {"gis_long": 8}
K = 4


class SmokeFailure(RuntimeError):
    """A check of the smoke failed: the run must not report success."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def same(a, b) -> bool:
    return all(np.array_equal(getattr(a, f), getattr(b, f)) for f in COUNTERS)


class PhaseClock:
    """Wall time per phase, with the XLA compile time inside it.

    ``compile_s`` is JAX's backend compile time, which includes reading an
    executable back from the persistent cache; ``cache`` counts the cache's
    lookups, hits and writes, so a warm run shows what it did not compile.
    Both are read from the database's tracing counters
    (:mod:`repro.core.tracing`).
    """

    _CACHE = {"lookups": "jax.cache_lookups", "hits": "jax.cache_hits",
              "writes": "jax.cache_writes"}

    def __init__(self, out=print):
        import jax

        from repro.core import tracing

        self.out = out
        self._tracing = tracing
        self._c0 = tracing.snapshot()["counters"]
        self._device = jax.devices()[0]

    def _since_start(self, name: str) -> int:
        return self._tracing.snapshot()["counters"].get(name, 0) - self._c0.get(name, 0)

    @property
    def compile_s(self) -> float:
        return self._since_start("jax.compile_ns") * 1e-9

    @property
    def cache(self) -> dict:
        return {k: self._since_start(name) for k, name in self._CACHE.items()}

    @contextlib.contextmanager
    def phase(self, name: str, **fields):
        info = dict(fields)
        c0, t0 = self.compile_s, time.perf_counter()
        yield info
        stats = self._device.memory_stats() or {}
        line = {"phase": name, **info,
                "wall_s": time.perf_counter() - t0,
                "compile_s": self.compile_s - c0}
        if "peak_bytes_in_use" in stats:
            line["peak_bytes_in_use"] = stats["peak_bytes_in_use"]
        self.out(json.dumps(line))


def _relax_path(svc, pattern: str) -> str:
    from repro.core.traffic_sharded import get_replayer

    eng = get_replayer(svc.graph, pattern, svc.mesh).engine
    return "pallas" if eng.use_kernel else "xla"


def replay_phases(svc, ops, clock: PhaseClock) -> None:
    """Cold then resident replay of one log; a sub-log against the oracle."""
    from repro.core.traffic import OpLog

    p = ops.pattern
    with clock.phase("replay_cold", pattern=p, n_ops=ops.n_ops) as info:
        cold = svc.run_ops(ops, engine="sharded", resident=False)
        info["percent_global"] = cold.percent_global
    info = {"pattern": p}
    if p.startswith("gis"):
        info["relax"] = _relax_path(svc, p)
    with clock.phase("replay_resident_capture", **info):
        captured = svc.run_ops(ops, engine="sharded")
    with clock.phase("replay_resident", pattern=p, n_ops=ops.n_ops) as info:
        t0 = time.perf_counter()
        resident = svc.run_ops(ops, engine="sharded")
        info["ops_per_s_info"] = ops.n_ops / (time.perf_counter() - t0)
        info["resident_state_bytes"] = svc.logger.resident_state_bytes
    require(same(captured, cold), f"{p}: resident capture != cold replay")
    require(same(resident, cold), f"{p}: resident replay != cold replay")

    n = SUB_LOG.get(p, 64)
    sub = OpLog(p, ops.starts[:n], ops.ends[:n], t_l=ops.t_l, t_pg=ops.t_pg)
    with clock.phase("oracle_check", pattern=p, n_ops=sub.n_ops):
        chip = svc.run_ops(sub, engine="sharded", resident=False)
        oracle = svc.run_ops(sub, engine="scalar")
    require(same(chip, oracle), f"{p}: chip replay != scalar oracle")


def lifecycle(name: str, scale: float, mesh, clock: PhaseClock):
    """Phases 1–4 on one dataset; returns the service for serving."""
    from repro.configs.paper_didic import PaperExperimentConfig
    from repro.core import metrics
    from repro.core.dynamism import generate_dynamism
    from repro.core.framework import PartitionedGraphService
    from repro.graphs import datasets

    cfg = PaperExperimentConfig(scale=scale, didic_iterations=DIDIC_T)
    with clock.phase("load", dataset=name) as info:
        graph = datasets.load(name, scale=scale)
        info.update(n_nodes=graph.n_nodes, n_edges=int(graph.senders.shape[0]))
    svc = PartitionedGraphService(graph, K, didic=cfg.didic(name, K), mesh=mesh)
    with clock.phase("didic_initial", dataset=name,
                     iterations=cfg.didic_iterations) as info:
        svc.partition_didic(seed=cfg.seed)
        info["edge_cut"] = metrics.edge_cut_fraction(graph, svc.parts)

    n_ops = GIS_OPS if name == "gis" else cfg.n_ops
    logs = [svc.make_ops(n_ops=n_ops, seed=cfg.seed, pattern=p)
            for p in PATTERNS[name]]
    for ops in logs:
        replay_phases(svc, ops, clock)

    with clock.phase("dynamism_maintain", dataset=name, amount=0.05) as info:
        svc.apply_dynamism(
            generate_dynamism(svc.parts, 0.05, "random", k=K, seed=cfg.seed + 1)
        )
        svc.maintain(1)
        info["edge_cut"] = metrics.edge_cut_fraction(svc.graph, svc.parts)
    for ops in logs:
        with clock.phase("replay_after_maintain", pattern=ops.pattern):
            resident = svc.run_ops(ops, engine="sharded")
            cold = svc.run_ops(ops, engine="sharded", resident=False)
        require(same(resident, cold),
                f"{ops.pattern}: resident != cold after maintenance")
    # The slice only moves vertices, so no growth store is attached, and
    # REPRO_GROWTH_HEADROOM (a store's default capacity) shapes nothing here.
    require(svc.graph.store is None, f"{name}: a growth store was attached")
    return svc


def online(svc, clock: PhaseClock, n_ops: int = 1200, ops_per_tick: int = 4):
    """Serve ``uniform`` twitter arrivals; check against offline replay."""
    from repro.core.online import OnlineServer, make_arrival_stream, offline_replay

    stream, t_counts = make_arrival_stream(
        svc.graph, ("twitter",), n_ops=n_ops, seed=0, process="uniform",
        ops_per_tick=ops_per_tick,
    )
    server = OnlineServer(svc, batch_slots=8)
    server.submit_stream(stream, t_counts)
    with clock.phase("online", dataset="twitter", n_ops=n_ops) as info:
        res = server.run()
        info.update(ticks=res.ticks, batches=res.batches_served,
                    epochs=len(res.epochs))
    with clock.phase("offline_replay", dataset="twitter"):
        per_op, per_partition, per_vertex = offline_replay(
            svc.graph, res.epochs, svc.k, t_counts, engine="batched"
        )
    require(res.ops_served == n_ops, "online: not every arrival was served")
    require(np.array_equal(res.per_op["twitter"], per_op["twitter"])
            and np.array_equal(res.per_partition, per_partition)
            and np.array_equal(res.per_vertex, per_vertex),
            "online serving != offline replay")


def one_chip(scale: float, clock: PhaseClock) -> None:
    from repro.launch.mesh import make_replay_mesh

    mesh = make_replay_mesh(1)
    for name in ("filesystem", "twitter", "gis"):
        svc = lifecycle(name, scale, mesh, clock)
        if name == "twitter":
            online(svc, clock)


def mesh_path(scale: float, chips: int, clock: PhaseClock,
              didic_dataset: str = "twitter") -> None:
    """Sharded replay vs one device, and halo DiDiC vs shared DiDiC."""
    from repro.configs.paper_didic import PaperExperimentConfig
    from repro.core import metrics, partitioners
    from repro.core.framework import PartitionedGraphService
    from repro.core.traffic import execute_ops, generate_ops
    from repro.core.traffic_sharded import replay_sharded
    from repro.graphs import datasets
    from repro.launch.mesh import make_replay_mesh

    cfg = PaperExperimentConfig(scale=scale, didic_iterations=DIDIC_T)
    mesh = make_replay_mesh(chips)
    for name, patterns in PATTERNS.items():
        graph = datasets.load(name, scale=scale)
        parts = partitioners.random_partition(graph.n_nodes, K, seed=cfg.seed)
        for p in patterns:
            ops = generate_ops(graph, n_ops=GIS_OPS if name == "gis"
                               else cfg.n_ops, seed=cfg.seed, pattern=p)
            with clock.phase("sharded_replay", pattern=p, shards=chips):
                got = replay_sharded(graph, ops, mesh, parts, K, resident=False)
            with clock.phase("sharded_resident_capture", pattern=p, shards=chips):
                captured = replay_sharded(graph, ops, mesh, parts, K)
            with clock.phase("sharded_resident", pattern=p, shards=chips):
                resident = replay_sharded(graph, ops, mesh, parts, K)
            with clock.phase("one_device_replay", pattern=p):
                ref = execute_ops(graph, ops, parts, K, engine="batched")
            require(same(got, ref), f"{p}: {chips}-shard replay != one device")
            require(same(captured, ref) and same(resident, ref),
                    f"{p}: {chips}-shard resident replay != one device")

        if name != didic_dataset:
            continue
        cuts = {"random": metrics.edge_cut_fraction(graph, parts)}
        for mode in ("sharded", "shared"):
            svc = PartitionedGraphService(graph, K, didic=cfg.didic(name, K),
                                          mesh=mesh, maintenance=mode)
            with clock.phase("didic_initial", dataset=name,
                             maintenance=mode) as info:
                svc.partition_didic(seed=cfg.seed)
                cuts[mode] = info["edge_cut"] = metrics.edge_cut_fraction(
                    graph, svc.parts)
        require(cuts["sharded"] < cuts["random"] and cuts["shared"] < cuts["random"],
                f"DiDiC edge cuts no better than random: {cuts}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh path, on four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {devices[0].platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked for, {len(devices)} found",
              file=sys.stderr)
        return 1

    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    print(json.dumps({"compile_cache": cache_dir}), flush=True)
    clock = PhaseClock(out=lambda s: print(s, flush=True))
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(SCALE, clock)
    else:
        mesh_path(SCALE, args.chips, clock)
    print(json.dumps({"total_wall_s": time.perf_counter() - t0,
                      "total_compile_s": clock.compile_s,
                      "compile_cache": clock.cache}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
