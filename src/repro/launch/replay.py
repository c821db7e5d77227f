"""Sharded traffic-replay CLI (ISSUE 2).

Replays a generated evaluation log against a partitioning on a 1-D data
mesh via :func:`repro.core.traffic_sharded.replay_sharded`, verifying
bit-exactness against the single-device batched engine before reporting
throughput. On a CPU-only host, ``--force-host-devices N`` fakes an
N-device platform (the flag must reach XLA before jax initializes, which
is why all heavy imports live inside :func:`main`).

``--degraded-check`` additionally runs the log through a
:class:`~repro.core.framework.PartitionedGraphService` with one shard
marked failed, verifying the degraded fallback (shared batched engine)
stays bit-equal to the healthy sharded replay and reporting the
degraded-operation accounting from the service's health report.

Examples::

  python -m repro.launch.replay --dataset gis --pattern gis_short \
      --n-ops 2000 --force-host-devices 8
  python -m repro.launch.replay --dataset twitter --n-ops 100000 \
      --partitioner didic --no-verify
  python -m repro.launch.replay --dataset gis --force-host-devices 4 \
      --degraded-check
"""

from __future__ import annotations

import argparse
import json
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--dataset", default="gis",
                    choices=("filesystem", "gis", "twitter"))
    ap.add_argument("--pattern", default=None,
                    help="access pattern (default: the dataset's)")
    ap.add_argument("--n-ops", type=int, default=2_000)
    ap.add_argument("--scale", type=float, default=0.004)
    ap.add_argument("--k", type=int, default=4, help="partition count")
    ap.add_argument("--partitioner", default="random",
                    choices=("random", "didic"))
    ap.add_argument("--shards", type=int, default=None,
                    help="data shards (default: all visible devices)")
    ap.add_argument("--force-host-devices", type=int, default=None,
                    help="fake an N-device CPU platform (set before jax init)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-verify", action="store_true",
                    help="skip the bit-exactness check vs the batched engine")
    ap.add_argument("--degraded-check", action="store_true",
                    help="also replay through a service with one failed "
                         "shard and verify the degraded fallback is bit-equal")
    args = ap.parse_args()

    if args.force_host_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.force_host_devices}"
        ).strip()

    import numpy as np  # noqa: E402 (after XLA_FLAGS on purpose)

    from repro.core import partitioners
    from repro.core.didic import DidicConfig, didic_partition
    from repro.core.traffic import execute_ops, generate_ops
    from repro.core.traffic_sharded import replay_sharded
    from repro.graphs import datasets
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_replay_mesh

    enable_compile_cache()
    graph = datasets.load(args.dataset, scale=args.scale)
    ops = generate_ops(graph, n_ops=args.n_ops, seed=args.seed,
                       pattern=args.pattern)
    if args.partitioner == "didic":
        parts, _ = didic_partition(
            graph, DidicConfig(k=args.k, iterations=40), seed=args.seed
        )
    else:
        parts = partitioners.random_partition(graph.n_nodes, args.k, seed=args.seed)

    mesh = make_replay_mesh(args.shards)
    res = replay_sharded(graph, ops, mesh, parts, args.k)  # warm / compile
    t0 = time.perf_counter()
    res = replay_sharded(graph, ops, mesh, parts, args.k)
    dt = time.perf_counter() - t0

    if not args.no_verify:
        ref = execute_ops(graph, ops, parts, args.k, engine="batched")
        for field in ("per_op_total", "per_op_global", "per_partition", "per_vertex"):
            if not np.array_equal(getattr(res, field), getattr(ref, field)):
                raise SystemExit(f"sharded replay diverged from batched on {field}")

    degraded = None
    if args.degraded_check:
        from repro.core.framework import PartitionedGraphService

        svc = PartitionedGraphService(graph, args.k, mesh=mesh)
        svc.partition_with(parts)
        svc.mark_shard_failed(len(mesh.devices.flat) - 1)
        deg = svc.run_ops(ops)
        for field in ("per_op_total", "per_op_global", "per_partition", "per_vertex"):
            if not np.array_equal(getattr(deg, field), getattr(res, field)):
                raise SystemExit(f"degraded fallback diverged on {field}")
        degraded = svc.logger.health_report()

    out = {
        "dataset": args.dataset,
        "pattern": ops.pattern,
        "n_ops": ops.n_ops,
        "shards": len(mesh.devices.flat),
        "ops_per_s": round(ops.n_ops / dt, 1),
        "total_traffic": res.total,
        "percent_global": round(res.percent_global, 6),
        "verified": not args.no_verify,
    }
    if degraded is not None:
        out["degraded"] = degraded
    print(json.dumps(out))


if __name__ == "__main__":
    main()
