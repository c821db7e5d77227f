"""End-to-end driver (the paper's kind: a partitioned graph database under
a served workload): build all three datasets, partition with all methods,
serve batched access-pattern requests, apply dynamism, repair with DiDiC —
the full Static → Insert → Stress → Dynamic lifecycle of Chapter 7.

    PYTHONPATH=src python examples/partition_and_serve.py [--scale 0.01]
"""

import argparse

import numpy as np

from repro.configs.paper_didic import PaperExperimentConfig
from repro.core import metrics, partitioners
from repro.core.didic import didic_partition, didic_refine
from repro.core.dynamism import apply_dynamism, generate_dynamism
from repro.core.framework import PartitionedGraphService
from repro.graphs import datasets
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--k", type=int, default=4)
    args = ap.parse_args()
    enable_compile_cache()
    cfg = PaperExperimentConfig(scale=args.scale)

    for name in cfg.datasets:
        graph = datasets.load(name, scale=cfg.scale)
        print(f"\n=== {name}: {graph.summary()}")
        svc = PartitionedGraphService(graph, args.k, didic=cfg.didic(name, args.k))
        n_ops = cfg.n_ops_gis if name == "gis" else cfg.n_ops
        ops = svc.make_ops(n_ops=n_ops, seed=0)

        # --- Static experiment: three partitioning methods
        results = {}
        for method in ("random", "didic", "hardcoded"):
            if method == "random":
                parts = partitioners.random_partition(graph.n_nodes, args.k, seed=0)
            elif method == "didic":
                parts, _ = didic_partition(graph, cfg.didic(name, args.k), seed=0)
            else:
                parts = partitioners.hardcoded_for(graph, args.k)
                if parts is None:
                    continue
            svc.partition_with(parts)
            res = svc.run_ops(ops)
            results[method] = res.percent_global
            print(f"  static/{method:9s}: ec={metrics.edge_cut_fraction(graph, parts)*100:5.1f}% "
                  f"T_G%={res.percent_global*100:6.2f}%")
        red = (1 - results["didic"] / max(results["random"], 1e-9)) * 100
        print(f"  → DiDiC traffic reduction vs random: {red:.0f}% (paper band: 40–90%)")

        # --- Insert + Stress: degrade with 25% dynamism, repair with 1 iter
        didic_parts, state = didic_partition(graph, cfg.didic(name, args.k), seed=0)
        log = generate_dynamism(didic_parts, 0.25, "random", k=args.k, seed=1)
        damaged = apply_dynamism(didic_parts, log)
        svc.partition_with(damaged)
        pg_damaged = svc.run_ops(ops).percent_global
        repaired, _ = didic_refine(graph, damaged, cfg.didic(name, args.k), state=state,
                                   iterations=1)
        svc.partition_with(repaired)
        pg_repaired = svc.run_ops(ops).percent_global
        print(f"  stress: damaged T_G%={pg_damaged*100:.2f} → repaired {pg_repaired*100:.2f} "
              f"(one DiDiC iteration)")


if __name__ == "__main__":
    main()
