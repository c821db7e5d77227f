"""Quickstart: partition a graph database with DiDiC and measure the
paper's metrics (edge cut, inter-partition traffic, load balance).

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import metrics, partitioners
from repro.core.didic import DidicConfig, didic_partition
from repro.core.framework import PartitionedGraphService
from repro.graphs import datasets
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    # 1. Load a graph dataset (synthetic Twitter crawl, ~6k users).
    graph = datasets.load("twitter", scale=0.01)
    print(graph.summary())

    # 2. Partition it: random baseline vs the paper's DiDiC algorithm.
    k = 4
    random_parts = partitioners.random_partition(graph.n_nodes, k, seed=0)
    didic_parts, _ = didic_partition(graph, DidicConfig(k=k, iterations=60), seed=0)

    # 3. Execute the friend-of-a-friend access pattern on both and compare.
    svc = PartitionedGraphService(graph, k)
    ops = svc.make_ops(n_ops=2000, seed=0)

    for name, parts in (("random", random_parts), ("didic", didic_parts)):
        svc.partition_with(parts)
        result = svc.run_ops(ops)
        report = svc.report()
        print(
            f"{name:>7}: edge_cut={report['edge_cut_fraction']*100:5.1f}%  "
            f"T_G%={result.percent_global*100:5.2f}%  "
            f"modularity={report['modularity']:+.3f}  "
            f"cv_traffic={metrics.coefficient_of_variation(result.per_partition)*100:5.1f}%"
        )

    print("\nDiDiC should cut inter-partition traffic by ≥40% vs random (paper §7.3.3).")


if __name__ == "__main__":
    main()
