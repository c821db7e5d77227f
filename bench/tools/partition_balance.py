"""How balanced a dynamic cell's partition is, for a number of initial
DiDiC iterations: the largest part over N/k after the initial partition and
after each of the run's slices (the warm-up slices and one window slice),
through the cell's own driver.

    JAX_PLATFORMS=cpu python bench/tools/partition_balance.py \\
        --workload filesystem_k4.dynamic --iterations 4 --seeds 0,1,2

One JSON line per seed on standard output; the exit code is 0 when every
part of every map is at most ``--limit`` × N/k, else 1. It runs on the
devices JAX finds (the CPU with ``JAX_PLATFORMS=cpu``).
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import drivers, harness  # noqa: E402


def balance(workload: str, iterations: int, seed: int, devices, overrides=None) -> dict:
    """Part sizes of every map the cell's driver served, from the initial
    partition on; ``ratio`` is the largest part over N/k."""
    _, config, mix, _ = harness.load_cell(workload)
    config = {**config, **(overrides or {}), "didic_iterations": iterations}
    d = drivers.make(config, mix, seed, harness.Spans(), devices, 0.0)
    d.setup()
    d.window(0.0)
    k, n = config["k"], d.edges.n_nodes
    sizes = [np.bincount(m, minlength=k).tolist() for m in d.maps]
    d.release()
    return {"iterations": iterations, "seed": seed, "n_nodes": n, "sizes": sizes,
            "ratio": max(max(s) for s in sizes) * k / n}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--iterations", type=int, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--limit", type=float, default=1.10)
    args = ap.parse_args(argv)
    import jax

    ok = True
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        out = balance(args.workload, args.iterations, seed, jax.devices()[:1])
        out["seconds"] = time.perf_counter() - t0
        out["device"] = jax.devices()[0].platform
        ok &= out["ratio"] <= args.limit
        print(json.dumps(out), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
