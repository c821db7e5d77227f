"""Run one benchmark cell on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` ``workloads``. Without a TPU, or
with fewer chips than the cell asks for, it prints no result and exits 2.
"""

import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    from bench import harness

    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
