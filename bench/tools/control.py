"""Run a cell with its control in the program's place, on the chip, at the
cell's own size.

    python bench/tools/control.py --workload twitter_k4.dynamic --seeds 3,4,5 --seconds 20

For each seed, one whole run of the cell in this process (set-up, window,
the program's state freed); then the control replaces what the window
produced: the reference one precision step down (DiDiC repair and route
distances in bfloat16) or, for the integer 2-hop counters, with the
receiver-side booking broken. The harness's own check judges it and prints
its result line, whose ``correct`` must be false and whose ``checks`` are
the control's readings. Needs a TPU.
"""

import argparse
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    cell, _, _, _ = harness.load_cell(args.workload)
    devices = harness.require_chips(int(cell["chips"]))
    harness.enable_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        harness.run(args.workload, seed, args.seconds, False, time.perf_counter(), devices,
                    control=True)
        sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
