"""Run a cell traced, as ``bench/run.py --trace 1`` does, and print what the
database's own tracing says about the measured window.

    python bench/tools/program_trace.py --workload gis_k4.replay --seed 7 --seconds 20

The run is the harness's own, with its lines and result line unchanged.
Two JSON lines follow on standard error: ``idle_by_program_span``, the
device's idle seconds in the window, each gap put down to the innermost
``repro:`` span of the program (``bench/program.py``); and ``program``, the
spans and counters the program booked over the window. Needs a TPU.
"""

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, program  # noqa: E402
from bench import trace as trace_mod  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    idle = {}
    reduce_file = trace_mod.reduce_file

    def reduce_and_attribute(path):
        import jax

        planes = list(jax.profiler.ProfileData.from_file(path).planes)
        idle.update(program.idle_by_program_span(planes))
        return trace_mod.reduce_planes(planes)

    trace_mod.reduce_file = reduce_and_attribute
    try:
        rc = harness.main(["--workload", args.workload, "--seed", str(args.seed),
                           "--seconds", str(args.seconds), "--trace", "1"], t_start=T_START)
    finally:
        trace_mod.reduce_file = reduce_file
    sys.stdout.flush()
    print(json.dumps({"idle_by_program_span": trace_mod.top(idle, 40)}), file=sys.stderr)
    print(json.dumps({"program": program.snapshot()}), file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
