"""Engines (``core/traffic_batched.py``): device ms per relax sweep of the
GIS solve.

Source: device seconds of the solve programs named in ``MODULES`` (the
windowed and the whole-graph redo solve, as a v5e trace names them),
divided by the program's ``sssp.relax_rounds`` counter (relax sweeps run,
summed over shards and chunks) over the same traced window
(``bench/program.py``). The solve's membership and ranking tail is in the
programs' time too, so this is the cost of a sweep with its share of that
tail. Nothing for a program without the counter. Moves ``ops_per_s``.
"""

from bench import program

MODULES = ("jit_solve_body", "jit_solve_full_body")


def read(run):
    t, snap = run.trace, program.snapshot()
    if t is None or snap is None:
        return None
    rounds = snap["counters"].get("sssp.relax_rounds", 0)
    secs = sum(t.modules.get(m, 0.0) for m in MODULES)
    if rounds <= 0 or secs <= 0:
        return None
    return 1000.0 * secs / rounds
