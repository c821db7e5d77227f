"""Engines (``core/traffic_sharded.py``, ``core/traffic_batched.py``):
device time of the replay engine's XLA programs per op replayed.

Source: device trace; the programs are the jitted engine functions named
in ``MODULES``, as a v5e trace names them: the GIS solve (windowed and
whole-graph redo), its per-vertex scatter, the BFS prefix table and
frontier-mass fold, and the replayer's small gathers. Moves ``ops_per_s``.
"""

MODULES = ("jit_solve_body", "jit_solve_full_body", "jit_scatter_psum",
           "jit__bfs_prefix_one", "jit_tm_body", "jit__lambda")


def read(run):
    t = run.trace
    ops = run.samples.get("ops", 0)
    if t is None or not ops:
        return None
    secs = sum(v for name, v in t.modules.items() if name in MODULES)
    if secs <= 0:
        return None
    return 1000.0 * secs / ops
