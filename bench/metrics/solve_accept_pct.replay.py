"""Engines (window acceptance, ``core/traffic_batched.py``): the share of
GIS op solves whose result was kept, in %.

A windowed solve whose A* ellipse does not provably fit its window is
thrown away and the op solved again on the whole graph. Source: the
program's counters over the traced window (``bench/program.py``):
100 × (``sssp.op_solves`` − ``sssp.redo_ops``) / ``sssp.op_solves``.
Nothing for a program without them. Moves ``ops_per_s``.
"""

from bench import program


def read(run):
    snap = program.snapshot()
    if snap is None:
        return None
    solves = snap["counters"].get("sssp.op_solves", 0)
    if not solves:
        return None
    return 100.0 * (solves - snap["counters"].get("sssp.redo_ops", 0)) / solves
