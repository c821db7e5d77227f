"""Engines (``core/traffic_batched.py``): host time of the GIS window build
per op replayed, in ms.

Source: the program's span ``sssp.window_build`` (box selection, capped
gather layout and the ops' destination coordinates; the heuristic rows are
computed inside the solve) over the traced window, divided by the
program's ``replay.ops`` counter of the same window (``bench/program.py``).
Nothing for a program without that span. Moves ``ops_per_s``.
"""

from bench import program


def read(run):
    snap = program.snapshot()
    if snap is None:
        return None
    build = snap["spans"].get("sssp.window_build")
    ops = snap["counters"].get("replay.ops", 0)
    if build is None or not ops:
        return None
    return 1000.0 * build["total_s"] / ops
