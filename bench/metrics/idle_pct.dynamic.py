"""Device: share of the measured window in which no XLA op ran on the
chip (1 - busy union / window), from the profiler trace of the dynamic cells.

Source: device trace. Moves ``slice_s``.
"""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
