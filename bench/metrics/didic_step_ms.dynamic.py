"""DiDiC (``core/didic_distributed.py``): device time of one repair
iteration, the jitted DiDiC ``step`` program, per iteration in the window.

Source: device trace, the modules named in ``MODULES``. Moves ``slice_s``.
"""

MODULES = ("jit_step",)


def step_seconds(run):
    t = run.trace
    its = run.samples.get("iterations", 0)
    if t is None or not its:
        return None
    secs = sum(v for name, v in t.modules.items() if name in MODULES)
    return secs / its if secs > 0 else None


def read(run):
    s = step_seconds(run)
    return None if s is None else 1000.0 * s
