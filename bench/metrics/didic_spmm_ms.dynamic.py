"""DiDiC (``core/didic_distributed.py``): device time of the repair step per
sparse product, in ms, so that repairs of different smoothing widths
compare.

Source: device seconds of the jitted DiDiC ``step`` program (``MODULES``)
in the traced window, divided by the program's ``didic.spmms`` counter
(ψ·(ρ+1) + smoothing width per repair iteration) over the same window
(``bench/program.py``). The step's element-wise updates and balance fit
are in its time too. Nothing for a program without the counter. Moves
``slice_s``.
"""

from bench import program

MODULES = ("jit_step",)


def read(run):
    t, snap = run.trace, program.snapshot()
    if t is None or snap is None:
        return None
    spmms = snap["counters"].get("didic.spmms", 0)
    secs = sum(t.modules.get(m, 0.0) for m in MODULES)
    if spmms <= 0 or secs <= 0:
        return None
    return 1000.0 * secs / spmms
