"""What the database's own tracing (``repro.core.tracing``) says about a run.

The program books spans (``sssp.window_build``, ``sssp.solve``, ``slice``,
...) and counters (``sssp.relax_rounds``, ``replay.ops``, ...) while it
works; while a profiler trace records, it books them a second time to a
registry that restarts with the trace, and puts each span on the trace's
host plane as ``repro:<name>``. The readers here take the traced window
when there is one. A program without the tracing module (an older
commit) gives ``None``, so its readers report nothing.
"""

from __future__ import annotations

import collections
import importlib
from typing import Dict, List, Optional, Tuple

from bench import trace as trace_mod

PREFIX = "repro:"
OUTSIDE = "outside program spans"


def snapshot() -> Optional[dict]:
    """``{"spans", "counters"}`` over the latest profiler trace, or over
    the whole process if no trace booked anything; ``None`` when the
    program has no tracing registry."""
    try:
        tracing = importlib.import_module("repro.core.tracing")
    except ImportError:
        return None
    snap = tracing.snapshot()
    traced = snap["traced"]
    if traced["spans"] or traced["counters"]:
        return traced
    return {"spans": snap["spans"], "counters": snap["counters"]}


def idle_by_program_span(planes) -> Dict[str, float]:
    """Device 0's idle seconds inside the ``bench:window`` span, by what
    the host was doing: each gap is cut at the ``repro:`` span boundaries
    inside it, and each piece put down to the innermost span covering it,
    or to ``OUTSIDE``. ``planes`` as for ``bench.trace.reduce_planes``;
    the busy intervals are those it takes (XLA ops, else modules)."""
    window: Optional[Tuple[float, float]] = None
    spans: List[Tuple[str, float, float]] = []
    devices = []
    for plane in planes:
        if trace_mod._DEVICE_PLANE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    a = float(ev.start_ns)
                    b = a + float(ev.duration_ns)
                    if ev.name == trace_mod.WINDOW:
                        window = (a, b)
                    elif ev.name.startswith(PREFIX):
                        spans.append((ev.name[len(PREFIX):], a, b))
    if window is None or not devices:
        raise ValueError("trace has no window span or no device plane")
    w0, w1 = window
    dev0 = sorted(devices, key=lambda p: p.name)[0]
    ivs = {"XLA Ops": [], "XLA Modules": []}
    for line in dev0.lines:
        if line.name in ivs:
            ivs[line.name] += [(float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns))
                               for ev in line.events]
    busy = trace_mod._union(ivs["XLA Ops"] or ivs["XLA Modules"])
    idle: Dict[str, float] = collections.defaultdict(float)
    cursor = w0
    for a, b in busy + [(w1, w1)]:
        a, b = max(a, w0), min(max(b, a), w1)
        if a > cursor:
            inside = [sp for sp in spans if sp[1] < a and sp[2] > cursor]
            cuts = sorted({cursor, a} | {t for sp in inside for t in sp[1:] if cursor < t < a})
            for c0, c1 in zip(cuts, cuts[1:]):
                idle[_innermost(inside, 0.5 * (c0 + c1))] += (c1 - c0) * 1e-9
        cursor = max(cursor, b)
    return dict(idle)


def _innermost(spans: List[Tuple[str, float, float]], t: float) -> str:
    best = None
    for s in spans:
        if s[1] <= t <= s[2] and (best is None or s[2] - s[1] < best[2] - best[1]):
            best = s
    return best[0] if best is not None else OUTSIDE
