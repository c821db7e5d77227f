"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

* busy: the union of the intervals in which an XLA op ran on a device,
  clipped to the measured window and averaged over the devices;
* module time: seconds per XLA module (a jitted function's program) on the
  devices, by name with the trailing ``(<id>)`` removed;
* op time: seconds per XLA op, named ``<module>/<op> (<kind>)``, for the
  breakdown;
* idle gaps: the device's idle time inside the window, attributed to the
  innermost harness span (host events named ``bench:<name>``) that covers
  each gap's midpoint.

The window is the host event ``bench:window``; host and device events share
the profiler's clock.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW = "bench:window"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
_MODULE_ID = re.compile(r"\(\d+\)$")
_OP_KIND = re.compile(r"[\]\})] ([a-z][\w-]*)\(")


@dataclasses.dataclass
class Trace:
    window_s: float
    busy_s: float                      # mean over devices
    n_devices: int
    modules: Dict[str, float]          # module name -> device seconds (summed)
    module_calls: Dict[str, int]
    ops: Dict[str, float]              # op name -> device seconds (summed)
    idle_by_span: Dict[str, float]     # host span -> idle device seconds (device 0)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(a: float, b: float, lo: float, hi: float) -> float:
    return max(0.0, min(b, hi) - max(a, lo))


def _module_name(name: str) -> str:
    return _MODULE_ID.sub("", name)


def _op_name(text: str) -> str:
    """``%fusion.12 (fusion)`` from an op's HLO text."""
    head = text.split(" = ", 1)[0].strip()
    kind = _OP_KIND.search(text)
    return f"{head} ({kind.group(1)})" if kind and head != text else head[:80]


def reduce_planes(planes) -> Trace:
    """``planes``: objects with ``name`` and ``lines``; lines with ``name``
    and ``events``; events with ``name``, ``start_ns`` and ``duration_ns``
    (the shape of ``jax.profiler.ProfileData``)."""
    host_spans: List[Tuple[str, float, float]] = []
    devices = []
    for plane in planes:
        if _DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench:"):
                    host_spans.append((ev.name, float(ev.start_ns),
                                       float(ev.start_ns) + float(ev.duration_ns)))
    windows = [s for s in host_spans if s[0] == WINDOW]
    if not windows:
        raise ValueError(f"trace has no {WINDOW!r} host span")
    _, w0, w1 = windows[-1]
    if not devices:
        raise ValueError("trace has no device plane")

    modules: Dict[str, float] = collections.defaultdict(float)
    calls: Dict[str, int] = collections.defaultdict(int)
    ops: Dict[str, float] = collections.defaultdict(float)
    busy = []
    busy0: List[Tuple[float, float]] = []
    for d, plane in enumerate(sorted(devices, key=lambda p: p.name)):
        op_iv: List[Tuple[float, float]] = []
        mod_iv: List[Tuple[float, float, str]] = []
        op_ev = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    a = float(ev.start_ns)
                    b = a + float(ev.duration_ns)
                    t = _clip(a, b, w0, w1)
                    if t > 0:
                        name = _module_name(ev.name)
                        modules[name] += t * 1e-9
                        calls[name] += 1
                        mod_iv.append((a, b, name))
            elif line.name == "XLA Ops":
                for ev in line.events:
                    a = float(ev.start_ns)
                    b = a + float(ev.duration_ns)
                    t = _clip(a, b, w0, w1)
                    if t > 0:
                        op_ev.append((a, t, ev.name))
                        op_iv.append((a, b))
        mod_iv.sort()
        starts = [m[0] for m in mod_iv]
        for a, t, name in op_ev:
            i = bisect.bisect_right(starts, a) - 1
            mod = mod_iv[i][2] if i >= 0 and a <= mod_iv[i][1] else "?"
            ops[f"{mod}/{_op_name(name)}"] += t * 1e-9
        merged = _union(op_iv if op_iv else [m[:2] for m in mod_iv])
        busy.append(sum(_clip(a, b, w0, w1) for a, b in merged) * 1e-9)
        if d == 0:
            busy0 = merged

    idle: Dict[str, float] = collections.defaultdict(float)
    spans = [s for s in host_spans if s[0] != WINDOW]
    cursor = w0
    for a, b in busy0 + [(w1, w1)]:
        a, b = max(a, w0), min(max(b, a), w1)
        if a > cursor:
            idle[_innermost(spans, 0.5 * (cursor + a))] += (a - cursor) * 1e-9
        cursor = max(cursor, b)
    return Trace(
        window_s=(w1 - w0) * 1e-9,
        busy_s=float(np.mean(busy)),
        n_devices=len(devices),
        modules=dict(modules),
        module_calls=dict(calls),
        ops=dict(ops),
        idle_by_span=dict(idle),
    )


def _innermost(spans: List[Tuple[str, float, float]], t: float) -> str:
    best: Optional[Tuple[str, float, float]] = None
    for s in spans:
        if s[1] <= t <= s[2] and (best is None or s[2] - s[1] < best[2] - best[1]):
            best = s
    return best[0][len("bench:"):] if best is not None else "outside spans"


def reduce_file(path: str) -> Trace:
    import jax

    return reduce_planes(jax.profiler.ProfileData.from_file(path).planes)


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
