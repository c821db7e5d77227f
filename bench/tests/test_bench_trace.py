"""The trace reduction, on made-up planes and on a small recorded trace.

``data/small.xplane.pb`` was recorded on one TPU v5e chip: inside a
``bench:window`` span, three ``bench:tick`` spans each ran one jitted
program (a 1024² matmul and a reduction) and three ``bench:idle`` spans
slept 20 ms.
"""

from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import trace

DATA = Path(__file__).with_name("data") / "small.xplane.pb"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench:window", 1000, 9000),
        ev("bench:tick", 1000, 4000),
        ev("bench:fold", 3000, 1500),
        ev("bench:idle", 5000, 5000),
        ev("other", 0, 100),
    ])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_step(12)", 500, 2000), ev("jit_step(12)", 6000, 1000),
                                       ev("jit_fold(3)", 9000, 4000)]),
        NS(name="XLA Ops", events=[
            ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 500, 1000),
            ev("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 1500, 1000),
            ev("%gather.3 = (f32[8]{0}, s32[]) gather(f32[8]{0} %p)", 6000, 1000),
            ev("scatter.4", 9000, 4000)]),
    ])
    return [host, dev, NS(name="/device:TPU:0 SparseCore 0", lines=[])]


def test_reduce_made_up_planes():
    t = trace.reduce_planes(planes())
    assert t.window_s == pytest.approx(9e-6)
    # busy inside [1000, 10000]: 1000–2500, 6000–7000, 9000–10000 ns
    assert t.busy_s == pytest.approx(3.5e-6)
    assert t.n_devices == 1
    assert t.modules == pytest.approx({"jit_step": 2.5e-6, "jit_fold": 1e-6})
    assert t.ops["jit_step/%fusion.1 (fusion)"] == pytest.approx(0.5e-6)
    assert t.ops["jit_step/%gather.3 (gather)"] == pytest.approx(1e-6)
    assert t.ops["jit_fold/scatter.4"] == pytest.approx(1e-6)
    # idle 2500–6000 (mid 4250: inside tick and fold -> fold), 7000–9000 (idle)
    assert t.idle_by_span == pytest.approx({"fold": 3.5e-6, "idle": 2e-6})
    assert trace.top(t.modules, 1) == [["jit_step", pytest.approx(2.5e-6)]]


def test_reduce_needs_window_and_device():
    p = planes()
    p[0].lines[0].events = p[0].lines[0].events[1:]
    with pytest.raises(ValueError, match="bench:window"):
        trace.reduce_planes(p)
    with pytest.raises(ValueError, match="device plane"):
        trace.reduce_planes(planes()[:1])


def test_reduce_recorded_trace():
    t = trace.reduce_file(str(DATA))
    assert t.n_devices == 1
    assert 0.06 < t.window_s < 1.0
    assert 0 < t.busy_s < t.window_s
    assert sum(t.modules.values()) == pytest.approx(t.busy_s, rel=0.2)
    assert t.idle_by_span.get("idle", 0) > 0.05
    assert t.idle_by_span["idle"] > 0.5 * sum(t.idle_by_span.values())
