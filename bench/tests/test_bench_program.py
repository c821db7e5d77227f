"""The readers of the program's own tracing: idle attributed to ``repro:``
spans, and the per-layer metrics that read the program's spans and
counters."""

from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace as NS

import jax
import pytest

from bench import harness, program
from bench import trace as trace_mod
from bench.tests.test_bench_trace import ev, planes

DATA = Path(__file__).with_name("data") / "small.xplane.pb"
READERS = ("window_build_ms_per_op.replay", "relax_round_ms.replay", "solve_accept_pct.replay")


def planes_with_program_spans():
    p = planes()
    p[0].lines.append(NS(name="python", events=[
        ev("repro:replay", 1000, 8000),
        ev("repro:sssp.window_build", 2000, 2000),
        ev("repro:sssp.heuristic", 3000, 500),
    ]))
    return p


def test_idle_by_program_span_on_made_up_planes():
    p = planes_with_program_spans()
    # idle 2500–6000 is cut at the spans' edges: 2500–3000 and 3500–4000
    # in the build, 3000–3500 in the heuristic, 4000–6000 in the replay;
    # 7000–9000 falls in the replay.
    assert program.idle_by_program_span(p) == pytest.approx(
        {"replay": 4e-6, "sssp.window_build": 1e-6, "sssp.heuristic": 0.5e-6})
    # Spans that end early leave the idle after them outside.
    p[0].lines[-1].events[0] = ev("repro:replay", 1000, 5000)
    assert program.idle_by_program_span(p) == pytest.approx(
        {"replay": 2e-6, "sssp.window_build": 1e-6, "sssp.heuristic": 0.5e-6,
         program.OUTSIDE: 2e-6})
    # The harness's reduction reads exactly what it read without them.
    assert trace_mod.reduce_planes(p) == trace_mod.reduce_planes(planes())


def test_idle_by_program_span_on_recorded_trace():
    ps = list(jax.profiler.ProfileData.from_file(str(DATA)).planes)
    t = trace_mod.reduce_planes(ps)
    idle = program.idle_by_program_span(ps)
    assert set(idle) == {program.OUTSIDE}
    assert idle[program.OUTSIDE] == pytest.approx(sum(t.idle_by_span.values()))


def fake_run(secs=0.6):
    t = trace_mod.Trace(window_s=1.0, busy_s=0.4, n_devices=1,
                        modules={"jit_solve_body": secs}, module_calls={}, ops={},
                        idle_by_span={})
    return harness.RunRecord({}, t, harness.peaks("TPU v5 lite"))


def test_readers_take_the_traced_window(tmp_path):
    from repro.core import tracing

    tracing.reset()
    try:
        tracing.count("replay.ops", 1000)  # before the trace: left out
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tracing.span("sssp.window_build"):
                pass
            tracing.count("replay.ops", 128)
            tracing.count("sssp.op_solves", 160)
            tracing.count("sssp.redo_ops", 32)
            tracing.count("sssp.relax_rounds", 300)
        finally:
            jax.profiler.stop_trace()
        build_s = program.snapshot()["spans"]["sssp.window_build"]["total_s"]
        got = {name: harness.metric_reader(name).read(fake_run()) for name in READERS}
    finally:
        tracing.reset()
    assert got["window_build_ms_per_op.replay"] == pytest.approx(1000 * build_s / 128)
    assert got["relax_round_ms.replay"] == pytest.approx(1000 * 0.6 / 300)
    assert got["solve_accept_pct.replay"] == pytest.approx(80.0)


def test_readers_report_nothing_without_the_program_registry(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)  # import fails
    assert program.snapshot() is None
    for name in READERS:
        assert harness.metric_reader(name).read(fake_run()) is None
