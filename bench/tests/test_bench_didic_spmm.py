"""``didic_spmm_ms.dynamic``: the DiDiC step's device time per sparse product,
from the program's ``didic.spmms`` over the traced window."""

from __future__ import annotations

import sys

import jax
import pytest

from bench import harness, program
from bench import trace as trace_mod

READER = "didic_spmm_ms.dynamic"


def fake_run(secs: float, samples: dict = None):
    t = trace_mod.Trace(window_s=1.0, busy_s=0.9, n_devices=1, modules={"jit_step": secs},
                        module_calls={}, ops={}, idle_by_span={})
    return harness.RunRecord(samples or {}, t, harness.peaks("TPU v5 lite"))


@pytest.mark.parametrize("width", [64, 256])
def test_reader_takes_the_traced_window(tmp_path, width):
    from repro.core import tracing

    spmms = 11 * 10 + width
    samples = {"iterations": 2}
    tracing.reset()
    try:
        tracing.count("didic.spmms", 1000)  # before the trace: left out
        jax.profiler.start_trace(str(tmp_path))
        try:
            tracing.count("didic.spmms", 2 * spmms)
        finally:
            jax.profiler.stop_trace()
        got = harness.metric_reader(READER).read(fake_run(30.0, samples))
        step = harness.metric_reader("didic_step_ms.dynamic").read(fake_run(30.0, samples))
    finally:
        tracing.reset()
    assert got == pytest.approx(1000 * 30.0 / (2 * spmms))
    # One repair iteration is spmm_per_iteration products.
    assert got * spmms == pytest.approx(step)


def test_reader_reports_nothing_without_the_counter_or_the_step(monkeypatch, tmp_path):
    from repro.core import tracing

    tracing.reset()
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            tracing.count("replay.ops", 5)
        finally:
            jax.profiler.stop_trace()
        assert harness.metric_reader(READER).read(fake_run(30.0)) is None
        jax.profiler.start_trace(str(tmp_path / "again"))
        try:
            tracing.count("didic.spmms", 174)
        finally:
            jax.profiler.stop_trace()
        assert harness.metric_reader(READER).read(fake_run(0.0)) is None
        assert harness.metric_reader(READER).read(fake_run(30.0)) is not None
    finally:
        tracing.reset()
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)  # import fails
    assert program.snapshot() is None
    assert harness.metric_reader(READER).read(fake_run(30.0)) is None
