"""Every cell of ``BENCHMARK.json``, rehearsed end to end on the CPU.

The harness's own ``run`` drives each cell at a tiny scale, with the look
for a chip skipped: set-up, a short window, the comparison with the
reference, and the result line. The per-layer readers are then fed the
run's samples and a made-up trace, and each must find its number.
"""

from __future__ import annotations

import json
import time

import pytest

from bench import harness
from bench import trace as trace_mod

BENCH = harness.load_benchmark()
CELLS = [c["name"] for c in BENCH["workloads"]]


def tiny(cell_name: str) -> dict:
    """The cell's CPU rehearsal sizes: its dataset module's ``TINY``."""
    from bench import drivers

    _, config, _, _ = harness.load_cell(cell_name)
    dataset = drivers.plugin("datasets", config["dataset"])
    if not hasattr(dataset, "TINY"):
        raise LookupError(f"{dataset.__name__} states no TINY, the configuration "
                          f"overrides at which its graph is built for a CPU rehearsal")
    return dict(dataset.TINY)


@pytest.mark.parametrize("config_name", [c["name"] for c in BENCH["configs"]])
def test_rehearsal_sizes_come_from_the_dataset_module(config_name):
    from bench import drivers

    cells = [c["name"] for c in BENCH["workloads"] if c["config"] == config_name]
    assert cells, config_name
    _, config, _, _ = harness.load_cell(cells[0])
    want = drivers.plugin("datasets", config["dataset"]).TINY
    assert set(want) <= set(config), "TINY overrides keys of the configuration"
    assert all(tiny(c) == want for c in cells)


def test_rehearsal_sizes_name_a_dataset_module_without_them(monkeypatch):
    import re
    import sys
    import types

    name = "bench.datasets.sizeless"
    monkeypatch.setitem(sys.modules, name, types.ModuleType(name))
    cell, config, mix, bench = harness.load_cell(CELLS[0])
    monkeypatch.setattr(harness, "load_cell", lambda workload, root=harness.ROOT: (
        cell, {**config, "dataset": "sizeless"}, mix, bench))
    with pytest.raises(LookupError, match=re.escape(name)):
        tiny(CELLS[0])


def rehearse(cell_name: str, seconds: float = 0.3, seed: int = 2**33 + 5):
    import jax

    lines, errs = [], []
    result = harness.run(cell_name, seed, seconds, False, time.perf_counter(),
                         devices=jax.devices(), out=lines.append, err=errs.append,
                         config_overrides=tiny(cell_name))
    return result, lines, errs


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_runs_and_is_correct(cell_name):
    result, lines, errs = rehearse(cell_name)
    assert json.loads(lines[-1]) == result
    assert list(result)[-1] == "checks"
    assert result["correct"] is True, errs
    assert result["attempted"] > 0 and result["failed"] == 0
    want = {m["name"] for m in BENCH["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])}
    assert set(result["metrics"]) == want
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"
    assert errs[-len(result["checks"]):] == [
        f"check {n}: {c['value']} limit {c['limit']} ok" for n, c in result["checks"].items()]


@pytest.mark.parametrize("cell_name", CELLS)
def test_per_layer_readers_find_their_numbers(cell_name):
    import jax

    from bench import drivers

    cell, config, mix, bench = harness.load_cell(cell_name)
    config = {**config, **tiny(cell_name)}
    spans = harness.Spans()
    d = drivers.make(config, mix, 3, spans, jax.devices(), 0.3)
    d.setup()
    with spans("window"):
        d.window(0.3)
    fake = trace_mod.Trace(window_s=1.0, busy_s=0.4, n_devices=1,
                           modules={"jit_step": 0.3, "jit__bfs_prefix_one": 0.1, "jit_solve_body": 0.2},
                           module_calls={}, ops={}, idle_by_span={})
    record = harness.RunRecord(d.samples, fake, harness.peaks("TPU v5 lite"))
    got = harness.per_layer(bench, cell, record)
    want = {m["name"] for m in bench["per_layer"] if cell_name in m["workloads"]}
    assert set(got) == want
    for name, m in got.items():
        assert m["value"] > 0, name
        if m["unit"] == "%":
            assert m["value"] <= 100, name

