"""The Dynamic driver's check sees the evaluation log as the program
replayed it: targets included, for datasets whose searches stop at one."""

from __future__ import annotations

import sys
import types

import numpy as np

from bench import drivers, harness
from bench.datasets import twitter
from bench.tests.test_bench_controls import REPAIRING


def _targeted_dataset(seen: list) -> types.ModuleType:
    """The Twitter dataset at its rehearsal size, with logs whose ends are
    real vertex ids and a reference that records the ends it is given."""
    mod = types.ModuleType("bench.datasets.targeted")
    mod.TINY = twitter.TINY
    mod.build = twitter.build

    def logs(config, edges, mix, rng):
        for starts, _ in twitter.logs(config, edges, mix, rng):
            yield starts, rng.integers(edges.n_nodes, size=starts.shape[0])

    class Reference(twitter.Reference):
        def counters(self, parts, starts, ends):
            seen.append(ends)
            return super().counters(parts, starts, ends)

    mod.logs, mod.Reference = logs, Reference
    return mod


def test_dynamic_check_sees_the_replayed_targets(monkeypatch):
    import jax

    from repro.core.dynamic_runtime import DynamicExperimentRuntime

    seen, replayed = [], []
    monkeypatch.setitem(sys.modules, "bench.datasets.targeted", _targeted_dataset(seen))
    run_slice = DynamicExperimentRuntime.run_slice

    def recording(self, i, ops, *a, **kw):
        replayed.append(np.array(ops.ends, copy=True))
        return run_slice(self, i, ops, *a, **kw)

    monkeypatch.setattr(DynamicExperimentRuntime, "run_slice", recording)
    _, config, mix, _ = harness.load_cell(REPAIRING[0])
    config = {**config, **twitter.TINY, "dataset": "targeted"}
    d = drivers.make(config, mix, 2**33 + 11, harness.Spans(), jax.devices(), 0.3)
    d.setup()
    d.window(0.3)
    d.release()
    checks = d.check()

    assert replayed and (replayed[0] >= 0).all()
    assert all(np.array_equal(r, replayed[0]) for r in replayed)
    assert len(seen) == d.slices
    for ends in seen:
        assert ends is not None
        np.testing.assert_array_equal(ends, replayed[0])
    assert all(c["ok"] for c in checks.values()), checks
