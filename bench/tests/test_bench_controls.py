"""The comparisons that decide ``correct`` fail what they must fail.

* The control, at a size a test run holds, put in the program's place
  before the harness's own check: the reference one precision step down
  (DiDiC repair and route distances in bfloat16) or, for the integer 2-hop
  counters, with a stated guarantee broken (each step's potentially-global
  action booked at its sender, not its receiver). ``bench/tools/control.py``
  makes the same runs on the chip at the cells' own sizes.
* The faults: a whole run, with the look for a chip skipped and the timed
  path broken underneath, must come out not correct.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from bench import harness
from bench.tests.test_bench_cells import CELLS, tiny


def _run(cell_name: str, seed: int, control: bool = False) -> dict:
    import jax

    lines, errs = [], []
    result = harness.run(cell_name, seed, 0.3, False, time.perf_counter(), devices=jax.devices(),
                         out=lines.append, err=errs.append, config_overrides=tiny(cell_name),
                         control=control)
    assert any("FAILED" in e for e in errs) == (not result["correct"])
    return result


@pytest.mark.parametrize("cell_name", CELLS)
def test_control_fails_the_check(cell_name):
    """The control, put in the program's place, fails the harness's own
    comparison; the repair's control fails the repair's number."""
    sound = _run(cell_name, 17)
    assert sound["correct"] is True, sound["checks"]
    result = _run(cell_name, 17, control=True)
    assert result["correct"] is False
    failed = {n for n, c in result["checks"].items() if c["value"] > c["limit"]}
    if "repair_mismatch_share" in result["checks"]:
        assert "repair_mismatch_share" in failed, result["checks"]
    else:
        assert failed == {"counter_mismatches"}


# -- faults in the timed path -------------------------------------------------

def _stale(monkeypatch):
    """Each replay returns what the previous one returned."""
    from repro.core.framework import PartitionedGraphService

    orig, last = PartitionedGraphService.run_ops, {}

    def stale(self, ops, *a, **kw):
        res = orig(self, ops, *a, **kw)
        prev = last.get(ops.n_ops)
        last[ops.n_ops] = res
        return res if prev is None else prev

    monkeypatch.setattr(PartitionedGraphService, "run_ops", stale)


def _unrepaired(monkeypatch):
    """The DiDiC repair hands back the map it was given."""
    from repro.core.framework import RuntimePartitioner

    monkeypatch.setattr(RuntimePartitioner, "maintain",
                        lambda self, graph, parts, iterations=1, pinned=None: parts)


def _half_batch(monkeypatch):
    """The first half of every log or batch is left out and reads zero
    (a partial serving batch holds its live requests first)."""
    from repro.core.framework import PartitionedGraphService
    from repro.core.traffic import OpLog, TrafficResult

    orig = PartitionedGraphService.run_ops

    def half(self, ops, *a, **kw):
        n = ops.n_ops // 2
        res = orig(self, OpLog(ops.pattern, ops.starts[n:], ops.ends[n:], ops.t_l, ops.t_pg),
                   *a, **kw)
        pad = np.zeros(n, dtype=np.int64)
        return TrafficResult(np.concatenate([pad, res.per_op_total]),
                             np.concatenate([pad, res.per_op_global]),
                             res.per_partition, res.per_vertex)

    monkeypatch.setattr(PartitionedGraphService, "run_ops", half)


def _altered(monkeypatch):
    """One op's global traffic is off by one where the counters are made."""
    from repro.core.traffic_batched import BatchedTrafficEngine

    orig = BatchedTrafficEngine.finalize

    def altered(self, *a, **kw):
        res = orig(self, *a, **kw)
        res.per_op_global = res.per_op_global.copy()
        res.per_op_global[0] += 1
        return res

    monkeypatch.setattr(BatchedTrafficEngine, "finalize", altered)


FAULTS = {"state_unchanged": _stale, "half_batch": _half_batch, "answer_altered": _altered}
CASES = [(c, f) for c in CELLS for f in FAULTS]
REPAIRING = [c for c in CELLS if harness.load_cell(c)[2]["driver"] == "dynamic"]
CASES += [(c, "repair_skipped") for c in REPAIRING]


@pytest.mark.parametrize("cell_name,fault", CASES)
def test_fault_in_the_timed_path_is_not_correct(cell_name, fault, monkeypatch):
    import jax

    (_unrepaired if fault == "repair_skipped" else FAULTS[fault])(monkeypatch)
    lines, errs = [], []
    result = harness.run(cell_name, 29, 0.3, False, time.perf_counter(), devices=jax.devices(),
                         out=lines.append, err=errs.append, config_overrides=tiny(cell_name))
    assert result["correct"] is False, (fault, result["checks"])
    assert any("FAILED" in e for e in errs)
