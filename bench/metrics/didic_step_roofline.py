"""DiDiC (``core/didic_distributed.py``): the repair iteration's share of
the chip's memory roofline.

Bytes are those the iteration's sparse products need at the least, from
the graph's shapes and the DiDiC configuration: each of the
ψ·(ρ+1) + smoothing SpMMs over E symmetrized edges and N vertices with k
load systems reads per edge two int32 indices, one float32 coefficient and
the k float32 loads of the neighbour, and per vertex reads and writes k
float32 loads:

    bytes = SpMMs × (E·(12 + 4k) + N·8k)

The time is the device time of the whole ``step`` program (see
``didic_step_ms.dynamic``), which also holds the element-wise updates and
the balance fit, so the share is a lower bound. The step is bound by
memory (about 0.25 FLOP per byte), so the bound is bytes over the HBM
bandwidth in ``bench/peaks.json``. Moves ``slice_s``.
"""

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_metric_didic_step_ms", Path(__file__).with_name("didic_step_ms.dynamic.py"))
_step = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_step)


def bytes_per_iteration(samples) -> float:
    k, e, n = samples["k"], samples["sym_edges"], samples["n_nodes"]
    return samples["spmm_per_iteration"] * (e * (12 + 4 * k) + n * 8 * k)


def read(run):
    secs = _step.step_seconds(run)
    if secs is None:
        return None
    least = bytes_per_iteration(run.samples) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / secs
