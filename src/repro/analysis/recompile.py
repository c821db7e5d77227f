"""Recompile sentinel: enforce zero closure retraces across growth slices.

The delta-overlay store (:class:`repro.graphs.structure.GraphStore`)
capacity-pads every growth-facing device layout, so a 20×5%
vertex-growth schedule compiles everything once during warm-up (the
``begin`` replay plus slice 0, where ``prepare_growth`` attaches the
store and traces the capacity-shaped programs) and then runs
**steady-state: zero XLA compilations from slice 1 on**. This sentinel
is the empirical gate for that invariant: it drives a real (tiny)
growth schedule through
:class:`~repro.core.dynamic_runtime.DynamicExperimentRuntime`
on a 1-shard replay mesh with ``jax_log_compiles`` enabled, records
every XLA compilation (closure name + abstract argument shapes, as
logged by jax's pjit path), and classifies each recompilation observed
after the warm-up slices:

* ``shape-change`` — same closure name, different abstract shapes: the
  traced program legitimately depends on a dimension that grew (e.g.
  the module-level dynamism scans retrace because the packed unit block
  ``[T/U, R, U]`` and padded ``N`` grow each slice). Fix = pad to a
  stable capacity (the delta overlay).
* ``identity-rehash`` — same closure name, *same* shapes recompiled:
  the jit cache keys on function identity, and the engine rebuilt the
  closure object for the grown graph (``get_replayer`` caches per
  graph), so a bit-identical program is re-traced from scratch. Fix =
  hoist the closure out of the per-graph rebuild.
* ``new-closure`` — a closure name first compiled after warm-up
  (lazily-built engine paths).

The sentinel is empirical, not simulated: it reports what the XLA
dispatch layer actually compiled, so its findings (rule
``recompile/growth-retrace``) are exactly the retraces a production
schedule would pay for. Since the overlay landed these findings are
**lint failures, not baseline notes** — ``baseline.json`` carries no
growth-retrace entries, so any post-warm-up retrace fails ``make
lint`` and must be fixed at the source (usually a closure keyed on
graph identity instead of the store, or a shape that tracks the live
extent instead of the capacity). The report (per-slice compile counts,
wall time, and per-closure causes) stays embedded in the JSON lint
report so steady-state is continuously re-measured, not assumed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import re
import time
from typing import Dict, Iterator, List, Optional, Tuple

from repro.analysis.framework import Finding

# Depending on its version, JAX prints the argument types as a list or a tuple.
_COMPILE_RE = re.compile(
    r"Compiling ([^\s]+) with global shapes and types (\[.*\]|\(.*\))\."
    r"\s*Argument mapping"
)
#: jax loggers that announce compilations when ``jax_log_compiles`` is on.
_COMPILE_LOGGERS = ("jax._src.interpreters.pxla", "jax._src.dispatch")


@dataclasses.dataclass
class CompileEvent:
    slice_label: str
    name: str
    shapes: str


@dataclasses.dataclass
class Retrace:
    closure: str
    cause: str          # shape-change | identity-rehash | new-closure
    count: int
    slices: List[str]
    detail: str

    def to_json(self) -> Dict:
        return dataclasses.asdict(self)


class _CompileCapture(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.events: List[CompileEvent] = []
        self.slice_label = "warmup"

    def emit(self, record: logging.LogRecord) -> None:
        m = _COMPILE_RE.search(record.getMessage())
        if m:
            self.events.append(
                CompileEvent(self.slice_label, m.group(1), m.group(2))
            )


@contextlib.contextmanager
def capture_compiles() -> Iterator[_CompileCapture]:
    """Enable ``jax_log_compiles`` and record every compilation event."""
    import jax

    handler = _CompileCapture()
    loggers = [logging.getLogger(name) for name in _COMPILE_LOGGERS]
    prior = [(lg.level, lg.propagate) for lg in loggers]
    jax.config.update("jax_log_compiles", True)
    for lg in loggers:
        lg.addHandler(handler)
        lg.propagate = False  # capture, don't spew to the console
        if lg.level > logging.WARNING or lg.level == logging.NOTSET:
            lg.setLevel(logging.WARNING)
    try:
        yield handler
    finally:
        jax.config.update("jax_log_compiles", False)
        for lg, (level, propagate) in zip(loggers, prior):
            lg.removeHandler(handler)
            lg.setLevel(level)
            lg.propagate = propagate


def classify(events: List[CompileEvent],
             warmup_labels: Tuple[str, ...] = ("warmup", "slice0")) -> List[Retrace]:
    """Classify every compilation after the warm-up slices (see module doc)."""
    history: Dict[str, List[CompileEvent]] = {}
    out: Dict[Tuple[str, str], Retrace] = {}
    for ev in events:
        prior = history.setdefault(ev.name, [])
        if ev.slice_label not in warmup_labels:
            if not prior:
                cause, detail = "new-closure", (
                    f"first compiled at {ev.slice_label}"
                )
            elif any(p.shapes == ev.shapes for p in prior):
                cause, detail = "identity-rehash", (
                    "recompiled with identical abstract shapes — the closure "
                    "object was rebuilt for the grown graph, so the jit cache "
                    "(keyed on function identity) missed"
                )
            else:
                cause, detail = "shape-change", (
                    f"{prior[-1].shapes} -> {ev.shapes}"
                )
            key = (ev.name, cause)
            r = out.get(key)
            if r is None:
                out[key] = Retrace(ev.name, cause, 1, [ev.slice_label], detail)
            else:
                r.count += 1
                if ev.slice_label not in r.slices:
                    r.slices.append(ev.slice_label)
        prior.append(ev)
    return sorted(out.values(), key=lambda r: (-r.count, r.closure, r.cause))


def _closure_path(root, name: str) -> str:
    """Best-effort source location of a compiled closure by def-name grep."""
    if name == "<lambda>":
        return "src/repro/core/traffic_sharded.py"
    pattern = re.compile(rf"def {re.escape(name.split('(')[0])}\b")
    for rel in ("src/repro/core", "src/repro/distributed", "src/repro/launch"):
        base = root / rel
        if not base.is_dir():
            continue
        for path in sorted(base.rglob("*.py")):
            if pattern.search(path.read_text()):
                return path.relative_to(root).as_posix()
    return "(jax internal)"


def run_growth_sentinel(
    slices: int = 20,
    amount: float = 0.05,
    insert_rate: float = 0.5,
    scale: float = 0.002,
    n_ops: int = 48,
    k: int = 4,
    maintain_every: int = 6,
    seed: int = 0,
    root=None,
) -> Dict:
    """Drive a growth schedule and report every post-warm-up recompile.

    Returns a JSON-ready report; ``findings_from_report`` turns the
    retraces into lint findings.
    """
    from repro.core import partitioners
    from repro.core.didic import DidicConfig
    from repro.core.dynamic_runtime import DynamicExperimentRuntime
    from repro.core.framework import PartitionedGraphService
    from repro.core.traffic import generate_ops
    from repro.graphs import datasets
    from repro.launch.mesh import make_replay_mesh

    graph = datasets.load("filesystem", scale=scale, seed=1)
    svc = PartitionedGraphService(
        graph, k, didic=DidicConfig(k=k, iterations=4),
        mesh=make_replay_mesh(), maintenance="shared",
    )
    svc.partition_with(partitioners.random_partition(graph.n_nodes, k, seed=0))
    ops = generate_ops(graph, n_ops=n_ops, seed=3)
    rt = DynamicExperimentRuntime(svc, insert_method="fewest_vertices",
                                  seed=seed)

    per_slice: List[Dict] = []
    with capture_compiles() as cap:
        cap.slice_label = "warmup"
        t0 = time.perf_counter()
        rt.begin(ops)
        warmup_s = time.perf_counter() - t0
        for i in range(slices):
            cap.slice_label = f"slice{i}"
            n_before = len(cap.events)
            t0 = time.perf_counter()
            rt.run_slice(i, ops, amount, maintain_every=maintain_every,
                         insert_rate=insert_rate)
            per_slice.append({
                "slice": i,
                "compiles": len(cap.events) - n_before,
                "seconds": round(time.perf_counter() - t0, 3),
                "n_nodes": int(rt.service.graph.n_nodes),
            })

    retraces = classify(cap.events)
    steady = per_slice[-1]["compiles"] == 0 if per_slice else True
    return {
        "schedule": {
            "slices": slices, "amount": amount, "insert_rate": insert_rate,
            "scale": scale, "n_ops": n_ops, "k": k,
            "maintain_every": maintain_every,
        },
        "warmup_seconds": round(warmup_s, 3),
        "per_slice": per_slice,
        "total_compiles_after_warmup": sum(s["compiles"] for s in per_slice[1:]),
        "steady_state": steady,
        "retraces": [r.to_json() for r in retraces],
    }


def findings_from_report(report: Dict, root) -> List[Finding]:
    """``recompile/growth-retrace`` findings, one per repo closure.

    Keys must stay stable across schedule tweaks so the baseline does not
    churn: the snippet carries only the closure name, causes/counts live
    in the message (and the full per-slice data in the JSON report). All
    jax-internal helper closures (elementwise primitives re-dispatched at
    new shapes) collapse into a single aggregate finding.
    """
    by_closure: Dict[Tuple[str, str], List[Dict]] = {}
    internal: List[Dict] = []
    for r in report["retraces"]:
        path = _closure_path(root, r["closure"])
        if path == "(jax internal)":
            internal.append(r)
        else:
            by_closure.setdefault((path, r["closure"]), []).append(r)

    findings = []
    for (path, closure), rs in sorted(by_closure.items()):
        causes = "; ".join(
            f"{r['cause']} {r['count']}x across {len(r['slices'])} slices "
            f"({r['detail']})" for r in rs
        )
        findings.append(Finding(
            rule="recompile/growth-retrace",
            path=path,
            line=0,
            message=f"{closure} retraces on growth: {causes}",
            snippet=f"{closure} retraces on growth",
        ))
    if internal:
        names = sorted({r["closure"] for r in internal})
        total = sum(r["count"] for r in internal)
        findings.append(Finding(
            rule="recompile/growth-retrace",
            path="(jax internal)",
            line=0,
            message=(
                f"jax-internal helper closures retrace on growth "
                f"({total}x): {', '.join(names)} — re-dispatched at the "
                f"grown shapes; disappears with the repo closures once "
                f"shapes are capacity-padded"
            ),
            snippet="jax-internal helper closures retrace on growth",
        ))
    return findings
