"""One benchmark run: set up a cell, measure a window, check, report.

The cell (``BENCHMARK.json`` ``workloads``) names a configuration file
``bench/configs/<config>.json`` and a traffic mix ``bench/mixes/<traffic>.json``;
the mix's ``driver`` names the general driver ``bench/drivers/<driver>.py``.
Per-layer metrics are the readers ``bench/metrics/<metric>.py``.

The last line of standard output is the JSON result; the numbers that
decide ``correct`` are also the last lines of standard error.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_trace"


class Refused(RuntimeError):
    """The run cannot measure what the cell asks for; no result is printed."""


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise Refused(f"unknown workload {workload!r}")


def load_cell(workload: str, root: Path = ROOT):
    """(cell entry, configuration dict, mix dict, benchmark dict)."""
    bench = load_benchmark(root)
    cell = find_cell(bench, workload)
    conf_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((root / conf_entry["file"]).read_text())
    mix = json.loads((root / "bench" / "mixes" / f"{cell['traffic']}.json").read_text())
    return cell, config, mix, bench


def metric_reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise Refused(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table["devices"][kind]


class Spans:
    """Host spans ``(name, start_s, end_s)`` on ``time.perf_counter``;
    mirrored into the profiler trace as ``bench:<name>`` when tracing."""

    def __init__(self, traced: bool = False):
        self.traced = traced
        self.records: List[tuple] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.traced:
            import jax

            ann = jax.profiler.TraceAnnotation(f"bench:{name}")
        t0 = time.perf_counter()
        with ann:
            yield
        self.records.append((name, t0, time.perf_counter()))

    def wrap(self, obj, attr: str, name: str) -> None:
        """Record a span around every call of ``obj.<attr>`` (an instance
        attribute shadows the method; behaviour is unchanged)."""
        fn = getattr(obj, attr)
        spans = self

        def spanned(*a, **kw):
            with spans(name):
                return fn(*a, **kw)

        setattr(obj, attr, spanned)


class CompileClock:
    """JAX compile time and persistent-cache events, from ``jax.monitoring``."""

    _CACHE_EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "lookups",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "writes",
    }

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.cache = dict.fromkeys(self._CACHE_EVENTS.values(), 0)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name: str, secs: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _on_event(self, name: str, **_) -> None:
        if name in self._CACHE_EVENTS:
            self.cache[self._CACHE_EVENTS[name]] += 1


def require_chips(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise Refused(f"{chips} chips asked for, {len(devices)} found")
    return devices[:chips]


def enable_cache() -> None:
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    when set, else ``.jax_cache`` inside the checkout), holding every
    program however small or quick to compile, so that only a cell's first
    run compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def per_layer(bench: dict, cell: dict, run) -> Dict[str, dict]:
    """Values of the per-layer metrics that this cell reports."""
    e2e_here = {m["name"] for m in bench["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])}
    out = {}
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if cells is None and m["moves"] not in e2e_here:
            continue
        if cells is not None and cell["name"] not in cells:
            continue
        value = metric_reader(m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class RunRecord:
    """What a metric reader sees: the driver's samples, the reduced trace
    and the chip's peaks."""

    def __init__(self, samples: dict, trace, peak: dict):
        self.samples, self.trace, self.peaks = samples, trace, peak


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, devices: Optional[list] = None, out=print, err=None,
        config_overrides: Optional[dict] = None, control: bool = False) -> dict:
    """Set up, measure, check; returns the result dict (also printed).

    ``config_overrides`` replaces configuration values (the tests shrink
    the graph with it); ``devices`` skips the look for a chip; ``control``
    puts the control in the program's place before the check, which must
    then fail (``bench/tools/control.py``; the benchmark's runs never set it).
    """
    from bench import drivers

    err = err or (lambda s: print(s, file=sys.stderr, flush=True))
    cell, config, mix, bench = load_cell(workload)
    config = {**config, **(config_overrides or {})}
    if devices is None:
        devices = require_chips(int(cell["chips"]))
    clock = CompileClock()
    spans = Spans(traced=trace)
    driver = drivers.make(config, mix, seed, spans, devices, seconds)
    driver.setup()
    setup_s = time.perf_counter() - t_start
    err(json.dumps({"setup_s": setup_s, "compile_s": clock.compile_s,
                    "compiles": clock.compiles, "cache": dict(clock.cache)}))

    compiles0 = clock.compiles
    log_dir = TRACE_DIR / f"{workload}-{seed}"
    if trace:
        import jax

        shutil.rmtree(log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        with spans("window"):
            e2e = driver.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    window_compiles = clock.compiles - compiles0
    stats = devices[0].memory_stats() or {}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                                     for d in devices)),
    }
    reduced = None
    if trace:
        from bench import trace as trace_mod

        reduced = trace_mod.reduce_file(trace_mod.find_xplane(str(log_dir)))
        shutil.rmtree(log_dir, ignore_errors=True)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        err(json.dumps({"modules": trace_mod.top(reduced.modules, 20),
                        "module_calls": reduced.module_calls}))
    err(json.dumps({"window_compiles": window_compiles, "attempted": driver.attempted,
                    "window_s": driver.samples.get("window_s"),
                    "memory": {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}}))

    if trace:
        record = RunRecord(driver.samples, reduced, peaks(devices[0].device_kind))
        metrics = per_layer(bench, cell, record)
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for m in bench["end_to_end"]:
            if m["name"] in e2e and cell["name"] in m.get("workloads", [cell["name"]]):
                metrics[m["name"]] = {"value": float(e2e[m["name"]]), "unit": m["unit"]}

    driver.release()
    gc.collect()
    if control:
        driver.plant_control()
    t_check = time.perf_counter()
    checks = driver.check()
    err(json.dumps({"check_s": time.perf_counter() - t_check}))
    correct = all(c["ok"] for c in checks.values()) and driver.failed == 0
    for name, c in checks.items():
        err(f"check {name}: {c['value']} limit {c['limit']} {'ok' if c['ok'] else 'FAILED'}")
    result = {
        "correct": bool(correct),
        "attempted": int(driver.attempted),
        "failed": int(driver.failed),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        result["breakdown"] = {
            "device_ops": trace_mod.top(reduced.ops),
            "idle_gaps": trace_mod.top(reduced.idle_by_span),
        }
    result["checks"] = {n: {"value": c["value"], "limit": c["limit"]} for n, c in checks.items()}
    out(json.dumps(result))
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell, _, _, _ = load_cell(args.workload)
        devices = require_chips(int(cell["chips"]))
        enable_cache()
        run(args.workload, args.seed, args.seconds, bool(args.trace), t_start, devices)
    except Refused as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 2
    return 0
