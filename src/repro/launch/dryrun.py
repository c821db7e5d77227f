import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware:
``jax.jit(step, in_shardings=…).lower(*ShapeDtypeStructs).compile()`` must
succeed on the single-pod (16×16) and multi-pod (2×16×16) production
meshes for every runnable cell. Per cell we record:

* ``memory_analysis``  — per-device argument/output/temp/peak bytes,
* ``cost_analysis``    — HLO FLOPs + bytes accessed,
* collective bytes     — parsed from the post-SPMD optimized HLO
  (all-gather / all-reduce / reduce-scatter / all-to-all /
  collective-permute output sizes),

appended to ``results/dryrun.jsonl`` for the roofline stage.

Usage:
  python -m repro.launch.dryrun --arch yi-34b --shape train_4k --mesh both
  python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""

import argparse
import json
import re
import time
import traceback

import jax

from repro.launch.mesh import make_production_mesh

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """'bf16[8,128]' → bytes; tuples handled by summing members."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Sum output sizes of collective ops in optimized HLO, by op kind."""
    stats = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for kind in _COLLECTIVES:
            # match "= <shape> kind(" — the op use, not operand mentions
            m = re.search(r"=\s+((?:\([^)]*\))|(?:\S+))\s+" + kind + r"(-start|-done)?\(", stripped)
            if m:
                if m.group(2) == "-done":
                    continue  # counted at -start
                stats[kind]["count"] += 1
                stats[kind]["bytes"] += _shape_bytes(m.group(1))
                break
    stats["total_bytes"] = sum(v["bytes"] for k, v in stats.items() if isinstance(v, dict))
    stats["total_count"] = sum(v["count"] for k, v in stats.items() if isinstance(v, dict))
    return stats


def _compile_spec(spec, mesh):
    from repro.distributed.sharding import to_shardings

    in_shardings = to_shardings(mesh, spec.in_specs)
    with jax.sharding.set_mesh(mesh):
        lowered = jax.jit(spec.step_fn, in_shardings=in_shardings).lower(*spec.abstract_args)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        coll = collective_stats(compiled.as_text())
    return mem, cost, coll


def _probe_correct(cfg, shape, mesh, cost, coll) -> dict:
    """Layer-count probe: XLA cost analysis counts a scan body once, so
    compile the same cell with 1 and 2 layers and reconstruct
    f(L) = f(1) + (L−1)·(f(2) − f(1)) for FLOPs and collective bytes."""
    L = cfg.probe_layers
    _, c1, k1 = _compile_spec(cfg.probe(shape, mesh, 1), mesh)
    _, c2, k2 = _compile_spec(cfg.probe(shape, mesh, 2), mesh)

    def extrap(f1: float, f2: float) -> float:
        return f1 + (L - 1) * (f2 - f1)

    return {
        "method": "scan-probe f(1)+(L-1)(f(2)-f(1))",
        "flops": extrap(c1.get("flops", 0.0), c2.get("flops", 0.0)),
        "bytes_accessed": extrap(c1.get("bytes accessed", 0.0), c2.get("bytes accessed", 0.0)),
        "collective_bytes": extrap(k1["total_bytes"], k2["total_bytes"]),
        "collective_count": extrap(k1["total_count"], k2["total_count"]),
        "scanned_flops": cost.get("flops", 0.0),
        "scanned_collective_bytes": coll["total_bytes"],
    }


def run_cell(arch: str, shape: str, multi_pod: bool, verbose: bool = True) -> dict:
    from repro.configs import get

    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get(arch)
    t0 = time.perf_counter()
    spec = cfg.dryrun(shape, mesh)
    t_lower = time.perf_counter() - t0
    mem, cost, coll = _compile_spec(spec, mesh)
    t_compile = time.perf_counter() - t0 - t_lower
    corrected = None
    if cfg.probe is not None:
        corrected = _probe_correct(cfg, shape, mesh, cost, coll)
    n_dev = mesh.devices.size
    result = {
        "arch": arch,
        "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(n_dev),
        "kind": spec.kind,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "peak_bytes": int(
                getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
            ),
        },
        "cost": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "transcendentals": float(cost.get("transcendentals", 0.0)),
        },
        "collectives": coll,
        "corrected": corrected,
        "meta": cfg.meta,
    }
    if verbose:
        flops_show = corrected["flops"] if corrected else result["cost"]["flops"]
        coll_show = corrected["collective_bytes"] if corrected else coll["total_bytes"]
        print(
            f"[dryrun] {arch} × {shape} × {result['mesh']}: OK "
            f"(lower {t_lower:.0f}s, compile {t_compile:.0f}s, "
            f"flops {flops_show:.3e}{' (probe-corrected)' if corrected else ''}, "
            f"coll {coll_show / 1e9:.2f} GB, "
            f"temp/dev {result['memory']['temp_bytes'] / n_dev / 1e9:.2f} GB)"
        )
        print("  memory_analysis:", result["memory"])
        print("  cost_analysis:", result["cost"])
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", type=str, default="results/dryrun.jsonl")
    ap.add_argument("--skip-done", action="store_true",
                    help="skip cells already present in --out")
    args = ap.parse_args()

    from repro.configs import all_cells, skipped_cells

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    if args.all:
        cells = all_cells()
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells = [(args.arch, args.shape)]

    done = set()
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    if args.skip_done and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("status") == "ok":
                        done.add((r["arch"], r["shape"], r["mesh"]))
                except json.JSONDecodeError:
                    pass

    for skip in skipped_cells():
        print(f"[dryrun] SKIP {skip[0]} × {skip[1]}: {skip[2]}")

    failures = []
    with open(args.out, "a") as f:
        for arch, shape in cells:
            for multi in meshes:
                mesh_name = "2x16x16" if multi else "16x16"
                if (arch, shape, mesh_name) in done:
                    print(f"[dryrun] {arch} × {shape} × {mesh_name}: already done")
                    continue
                try:
                    r = run_cell(arch, shape, multi)
                    r["status"] = "ok"
                except Exception as e:  # noqa: BLE001 — report and continue
                    traceback.print_exc()
                    r = {
                        "arch": arch, "shape": shape, "mesh": mesh_name,
                        "status": "fail", "error": f"{type(e).__name__}: {e}",
                    }
                    failures.append((arch, shape, mesh_name))
                f.write(json.dumps(r) + "\n")
                f.flush()
    if failures:
        print(f"[dryrun] FAILURES: {failures}")
        raise SystemExit(1)
    print("[dryrun] all requested cells compiled successfully")


if __name__ == "__main__":
    main()
