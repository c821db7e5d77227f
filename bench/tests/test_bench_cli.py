"""The command refuses to measure without a chip, and ``BENCHMARK.json``
keeps to the benchmark's contract."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _run(cwd: Path, workload: str = "gis_k4.replay"):
    env = {k: v for k, v in os.environ.items() if k not in ("PYTHONPATH",)}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", workload, "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("workload", [c["name"] for c in BENCH["workloads"]])
def test_command_refuses_without_tpu(workload):
    out = _run(ROOT, workload)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""


def test_benchmark_names_files_and_metrics():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    for item in BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(item["name"]), item["name"]
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names
    cells = {c["name"]: c for c in BENCH["workloads"]}
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        for key in c["reduced"]:
            assert key in conf and key in conf["paper"], key
        assert (ROOT / "bench" / "datasets" / f"{conf['dataset']}.py").is_file()
        assert (ROOT / "bench" / "partitioners" / f"{conf['partitioner']}.py").is_file()
    for cell in cells.values():
        assert cell["config"] in configs
        mix = json.loads((ROOT / "bench" / "mixes" / f"{cell['traffic']}.json").read_text())
        assert (ROOT / "bench" / "drivers" / f"{mix['driver']}.py").is_file()
        e2e = [m for m in BENCH["end_to_end"] if cell["name"] in m.get("workloads", cells)]
        assert {m["name"] for m in e2e} - {"setup_s"}, cell["name"]
        assert any(cell["name"] in m["workloads"] for m in BENCH["per_layer"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", [])) <= set(cells)
    for m in BENCH["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        for w in m["workloads"]:
            moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
            assert w in moved.get("workloads", cells)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
