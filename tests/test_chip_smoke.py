"""Rehearse ``chip_smoke.py`` on the CPU at a tiny scale.

The script's phases run here exactly as they run on the chip, on graphs
of ~1.5 k vertices instead of the paper's ~800 k. The script itself must
refuse to run, and must not report success, without a TPU.
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCALE = 0.002


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def smoke():
    return _load_smoke()


def _phases(lines):
    return [json.loads(s) for s in lines]


def test_one_chip_phases_rehearse_on_cpu(smoke):
    lines = []
    clock = smoke.PhaseClock(out=lines.append)
    smoke.one_chip(SCALE, clock)

    phases = _phases(lines)
    names = {p["phase"] for p in phases}
    assert {"load", "didic_initial", "replay_cold", "replay_resident",
            "oracle_check", "dynamism_maintain", "replay_after_maintain",
            "online", "offline_replay"} <= names
    checked = {p["pattern"] for p in phases if p["phase"] == "oracle_check"}
    assert checked == {"filesystem", "twitter", "gis_short", "gis_long"}
    relax = {p["pattern"]: p["relax"] for p in phases if "relax" in p}
    assert relax == {"gis_short": "xla", "gis_long": "xla"}
    online = next(p for p in phases if p["phase"] == "online")
    assert online["ticks"] >= 200
    assert all(p["wall_s"] >= p["compile_s"] >= 0 for p in phases)


def test_mesh_path_rehearses_on_four_host_devices():
    code = (
        "import sys, chip_smoke as s\n"
        "s.mesh_path(%r, 4, s.PhaseClock())\n"
        "print('MESH_OK')\n" % SCALE
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), str(ROOT / "src")])
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    lines = out.stdout.strip().splitlines()
    assert lines[-1] == "MESH_OK"
    phases = _phases(lines[:-1])
    for phase in ("sharded_replay", "sharded_resident_capture", "sharded_resident"):
        replayed = {p["pattern"] for p in phases if p["phase"] == phase}
        assert replayed == {"filesystem", "twitter", "gis_short", "gis_long"}
    modes = {p["maintenance"] for p in phases if p["phase"] == "didic_initial"}
    assert modes == {"sharded", "shared"}


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert smoke.main(["--chips", "4"]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    assert out == ""
