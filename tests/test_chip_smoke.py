"""``chip_smoke.py`` at its rehearsal size on the CPU.

Each run is a subprocess held to the CPU (``JAX_PLATFORMS=cpu``), so it
touches no TPU. Every phase must pass, and the run must still fail as a
chip run: the last line says ``ok`` false with platform ``cpu`` and the
exit code is non-zero."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def _smoke(*args, cache_dir, devices=1):
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(cache_dir),
    )
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    out = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), "--size", "rehearsal",
         *args],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT,
    )
    lines = [json.loads(s) for s in out.stdout.splitlines() if s.startswith("{")]
    return out, lines


@pytest.mark.parametrize(
    "args,devices,phases",
    [
        ((), 1, ["device", "solve", "pcg", "serve", "pallas"]),
        (("--chips", "4"), 4, ["device", "sharded"]),
    ],
    ids=["one-chip", "four-chip"],
)
def test_rehearsal_passes_phases_but_not_as_chip_run(
    args, devices, phases, tmp_path
):
    out, lines = _smoke(*args, cache_dir=tmp_path, devices=devices)
    detail = f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-4000:]}"
    assert [ln.get("phase") for ln in lines[:-1]] == phases, detail
    assert all(ln["pass"] for ln in lines[:-1]), detail
    assert lines[-1] == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": devices},
    }, detail
    assert out.returncode != 0


def test_default_size_stops_after_device_phase_off_tpu(tmp_path):
    """Without the rehearsal option a non-TPU platform fails at once:
    no full-size phase runs on the CPU."""
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
    )
    out = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT,
    )
    lines = [json.loads(s) for s in out.stdout.splitlines()]
    assert [ln.get("phase") for ln in lines] == ["device", None]
    assert lines[0]["pass"] is False
    assert lines[-1]["ok"] is False
    assert out.returncode != 0


def test_smoke_alone_fails_without_the_program(tmp_path):
    """Copied into a directory with nothing else of the repo, the script
    cannot import the program: it exits non-zero and prints no result."""
    (tmp_path / "chip_smoke.py").write_text(
        (ROOT / "chip_smoke.py").read_text()
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, str(tmp_path / "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=tmp_path,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
