"""Without a TPU, or without the program beside it, a run fails and
prints no result."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ic0_64.precond",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def _no_result(out):
    return not any(line.lstrip().startswith("{") for line in out.splitlines())


def test_fails_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = _run(REPO, env)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_fails_without_the_program(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark: the
    run passes the device check (skipped here) and fails on the import."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    code = ("import sys; sys.path.insert(0, '.'); from bench import run; "
            "sys.exit(run.main(['--workload', 'er_100k.block16', '--seed', "
            "'1', '--seconds', '1'], require_accelerator=False))")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode != 0
    assert "No module named 'repro'" in p.stderr
    assert _no_result(p.stdout)
