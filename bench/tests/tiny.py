"""A copy of the benchmark with small cells that a CPU test run can hold.

``make_root(tmp)`` copies ``BENCHMARK.json`` and the benchmark's directory
into ``tmp`` and adds, as new files and entries only, two small
configurations and three cells that use the real generators, drivers,
traffic keys and readers.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

CONFIGS = {
    "ic0_tiny": {"matrix": {"generator": "ic0_poisson3d", "nx": 6},
                 "dtype": "float32",
                 "plan": {"strategy": "growlocal", "k": 8, "slack": 8,
                          "backend": "scan"}},
    "er_tiny": {"matrix": {"generator": "erdos_renyi_lower", "n": 3000,
                           "p": 2e-3, "pattern_seed": 0},
                "dtype": "float32",
                "plan": {"strategy": "auto", "backend": "scan"}},
}
TRAFFIC = {"serve_tiny": {"driver": "open_loop", "rate_hz": 100, "pool": 16,
                          "warm_requests": 4, "sample": 16, "service": {},
                          "trace_after_s": 0.2}}
CELLS = {
    "ic0_tiny.precond": ("ic0_tiny", "precond"),
    "er_tiny.block16": ("er_tiny", "block16"),
    "er_tiny.serve": ("er_tiny", "serve_tiny"),
}
LIMITS = {"rel_err": 1e-4, "min_compared": 1}


def copy_root(tmp: Path) -> Path:
    """``BENCHMARK.json`` and the benchmark's directory, copied to ``tmp``."""
    tmp = Path(tmp)
    shutil.copy(REPO / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp


def make_root(tmp: Path) -> Path:
    return add_tiny(copy_root(tmp))


def add_tiny(tmp: Path) -> Path:
    """Add the small configurations and cells: new files, new entries."""
    bench = json.loads((tmp / "BENCHMARK.json").read_text())
    for name, cfg in CONFIGS.items():
        path = f"bench/configs/{name}.json"
        (tmp / path).write_text(json.dumps(cfg))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": path, "reduced": [], "why": "test"})
    for name, mix in TRAFFIC.items():
        (tmp / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    for cell, (config, traffic) in CELLS.items():
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "test"})
        (tmp / "bench" / "limits" / f"{cell}.json").write_text(
            json.dumps(LIMITS))
        for group in ("end_to_end", "per_layer"):
            for m in bench[group]:
                if "workloads" in m and any(
                        w.split(".", 1)[1] == cell.split(".", 1)[1]
                        for w in m["workloads"]):
                    m["workloads"].append(cell)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return tmp
