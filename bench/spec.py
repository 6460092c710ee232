"""Find a cell's parts by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, cell or metric
is a file of its own under the benchmark's directory, so that a new one is
a new file plus a new entry, and no existing file changes:

- ``configs/<config>.json``: the deployment (the entry's ``file``);
- ``generators/<generator>.py``: makes a configuration's matrices;
- ``traffic/<traffic>.json``: the mix, read by ``drivers/<driver>.py``;
- ``limits/<cell>.json``: the cell's correctness limits;
- ``end_to_end/<metric>.py`` and ``layers/<metric>.py``: one reader per
  metric, ``read(record) -> float | None``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent


class Spec:
    """``BENCHMARK.json`` at ``root``, with the benchmark's files under
    ``root/<bench_dir name>``."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.bench = json.loads((self.root / "BENCHMARK.json").read_text())
        self.dir = self.root / BENCH_DIR.name

    def cell(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def limits(self, cell: str) -> dict:
        return json.loads((self.dir / "limits" / f"{cell}.json").read_text())

    def metrics(self, cell: str, trace: bool) -> list:
        """The metric entries that ``cell`` reports: its end-to-end metrics
        untraced, its per-layer metrics traced."""
        group = self.bench["per_layer" if trace else "end_to_end"]
        return [m for m in group if cell in m.get("workloads", [cell])]

    def reader(self, metric: dict, trace: bool):
        kind = "layers" if trace else "end_to_end"
        return load(self.dir / kind / f"{metric['name']}.py").read

    def plugin(self, kind: str, name: str):
        return load(self.dir / kind / f"{name}.py")


def load(path: Path):
    """Import one benchmark file by its path (metric names hold dots)."""
    path = Path(path)
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    mod_name = "bench_plugin_" + "_".join(path.parts[-2:]).replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
