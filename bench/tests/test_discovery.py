"""A configuration, a traffic mix and a metric are found by name: new files
plus new entries in BENCHMARK.json, and no existing file changes."""
import hashlib
import json

import pytest

from bench import roofline, run
from bench.tests.tiny import add_tiny, copy_root

NEW_E2E = '''
def read(rec):
    return rec["window"]["calls"] / rec["window"]["wall_s"]
'''
NEW_LAYER = '''
def read(rec):
    tr = rec.get("trace")
    return tr["calls"] if tr else None
'''


def _hashes(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()}


def _last_json(out):
    return json.loads(out.strip().splitlines()[-1])


def test_new_files_and_entries_are_picked_up(tmp_path, capsys, monkeypatch):
    root = copy_root(tmp_path)
    before = _hashes(root)
    add_tiny(root)  # two configurations, a traffic mix, three cells
    (root / "bench" / "end_to_end" / "calls_per_s.py").write_text(NEW_E2E)
    (root / "bench" / "layers" / "traced_calls.py").write_text(NEW_LAYER)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["end_to_end"].append({"name": "calls_per_s", "unit": "1/s",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["ic0_tiny.precond"]})
    bench["per_layer"].append({"name": "traced_calls", "unit": "calls",
                               "better": "higher", "source": "host_clock",
                               "layer": "harness", "moves": "calls_per_s",
                               "workloads": ["ic0_tiny.precond"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    argv = ["--workload", "ic0_tiny.precond", "--seed", "3",
            "--seconds", "0.5"]
    assert run.main(argv + ["--trace", "0"], root=root,
                    require_accelerator=False) == 0
    res = _last_json(capsys.readouterr().out)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"setup_s", "solve_ms", "calls_per_s"}

    # a traced run on the CPU: the test's own peaks table stands in for a
    # chip's, so that the per-layer readers run; nothing here is a chip
    # number
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"devices": {"cpu": {
        "hbm_bytes_per_s": 1e11, "flops_per_s": 1e12}}}))
    monkeypatch.setattr(roofline, "PEAKS", peaks)
    assert run.main(argv + ["--trace", "1"], root=root,
                    require_accelerator=False) == 0
    res = _last_json(capsys.readouterr().out)
    assert res["metrics"]["traced_calls"]["value"] >= 1
    assert {"inspector_s", "compile_s"} <= set(res["metrics"])
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    after = _hashes(root)
    assert {k: after[k] for k in before} == before


@pytest.mark.parametrize("cell, names", [
    ("ic0_tiny.precond", {"setup_s", "solve_ms"}),
    ("er_tiny.serve", {"setup_s", "req_p95_ms"}),
])
def test_each_cell_reports_its_own_metrics(tmp_path, capsys, cell, names):
    """A metric with ``workloads`` is reported in those cells (and in the
    tiny cells of the same traffic), and in no other."""
    root = add_tiny(copy_root(tmp_path))
    argv = ["--workload", cell, "--seed", "5", "--seconds", "0.5",
            "--trace", "0"]
    assert run.main(argv, root=root, require_accelerator=False) == 0
    res = _last_json(capsys.readouterr().out)
    assert set(res["metrics"]) == names
    assert all(m["value"] > 0 for m in res["metrics"].values())


def test_traced_serve_run_reads_its_slice(tmp_path, capsys, monkeypatch):
    """The open loop traces from ``trace_after_s`` into the window, and
    ``queue_wait_ms.serve`` is the mean over every request it served."""
    root = add_tiny(copy_root(tmp_path))
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"devices": {"cpu": {
        "hbm_bytes_per_s": 1e11, "flops_per_s": 1e12}}}))
    monkeypatch.setattr(roofline, "PEAKS", peaks)
    argv = ["--workload", "er_tiny.serve", "--seed", "6", "--seconds", "0.6",
            "--trace", "1"]
    assert run.main(argv, root=root, require_accelerator=False) == 0
    out, err = capsys.readouterr()
    res = _last_json(out)
    assert res["correct"] is True
    assert {"queue_wait_ms.serve", "batch_fill.serve", "idle_pct.serve",
            "inspector_s", "compile_s"} <= set(res["metrics"])
    window = next(json.loads(line) for line in err.splitlines()
                  if line.startswith('{"plans"'))["window"]
    assert window["queue_waits"] == res["attempted"] - res["failed"] > 0
    assert 0 < res["device"]["window_s"] < 0.6
