"""The HPCG cell's parts on a small block: its generator against the
program's, the reference's two solves against ``ComputeSYMGS_ref``'s sweep,
the bfloat16 control against the cell's limit, and the readers of the
plans' statistics."""
import json

import numpy as np
import pytest

from bench import program_spans, reference, roofline, run
from bench.generators import hpcg27
from bench.spec import load
from bench.tests.tiny import REPO, copy_root

SHAPE = (5, 6, 7)  # unequal sides catch axis-order mistakes
LIMITS = json.loads((REPO / "bench" / "limits" / "hpcg_104.symgs.json")
                    .read_text())


def _reader(name):
    return load(REPO / "bench" / "layers" / f"{name}.py").read


@pytest.fixture(autouse=True)
def _program_tracing_off():
    """Loading a reader switches the program's tracing on, as in a traced
    run; a hand-built record ends no run, so switch it off again."""
    from repro import obs

    yield
    program_spans._buffer = None
    obs.disable()


def test_generator_copy_equals_the_program_stencil():
    from repro.sparse import stencil27_matrix

    mine = hpcg27.stencil27(*SHAPE)
    theirs = stencil27_matrix(*SHAPE)
    assert np.array_equal(mine.indptr, theirs.indptr)
    assert np.array_equal(mine.indices, theirs.indices)
    assert np.array_equal(mine.data, theirs.data)


def _symgs_ref(a, r):
    """``ComputeSYMGS_ref`` from x = 0, written out: a forward loop over
    the rows, then a backward loop; each row subtracts every entry's
    product and adds its diagonal's back before dividing by it."""
    x = np.zeros(a.shape[0])
    diag = a.diagonal()
    n = a.shape[0]
    for i in list(range(n)) + list(range(n - 1, -1, -1)):
        s = r[i]
        for j in range(a.indptr[i], a.indptr[i + 1]):
            s -= a.data[j] * x[a.indices[j]]
        s += x[i] * diag[i]
        x[i] = s / diag[i]
    return x


def test_chain_of_the_two_operators_is_the_symgs_sweep():
    a = hpcg27.stencil27(*SHAPE)
    ops = hpcg27.operators({"nx": SHAPE[0], "ny": SHAPE[1],
                            "nz": SHAPE[2]}, [1, 7])
    assert [lower for _, lower in ops] == [True, False]
    refs = [reference.TriangularReference(m, lower) for m, lower in ops]
    for seed in range(3):
        r = np.random.default_rng(seed).standard_normal(a.shape[0])
        got = reference.chain_solve(refs, r)
        assert reference.rel_err(got, _symgs_ref(a, r)) < 1e-13


def test_bfloat16_control_reads_above_the_limit():
    ops = hpcg27.operators({"nx": SHAPE[0], "ny": SHAPE[1],
                            "nz": SHAPE[2]}, [1, 7])
    refs = [reference.TriangularReference(m, lower) for m, lower in ops]
    b = np.random.default_rng(2).standard_normal((ops[0][0].shape[0], 16))
    want = reference.chain_solve(refs, b)
    bf16 = reference.chain_solve(refs, b, "bfloat16")
    assert reference.rel_err(bf16, want) > LIMITS["rel_err"]


@pytest.mark.parametrize("name", ["lane_fill.plan", "steps.plan"])
def test_plan_readers_read_nothing_without_plans(name):
    read = _reader(name)
    assert read({}) is None
    assert read({"plans": []}) is None


@pytest.mark.parametrize("name, value", [
    ("lane_fill.plan", (1000 + 1000) / (20 * 64 + 18 * 64)),
    ("steps.plan", (20 + 18) / 2),
])
def test_plan_readers_on_a_synthetic_record(name, value):
    plans = [{"n_steps": t, "k": 64, "row_slot_utilization": 1000 / (t * 64)}
             for t in (20, 18)]
    assert _reader(name)({"plans": plans}) == pytest.approx(value)


def _tiny_hpcg_root(tmp_path):
    """The benchmark with a small HPCG cell beside the real one: the same
    generator, traffic and limits, a 5 x 6 x 7 block."""
    root = copy_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench" / "configs" / "hpcg27_104.json")
                     .read_text())
    cfg["matrix"].update(nx=SHAPE[0], ny=SHAPE[1], nz=SHAPE[2])
    cfg["plan"] = {"strategy": "hdagg", "k": 8, "backend": "scan"}
    (root / "bench" / "configs" / "hpcg27_tiny.json").write_text(
        json.dumps(cfg))
    bench["configs"].append({"name": "hpcg27_tiny", "source": "test",
                             "file": "bench/configs/hpcg27_tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "hpcg_tiny.symgs",
                               "config": "hpcg27_tiny", "traffic": "precond",
                               "chips": 1, "why": "test"})
    (root / "bench" / "limits" / "hpcg_tiny.symgs.json").write_text(
        json.dumps(LIMITS))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "hpcg_104.symgs" in m.get("workloads", []):
            m["workloads"].append("hpcg_tiny.symgs")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_small_hpcg_cell_runs_and_reads_its_plans(tmp_path, capsys,
                                                   monkeypatch):
    root = _tiny_hpcg_root(tmp_path)
    argv = ["--workload", "hpcg_tiny.symgs", "--seed", "3000000151",
            "--seconds", "0.5"]
    assert run.main(argv + ["--trace", "0"], root=root,
                    require_accelerator=False) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    assert res["checks"]["compared"]["value"] >= LIMITS["min_compared"]
    assert set(res["metrics"]) == {"setup_s", "solve_ms"}

    # traced, with the test's own peaks table standing in for a chip's
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"devices": {"cpu": {
        "hbm_bytes_per_s": 1e11, "flops_per_s": 1e12}}}))
    monkeypatch.setattr(roofline, "PEAKS", peaks)
    assert run.main(argv + ["--trace", "1"], root=root,
                    require_accelerator=False) == 0
    out, err = capsys.readouterr()
    res = json.loads(out.strip().splitlines()[-1])
    m = {k: v["value"] for k, v in res["metrics"].items()}
    plans = next(json.loads(line)["plans"] for line in err.splitlines()
                 if line.startswith('{"plans"'))
    assert len(plans) == 2
    n = SHAPE[0] * SHAPE[1] * SHAPE[2]
    steps = [p["n_steps"] for p in plans]
    assert m["steps.plan"] == pytest.approx(sum(steps) / 2)
    assert m["lane_fill.plan"] == pytest.approx(
        2 * n / sum(t * p["k"] for t, p in zip(steps, plans)))
    # the device-trace readers need a chip's trace; these read the host's
    assert {"schedule_s", "bind_s", "inspector_s", "compile_s"} <= set(m)
