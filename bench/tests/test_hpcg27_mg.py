"""The V-cycle cell's parts on small blocks: its generator against the
program's hierarchy, its reference against ``ComputeMG_ref``'s loops, the
bfloat16 control against the cell's limit, the count of its least time,
and a small copy of the cell run end to end on the CPU."""
import json

import numpy as np
import pytest
import scipy.sparse as sp

from bench import mg_reference, mg_roofline, program_spans, reference
from bench import roofline, run, trace
from bench.generators import hpcg27_mg
from bench.spec import Spec, load
from bench.tests.tiny import REPO, copy_root

LIMITS = json.loads((REPO / "bench" / "limits" / "hpcg_104.vcycle.json")
                    .read_text())
# the tests' own row-by-row transcription of ComputeSYMGS_ref and
# ComputeMG_ref (hpcg 3.1)
LOOPS = load(REPO / "tests" / "_symgs_ref.py")
TINY = {"generator": "hpcg27_mg", "nx": 4, "ny": 8, "nz": 8, "levels": 3}


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


@pytest.mark.parametrize("shape, levels", [((8, 10, 12), 2),
                                           ((8, 8, 16), 4)])
def test_generator_levels_and_f2c_equal_the_programs(shape, levels):
    from repro.sparse import hpcg_f2c, mg_block_shapes, stencil27_matrix

    spec = dict(zip(("nx", "ny", "nz"), shape), levels=levels)
    mine = hpcg27_mg.levels(spec)
    blocks = mg_block_shapes(*shape, levels)
    assert len(mine) == len(blocks)
    for (a, f2c), blk in zip(mine, blocks):
        theirs = stencil27_matrix(*blk)
        assert np.array_equal(a.indptr, theirs.indptr)
        assert np.array_equal(a.indices, theirs.indices)
        assert np.array_equal(a.data, theirs.data)
        if blk == blocks[-1]:
            assert f2c is None
        else:
            assert np.array_equal(f2c, hpcg_f2c(*blk))


def test_generator_refuses_a_block_the_levels_do_not_divide():
    with pytest.raises(ValueError, match="divisible"):
        hpcg27_mg.levels({"nx": 12, "ny": 16, "nz": 16, "levels": 4})


def _loops_levels(levels):
    return [(a.indptr, a.indices, a.data, f2c) for a, f2c in levels]


def test_reference_equals_compute_mg_loops():
    levels = hpcg27_mg.levels(TINY)
    ref = mg_reference.MGReference(levels)
    rng = np.random.default_rng(4)
    r = rng.standard_normal((levels[0][0].shape[0], 3))
    got = ref.apply(r)
    for j in range(3):
        want = LOOPS.compute_mg(_loops_levels(levels), r[:, j])
        assert reference.rel_err(got[:, j], want) < 1e-13
        assert reference.rel_err(ref.apply(r[:, j]), want) < 1e-13


def test_reference_sweep_from_x_equals_compute_symgs_loops():
    a = hpcg27_mg.levels(TINY)[0][0]
    sweep = mg_reference.SweepReference(a)
    rng = np.random.default_rng(5)
    r, x0 = rng.standard_normal((2, a.shape[0]))
    got = sweep.apply(r[:, None], x0[:, None], "float64", lambda v: v)
    want = LOOPS.compute_symgs(a.indptr, a.indices, a.data, r, x0.copy())
    assert reference.rel_err(got[:, 0], want) < 1e-13


def test_reference_from_a_coarse_level_is_that_levels_part():
    """``apply(rc, level=i)`` on level i's right-hand side in the V-cycle
    of r gives level i's result there; level 0 is z."""
    levels = hpcg27_mg.levels(TINY)
    ref = mg_reference.MGReference(levels)
    r = np.random.default_rng(7).standard_normal((levels[0][0].shape[0], 2))
    rhs, out = ref.levels_of(r)
    assert [x.shape for x in rhs] == [(a.shape[0], 2) for a, _ in levels]
    np.testing.assert_array_equal(out[0], ref.apply(r))
    for i in range(1, len(levels)):
        np.testing.assert_array_equal(ref.apply(rhs[i], level=i), out[i])
    loops = []
    LOOPS.compute_mg(_loops_levels(levels), r[:, 0], loops)
    for got, want in zip(out, loops):
        assert reference.rel_err(got[:, 0], want) < 1e-13


class _Faulty(mg_reference.MGReference):
    """The float64 reference with one fault, standing in for a program
    whose coarse level is wrong: ``zero`` drops level k's correction
    (the level above adds x_c = 0); ``half`` leaves out the backward solve
    of level k's sweep from zero; ``dropped`` solves level k's forward
    triangle without one strictly lower entry (a plan that reads a row
    before it is written)."""

    def __init__(self, levels, fault, k):
        super().__init__(levels)
        self.fault, self.k = fault, k
        if fault == "dropped":
            a = sp.csr_array(levels[k][0], copy=True)
            rows = np.repeat(np.arange(a.shape[0]), np.diff(a.indptr))
            lower = np.flatnonzero(rows > a.indices)
            a.data[lower[-1]] = 0.0  # the last row's, next to the diagonal
            self.levels[k][0].fwd = mg_reference.SweepReference(a).fwd

    def _cycle(self, i, r, precision, rnd, rhs, out):
        if i == self.k and self.fault == "zero":
            x = super()._cycle(i, r, precision, rnd, rhs, out)
            out[i] = 0 * x
            return out[i]
        if i == self.k and self.fault == "half":
            sweep, f2c, _ = self.levels[i]
            assert f2c is None  # the coarsest level
            rhs[i] = r
            out[i] = sweep.fwd.solve(r, precision)
            return out[i]
        return super()._cycle(i, r, precision, rnd, rhs, out)


@pytest.mark.parametrize("fault, k", [("zero", 1), ("zero", 2),
                                      ("half", 2), ("dropped", 1),
                                      ("dropped", 2)])
def test_comparison_fails_a_wrong_coarse_level(fault, k):
    """The run's comparison, with a faulty float64 V-cycle in the program's
    place, reads above the cell's limit; the sound V-cycle reads 0."""
    levels = hpcg27_mg.levels(TINY)
    ref = mg_reference.MGReference(levels)
    r = np.random.default_rng(8).standard_normal((levels[0][0].shape[0], 4))
    rhs, want = ref.levels_of(r)

    def readings(prog):
        return mg_reference.compare(
            [(j, prog.apply(r[:, j])) for j in range(4)], want[0],
            {j: [prog.apply(rhs[i][:, j], level=i)
                 for i in range(1, len(levels))] for j in range(4)},
            want[1:])

    assert readings(ref)["rel_err"] == 0.0
    bad = readings(_Faulty(levels, fault, k))
    assert bad["rel_err"] > LIMITS["rel_err"]
    assert bad["levels"][k - 1] > LIMITS["rel_err"]


def test_bfloat16_control_reads_above_the_limit():
    levels = hpcg27_mg.levels(TINY)
    ref = mg_reference.MGReference(levels)
    b = np.random.default_rng(6).standard_normal((levels[0][0].shape[0], 8))
    err = reference.rel_err(ref.apply(b, "bfloat16"), ref.apply(b))
    assert err > LIMITS["rel_err"]


def test_roofline_counts_fourteen_solves_and_six_spmvs():
    levels = hpcg27_mg.levels({"nx": 8, "ny": 8, "nz": 8, "levels": 4})
    parts = mg_roofline.parts(levels)
    kinds = [k for k, _ in parts]
    assert kinds.count("solve") == 14 and kinds.count("spmv") == 6
    a, f2c = levels[0]
    restrict = mg_roofline.spmv_work(a[f2c])
    entries = int(np.diff(a.indptr)[f2c].sum())
    # every fine point is a neighbour of a coarse one: x is read whole
    assert restrict == {"bytes": 8 * entries + 4 * (len(f2c) + 1)
                        + 4 * a.shape[0] + 4 * len(f2c),
                        "flops": 2 * entries}
    upper = mg_roofline.spmv_work(sp.triu(a, k=1, format="csr"))
    assert upper["flops"] == a.nnz - a.shape[0]
    peak = {"hbm_bytes_per_s": 1e9, "flops_per_s": 1e12}
    least = mg_roofline.least_seconds(levels, peak)
    assert least["bounds"] == ["bytes"] and least["spmv_count"] == 6
    assert least["vcycle"] == pytest.approx(
        sum(w["bytes"] for _, w in parts) / 1e9)


def _tiny_root(tmp_path):
    """The benchmark with a small V-cycle cell beside the real one: the
    same generator, driver, traffic and limits, on a 4 x 8 x 8 block of
    three levels."""
    root = copy_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench" / "configs" / "hpcg27_104_mg.json")
                     .read_text())
    cfg["matrix"] = dict(TINY)
    cfg["plans"] = [{"strategy": "hdagg", "k": 8, "slack": 8},
                    {"strategy": "growlocal", "k": 8},
                    {"strategy": "wavefront", "k": 8}]
    (root / "bench" / "configs" / "mg_tiny.json").write_text(json.dumps(cfg))
    bench["configs"].append({"name": "mg_tiny", "source": "test",
                             "file": "bench/configs/mg_tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "mg_tiny.vcycle", "config": "mg_tiny",
                               "traffic": "vcycle", "chips": 1,
                               "why": "test"})
    (root / "bench" / "limits" / "mg_tiny.vcycle.json").write_text(
        json.dumps(LIMITS))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "hpcg_104.vcycle" in m.get("workloads", []):
            m["workloads"].append("mg_tiny.vcycle")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_control_command_reads_not_correct(tmp_path):
    res = mg_reference.control(Spec(_tiny_root(tmp_path)), "mg_tiny.vcycle",
                               3000000152)
    assert res["correct"] is False
    assert res["checks"]["rel_err"]["value"] > LIMITS["rel_err"]
    assert res["checks"]["compared"]["value"] == 16


def _with_a_chip_plane(read_xplane):
    """``read_xplane`` with a made-up chip plane: in each ``bench.solve``
    span of the window, the runs of a three-level V-cycle, four SpMVs and
    ten solves, so that the device-trace readers have a chip's record to
    read on the CPU. Nothing here is a chip number."""
    programs = ["jit__spmv_ell(7)"] * 4 + ["jit__solve_elastic(9)"] * 10

    def read(path):
        device, host = read_xplane(path)
        (lo, hi), = [(s, e) for name, s, e in host if name == trace.WINDOW]
        ev = trace.Events()
        for name, s, e in host:
            if name == "bench.solve" and lo <= s and e <= hi:
                step = (e - s) / len(programs)
                for i, prog in enumerate(programs):
                    ev.add(prog, s + i * step, 0.9 * step)
        return {**device, "/device:TPU:0": {trace.MODULES: ev}}, host
    return read


def test_small_vcycle_cell_runs_and_reads_its_metrics(tmp_path, capsys,
                                                       monkeypatch):
    root = _tiny_root(tmp_path)
    argv = ["--workload", "mg_tiny.vcycle", "--seed", "3000000153",
            "--seconds", "0.5"]
    assert run.main(argv + ["--trace", "0"], root=root,
                    require_accelerator=False) == 0
    out, err = capsys.readouterr()
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is True
    assert res["checks"]["compared"]["value"] == LIMITS["min_compared"]
    assert set(res["metrics"]) == {"setup_s", "solve_ms"}
    window = next(json.loads(line) for line in err.splitlines()
                  if line.startswith('{"plans"'))["window"]
    assert window["solves"] == 10 * window["calls"]  # 3 levels: 4 + 4 + 2
    assert res["metrics"]["solve_ms"]["value"] == pytest.approx(
        1e3 * window["wall_s"] / window["solves"])

    # traced, with the test's own peaks table standing in for a chip's
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"devices": {"cpu": {
        "hbm_bytes_per_s": 1e11, "flops_per_s": 1e12}}}))
    monkeypatch.setattr(roofline, "PEAKS", peaks)
    monkeypatch.setattr(trace, "read_xplane",
                        _with_a_chip_plane(trace.read_xplane))
    assert run.main(argv + ["--trace", "1"], root=root,
                    require_accelerator=False) == 0
    out, err = capsys.readouterr()
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is True
    m = {k: v["value"] for k, v in res["metrics"].items()}
    new = {"vcycle_roofline.mg", "spmv_roofline.mg", "dispatch_ms.mg"}
    assert new <= set(m)
    assert all(0 < m[k] < 100 for k in new - {"dispatch_ms.mg"})
    assert m["dispatch_ms.mg"] > 0
    assert {"idle_pct.solve", "schedule_s", "bind_s", "lane_fill.plan",
            "steps.plan"} <= set(m)
    assert not {"step_us.scan", "solve_roofline"} & set(m)
    info = next(json.loads(line) for line in err.splitlines()
                if line.startswith('{"plans"'))
    assert [p["level"] for p in info["plans"]] == [0, 0, 1, 1, 2, 2]
    assert info["trace"]["solve_only"] is False
    chk = next(json.loads(line) for line in err.splitlines()
               if line.startswith('{"vcycle_checks"'))["vcycle_checks"]
    assert len(chk["level_rel_err"]) == 2  # levels 1 and 2
    assert chk["rel_err"] == res["checks"]["rel_err"]["value"] == max(
        [chk["answer_rel_err"]] + chk["level_rel_err"])
    spmv = info["trace"]["spmv"]
    assert spmv["per_cycle"] == 4
    cycle = info["trace"]["cycle"]
    assert cycle["calls"] == info["trace"]["calls"]
    assert cycle["names"] == ["jit__spmv_ell(7)"] * 4 + [
        "jit__solve_elastic(9)"] * 10
    assert len(cycle["seconds"]) == 14 and min(cycle["seconds"]) > 0
    assert spmv["runs"] == 4 * info["trace"]["calls"] > 0


def test_spmv_reader_reads_nothing_where_no_spmv_ran():
    """The recorded chip trace holds no SpMV program: its runs are 0 and
    ``spmv_roofline.mg`` reports nothing."""
    from bench.drivers import vcycle

    device, host = trace.read_xplane(
        str(REPO / "bench" / "tests" / "data" / "tiny_tpu.xplane.pb"))
    window = trace.reduce(device, host)
    spmv = vcycle.spmv_runs(device, host, window) | {"per_cycle": 6}
    assert spmv["runs"] == 0
    assert _reader("spmv_roofline.mg")({"trace": {
        "spmv": spmv, "spmv_least_s": 1e-3}}) is None


@pytest.mark.parametrize("name", ["vcycle_roofline.mg", "spmv_roofline.mg",
                                  "dispatch_ms.mg"])
def test_new_readers_read_nothing_without_their_data(name):
    assert _reader(name)({"driver": "closed_loop", "window": {}}) is None


def test_roofline_readers_on_a_synthetic_record():
    tr = {"busy_s": 2.0, "cycles": 4.0, "vcycle_least_s": 0.05,
          "spmv_least_s": 0.01,
          "spmv": {"runs": 24, "seconds": 0.2, "per_cycle": 6}}
    assert _reader("vcycle_roofline.mg")({"trace": tr}) == pytest.approx(10.0)
    assert _reader("spmv_roofline.mg")({"trace": tr}) == pytest.approx(20.0)


def test_cycle_idle_is_the_chip_idle_inside_each_call():
    """Two calls in the window: the first 100 ns with programs over 80 of
    them, the second 100 ns with 90; the chip waited 15 ns a V-cycle."""
    from bench.drivers import vcycle

    ev = trace.Events()
    for name, s, e in [("jit__solve_elastic(1)", 110, 150),
                       ("jit__spmv_ell(2)", 150, 190),
                       ("jit__solve_elastic(1)", 300, 390)]:
        ev.add(name, s, e - s)
    host = [(trace.WINDOW, 0, 1000), ("bench.solve", 100, 200),
            ("bench.solve", 300, 400)]
    cyc = vcycle.cycle_programs({"/device:TPU:0": {trace.MODULES: ev}}, host,
                                {"cut_s": 0.0})
    assert cyc["idle_s"] == pytest.approx(15e-9)
    assert _reader("dispatch_ms.mg")({"trace": {"cycle": cyc}}) == \
        pytest.approx(15e-6)
