"""The roofline's work comes from the matrix, and its peaks from the table
by device kind."""
import numpy as np
import pytest

from bench import harness, roofline
from bench.matrices import erdos_renyi_lower
from bench.spec import load

READER = roofline.PEAKS.parent / "layers" / "solve_roofline.py"


def test_work_counted_from_matrix():
    w = roofline.solve_work(n=3, nnz=5, m=2)
    assert w["bytes"] == 8 * 5 + 4 * 4 + 8 * 2 * 3
    assert w["flops"] == 2 * (2 * (5 - 3) + 3)


def test_v5e_least_time_of_the_ic0_factor():
    w = roofline.solve_work(n=262_144, nnz=1_036_288, m=1)
    assert w["bytes"] == 11_436_036
    least, bound = roofline.least_seconds(w, roofline.peaks("TPU v5 lite"))
    assert bound == "bytes"
    assert least == pytest.approx(11_436_036 / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_padding_does_not_change_the_count():
    """Two plans of one matrix with different widths stream different
    padded bytes; the count, and so the share, stays."""
    from repro.pipeline import TriangularSolver

    a = erdos_renyi_lower(400, 0.02, pattern_seed=1, value_seed=2)
    narrow = TriangularSolver.plan(harness.program_csr(a), width=4)
    wide = TriangularSolver.plan(harness.program_csr(a), width=16)
    assert (narrow.exec_plan.stats()["bytes_streamed"]
            != wide.exec_plan.stats()["bytes_streamed"])
    ops = [(a, True)]
    assert harness.call_work(ops, 4) == roofline.solve_work(400, a.nnz, 4)
    b = np.ones((400, 4), np.float32)
    np.testing.assert_allclose(np.asarray(narrow.solve(b)),
                               np.asarray(wide.solve(b)), rtol=1e-5)


def test_share_counts_every_program_a_solve_dispatches():
    """Two whole runs of 10 ms of the solve program, which has 80% of the
    busy time: one solve costs 12.5 ms of device time, gathers included."""
    rec = {"trace": {"solve_only": True, "solve_least_s": 1e-4,
                     "program": {"name": "jit_solve", "runs": 2,
                                 "seconds": 0.02, "share": 0.8}}}
    assert load(READER).read(rec) == pytest.approx(100 * 1e-4 / 0.0125)
    rec["trace"]["program"]["runs"] = 0  # no whole run: nothing to read
    assert load(READER).read(rec) is None
