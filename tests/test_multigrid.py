"""HPCG's multigrid V-cycle (``repro.pipeline.VCycle``) and the symmetric
Gauss–Seidel sweep from any x (``SymGSSweep``), against row-by-row float64
transcriptions of ``ComputeSYMGS_ref``, ``GenerateCoarseProblem``'s
``f2cOperator`` and ``ComputeMG_ref`` (``tests/_symgs_ref.py``)."""
import numpy as np
import pytest

from _symgs_ref import compute_mg, compute_symgs, generate_f2c
from repro import obs
from repro.pipeline import PlanCache, SymGSSweep, VCycle
from repro.solver.ell import ell_layout, spmv
from repro.sparse import (
    CSRMatrix,
    hpcg_f2c,
    mg_block_shapes,
    stencil27_matrix,
)

# A sweep from x0, float32 against float64. As in tests/test_symgs.py the
# rows are strictly diagonally dominant, so a row's rounding error is damped
# in the rows that read it; the sweep from x0 adds one SpMV (13 products a
# row) and two vector updates to the two solves, so the error stays a few
# times float32's unit roundoff (6e-8) times the 27 terms of a row:
# 1e-5 of the largest |x| is threefold room over 27 * 2 * 6e-8 ≈ 3e-6, and
# far under a wrong sign, order or orientation (O(1e-2) and up).
SWEEP_RTOL = 1e-5

# A V-cycle is 14 such solves and 6 SpMVs in a chain. Its errors do not
# add up as a worst-case bound would: each post-smoother recomputes its
# level's answer from r and damps what the levels below handed back. On
# these blocks it reads 1.1e-7 (16^3) and 1.5e-7 (8 x 16 x 24) of the
# largest |x|, two to three float32 roundoffs. 1e-6 leaves sixfold room
# and still fails a cycle that leaves out its coarsest level, which moves
# the answer by 3e-6 and 1.6e-5 of its largest value here
# (``test_vcycle_tolerance_sees_a_missing_level``).
VCYCLE_RTOL = 1e-6

PLANS = [
    pytest.param(dict(strategy="hdagg", k=8), id="hdagg-k8"),
    pytest.param(dict(strategy="wavefront", k=32, slack=8),
                 id="wavefront-k32-elastic"),
]


def _dominant_values(a: CSRMatrix, seed: int) -> CSRMatrix:
    """The pattern of ``a`` with seeded values, not symmetric: each
    off-diagonal ~ U[-1, 1], each diagonal the row's absolute sum plus
    U[0.5, 1.5] with a random sign (strictly diagonally dominant)."""
    rng = np.random.default_rng(seed)
    rows = a.row_of_entry()
    diag = a.indices == rows
    data = rng.uniform(-1.0, 1.0, a.nnz)
    data[diag] = 0.0
    dom = np.bincount(rows, weights=np.abs(data), minlength=a.n_rows)
    dom += rng.uniform(0.5, 1.5, a.n_rows)
    dom *= rng.choice([-1.0, 1.0], a.n_rows)
    data[diag] = dom[rows[diag]]
    return CSRMatrix(a.n_rows, a.n_cols, a.indptr, a.indices, data)


MATRICES = {
    "hpcg": lambda: stencil27_matrix(5, 6, 7),
    "dominant": lambda: _dominant_values(stencil27_matrix(5, 6, 7), 11),
}


def _sweep(a: CSRMatrix, **plan_kw):
    """The sweep of a 5 x 6 x 7 block's matrix, as ``VCycle.build`` builds
    every sweep: a hierarchy of one level."""
    return VCycle.build((5, 6, 7), a=a, levels=1, plans=plan_kw).levels[0].sweep


def _close(got, want, rtol):
    got = np.asarray(got, np.float64)
    return np.abs(got - want).max() <= rtol * np.abs(want).max()


@pytest.mark.parametrize("plan_kw", PLANS)
@pytest.mark.parametrize("matrix", sorted(MATRICES))
def test_sweep_from_x_matches_compute_symgs(matrix, plan_kw):
    a = MATRICES[matrix]()
    sweep = _sweep(a, **plan_kw)
    rng = np.random.default_rng(7)
    for _ in range(2):
        r = rng.standard_normal(a.n_rows)
        x0 = rng.standard_normal(a.n_rows)
        want = compute_symgs(a.indptr, a.indices, a.data, r, x0.copy())
        assert _close(sweep(r, x0), want, SWEEP_RTOL)
    # from zero, the same object is the pair of gauss_seidel_pair
    want = compute_symgs(a.indptr, a.indices, a.data, r, np.zeros(a.n_rows))
    assert _close(sweep(r), want, SWEEP_RTOL)


def test_sweep_from_x_multi_rhs_columns_are_single_sweeps():
    a = MATRICES["dominant"]()
    sweep = _sweep(a, strategy="hdagg", k=8)
    rng = np.random.default_rng(8)
    r = rng.standard_normal((a.n_rows, 3))
    x0 = rng.standard_normal((a.n_rows, 3))
    got = sweep(r, x0)
    for j in range(3):
        want = compute_symgs(a.indptr, a.indices, a.data, r[:, j],
                             x0[:, j].copy())
        assert _close(got[:, j], want, SWEEP_RTOL)


@pytest.mark.parametrize("shape", [(8, 10, 12), (2, 2, 2), (6, 4, 2)])
def test_f2c_is_generate_coarse_problem(shape):
    got = hpcg_f2c(*shape)
    assert got.dtype == np.int32
    assert np.array_equal(got, generate_f2c(*shape))


@pytest.mark.parametrize("rows", ["all", "coarse"])
def test_ell_spmv_equals_csr_product(rows):
    a = MATRICES["dominant"]()
    pick = None if rows == "all" else np.array([0, 9, 3, 200, 209])
    ell = ell_layout(a, pick)
    x = np.random.default_rng(9).standard_normal(a.n_rows)
    want = a.to_scipy() @ x
    if pick is not None:
        want = want[pick]
    lens = np.diff(a.indptr) if pick is None else np.diff(a.indptr)[pick]
    assert ell.width == lens.max() == (27 if pick is None else 12)
    np.testing.assert_allclose(np.asarray(spmv(ell, x)), want,
                               rtol=0, atol=1e-5 * np.abs(want).max())


def _levels_of(vc, fine=None):
    """The hierarchy as ``compute_mg`` reads it: each level's CSR arrays
    from the program's own stencil (the matrix is input data here, as in
    tests/test_symgs.py) and the transcription's own f2c."""
    out = []
    for i, lv in enumerate(vc.levels):
        a = fine if (i == 0 and fine is not None) else stencil27_matrix(
            *lv.shape)
        f2c = generate_f2c(*lv.shape) if lv.f2c is not None else None
        out.append((a.indptr, a.indices, a.data, f2c))
    return out


@pytest.mark.parametrize("shape", [(16, 16, 16), (8, 16, 24)],
                         ids=["16^3", "8x16x24"])
def test_vcycle_matches_compute_mg(shape):
    vc = VCycle.build(shape, levels=4,
                      plans=[dict(strategy="hdagg", k=8, slack=8),
                             dict(strategy="growlocal", k=8),
                             dict(strategy="wavefront", k=32),
                             dict(strategy="hdagg", k=8)])
    assert [lv.shape for lv in vc.levels] == mg_block_shapes(*shape, 4)
    levels = _levels_of(vc)
    rng = np.random.default_rng(sum(shape))
    for _ in range(2):
        r = rng.standard_normal(vc.n)
        want = compute_mg(levels, r)
        assert _close(vc(r), want, VCYCLE_RTOL)


@pytest.mark.parametrize("shape", [(16, 16, 16), (8, 16, 24)],
                         ids=["16^3", "8x16x24"])
def test_vcycle_from_each_level_matches_compute_mg(shape):
    """``from_level(i, rc)`` is level i's part of the V-cycle: on the
    transcription's restricted residual there it gives the
    transcription's result there (each against its own largest value, to
    the V-cycle's tolerance), and from level 0 it is ``vc(r)``, bit for
    bit."""
    vc = VCycle.build(shape, levels=4, plans=dict(strategy="hdagg", k=8))
    levels = _levels_of(vc)
    r = np.random.default_rng(sum(shape) + 1).standard_normal(vc.n)
    want = []
    compute_mg(levels, r, want)
    assert np.array_equal(np.asarray(vc.from_level(0, r)), np.asarray(vc(r)))
    rc = r
    for i in range(1, 4):
        indptr, indices, data, f2c = levels[i - 1]
        ax = CSRMatrix(len(indptr) - 1, len(indptr) - 1, indptr, indices,
                       data).to_scipy() @ _pre_smoothed(levels[i - 1], rc)
        rc = rc[f2c] - ax[f2c]
        assert _close(vc.from_level(i, rc), want[i], VCYCLE_RTOL)
    with pytest.raises(ValueError, match="level 4"):
        vc.from_level(4, rc)


def _pre_smoothed(level, r):
    indptr, indices, data, _ = level
    return compute_symgs(indptr, indices, data, r, np.zeros(len(indptr) - 1))


@pytest.mark.parametrize("shape", [(16, 16, 16), (8, 16, 24)],
                         ids=["16^3", "8x16x24"])
def test_vcycle_tolerance_sees_a_missing_level(shape):
    """Three levels where ``ComputeMG_ref`` has four fails the tolerance."""
    plans = dict(strategy="hdagg", k=8)
    four = VCycle.build(shape, levels=4, plans=plans)
    three = VCycle.build(shape, levels=3, plans=plans)
    r = np.random.default_rng(sum(shape)).standard_normal(four.n)
    want = compute_mg(_levels_of(four), r)
    assert _close(four(r), want, VCYCLE_RTOL)
    assert not _close(three(r), want, VCYCLE_RTOL)


def test_vcycle_on_a_given_fine_matrix():
    """``a=`` replaces the finest level's matrix; the coarse levels stay
    HPCG's stencils."""
    shape = (8, 8, 8)
    fine = _dominant_values(stencil27_matrix(*shape), 12)
    vc = VCycle.build(shape, a=fine, levels=3,
                      plans=dict(strategy="hdagg", k=8))
    r = np.random.default_rng(13).standard_normal(vc.n)
    assert _close(vc(r), compute_mg(_levels_of(vc, fine), r), VCYCLE_RTOL)


def test_vcycle_is_the_symmetric_preconditioner_hpcg_needs():
    """With one pre- and one post-sweep the V-cycle is a symmetric linear
    map (what lets HPCG's CG use it): <M^-1 u, v> = <u, M^-1 v>."""
    vc = VCycle.build((8, 8, 8), levels=4, plans=dict(strategy="hdagg", k=8))
    rng = np.random.default_rng(14)
    u, v = rng.standard_normal((2, vc.n))
    mu, mv = (np.asarray(vc(w), np.float64) for w in (u, v))
    assert abs(mu @ v - u @ mv) <= 1e-5 * np.abs(mu).max() * np.abs(v).sum()


def test_spans_and_counters_are_recorded():
    buf = obs.TraceBuffer("mg")
    cache = PlanCache()
    with obs.tracing(buf):
        vc = VCycle.build((8, 8, 8), levels=4, cache=cache,
                          plans=dict(strategy="hdagg", k=8))
        r = np.random.default_rng(15).standard_normal(vc.n)
        for _ in range(2):
            np.asarray(vc(r))
    spans = buf.spans()
    builds = [s for s in spans if s.name == "mg.build"]
    assert [(s.args["level"], s.args["n"]) for s in builds] == [
        (0, 512), (1, 64), (2, 8), (3, 1)]
    # the plans keep their own spans, outside mg.build
    for s in spans:
        if s.name == "inspector.schedule":
            assert not any(b.t0_ns <= s.t0_ns and s.t1_ns <= b.t1_ns
                           for b in builds)
    cycles = [s for s in spans if s.name == "mg.vcycle"]
    assert len(cycles) == 2
    dispatches = [s for s in spans if s.name == "executor.dispatch"]
    assert len(dispatches) == 2 * 14
    assert all(any(c.t0_ns <= d.t0_ns and d.t1_ns <= c.t1_ns
                   for c in cycles) for d in dispatches)
    counters = buf.counters()
    assert counters["mg.levels"] == 4
    assert counters["mg.spmv"] == 2 * 6
    assert counters["mg.sweep.from_x"] == 2 * 3
    assert cache.stats.misses == 8  # two plans a level, one shared cache


def test_spmv_trace_counter_counts_traces_not_calls():
    """``jit.trace.spmv`` is bumped where JAX traces the SpMV program: a
    shape it has not seen yet, never a repeat."""
    a = stencil27_matrix(3, 5, 7)  # a row count no other test uses
    ell = ell_layout(a)
    buf = obs.TraceBuffer("spmv")
    with obs.tracing(buf):
        for _ in range(3):
            np.asarray(spmv(ell, np.ones(a.n_rows, np.float32)))
    assert buf.counters().get("jit.trace.spmv") == 1


@pytest.mark.parametrize("shape, levels", [
    ((12, 16, 16), 4), ((16, 16, 20), 4), ((3, 4, 4), 2), ((8, 8, 8), 5),
])
def test_block_not_divisible_by_the_levels_is_refused(shape, levels):
    with pytest.raises(ValueError, match="divisible"):
        mg_block_shapes(*shape, levels)
    with pytest.raises(ValueError, match="divisible"):
        VCycle.build(shape, levels=levels)


def test_wrong_sized_fine_matrix_and_plans_are_refused():
    with pytest.raises(ValueError, match="rows"):
        VCycle.build((8, 8, 8), a=stencil27_matrix(4, 4, 4), levels=2)
    with pytest.raises(ValueError, match="one per level"):
        VCycle.build((8, 8, 8), levels=2, plans=[{}])


def test_ell_of_an_empty_upper_part():
    """The 1 x 1 x 1 coarsest block of an 8^3 hierarchy has no strict
    upper entries: its D^-1 U is one padding column, a zero product."""
    d, upper = SymGSSweep.operators(stencil27_matrix(1, 1, 1))
    assert upper.width == 1
    assert float(np.asarray(spmv(upper, np.ones(1)))[0]) == 0.0
    assert np.asarray(d).tolist() == [26.0]
