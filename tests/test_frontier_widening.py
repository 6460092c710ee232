"""Beyond-paper frontier-widening cut (PERF.md §6): off on a direct
``grow_local`` call (paper-faithful), on in the registry's ``"growlocal"``
strategy (``widened_grow_local``) where it lowers the BSP cost. It must
stay valid, must break the single-core takeover on single-source grids,
and must leave many-source DAGs' plans bit for bit."""
import numpy as np
import pytest
from scipy.sparse.linalg import spsolve_triangular

from repro import obs
from repro.core import bsp_cost, check_validity, compile_plan, schedule_stats
from repro.core.growlocal import grow_local, widened_grow_local
from repro.core.plan import plans_bitwise_equal
from repro.pipeline import TriangularSolver, schedule
from repro.pipeline.solver import mirror_to_lower
from repro.sparse import (
    dag_from_lower_csr,
    erdos_renyi_lower,
    ichol0,
    narrow_band_lower,
    poisson2d_matrix,
    poisson3d_matrix,
    transpose_csr,
)
from repro.sparse.dag import average_wavefront_size

WIDEN_CUT = "inspector.growlocal.widen_cut"


def test_widening_valid_everywhere():
    for L in (
        ichol0(poisson2d_matrix(40)),
        erdos_renyi_lower(1500, 1e-3, seed=3),
        narrow_band_lower(1500, 0.14, 10, seed=4),
    ):
        dag = dag_from_lower_csr(L)
        s = grow_local(dag, 8, frontier_widening=True)
        check_validity(dag, s)


def test_widening_breaks_serial_takeover():
    """Single-source IC0 grid at paper-filter scale: faithful GrowLocal
    emits one serial superstep; widening unlocks the wavefront parallelism."""
    dag = dag_from_lower_csr(ichol0(poisson2d_matrix(120)))
    base = grow_local(dag, 8, frontier_widening=False)
    widened = grow_local(dag, 8, frontier_widening=True)
    assert base.n_supersteps == 1  # the takeover regime
    assert widened.n_supersteps > 1
    assert bsp_cost(dag, widened) < bsp_cost(dag, base)


def test_widening_near_noop_on_wide_dags():
    """Many-source DAGs: the rule must not fire destructively (<5% cost)."""
    dag = dag_from_lower_csr(erdos_renyi_lower(4000, 6e-4, seed=5))
    base = grow_local(dag, 8, frontier_widening=False)
    widened = grow_local(dag, 8, frontier_widening=True)
    assert bsp_cost(dag, widened) <= 1.05 * bsp_cost(dag, base)


# ------------------------------------------ the registered strategy


def _ic0_grid(lower: bool):
    """IC(0) factor of the 24^3 7-point grid (lower) or its transpose."""
    lf = ichol0(poisson3d_matrix(24))
    return lf if lower else transpose_csr(lf)


def _many_source_er():
    return erdos_renyi_lower(4000, 6e-4, seed=5)


@pytest.mark.parametrize("lower", [True, False], ids=["L", "LT"])
def test_registered_growlocal_widens_ic0_grid(lower):
    """The program's ``growlocal`` on a single-source grid factor: a valid
    many-superstep schedule whose plan needs well under n/3 steps (the
    faithful schedule needs n), and a solve that matches scipy."""
    a = _ic0_grid(lower)
    n = a.n_rows
    dag = dag_from_lower_csr(mirror_to_lower(a, lower)[0])
    s = schedule(dag, 8, strategy="growlocal")
    check_validity(dag, s)
    assert s.n_supersteps > 1
    assert grow_local(dag, 8, frontier_widening=False).n_supersteps == 1

    solver = TriangularSolver.plan(
        a, lower=lower, strategy="growlocal", k=8, slack=8
    )
    assert solver.exec_plan.stats()["n_steps"] < n / 3
    b = np.random.default_rng(16).standard_normal(n)
    x = np.asarray(solver.solve(b))
    ref = spsolve_triangular(a.to_scipy().tocsr(), b, lower=lower)
    assert np.abs(x - ref).max() / np.abs(ref).max() < 1e-5


def test_registered_growlocal_plan_bitwise_on_many_source_er():
    """Where no superstep grows on a single core, the registered strategy
    plans exactly what the paper's GrowLocal plans (the ER cells)."""
    m = _many_source_er()
    dag = dag_from_lower_csr(m)
    assert average_wavefront_size(dag) >= 2.0  # the cut is armed
    registered = schedule(dag, 8, strategy="growlocal")
    faithful = grow_local(dag, 8, frontier_widening=False)
    for name in ("pi", "sigma", "rank"):
        np.testing.assert_array_equal(
            getattr(registered, name), getattr(faithful, name), err_msg=name
        )
    assert plans_bitwise_equal(
        compile_plan(m, registered), compile_plan(m, faithful)
    )
    assert schedule_stats(dag, registered) == schedule_stats(dag, faithful)


@pytest.mark.parametrize(
    "matrix",
    [
        lambda: ichol0(poisson2d_matrix(60)),
        lambda: erdos_renyi_lower(500, 0.03, seed=102),
    ],
    ids=["ic0_2d_60", "er_500_dense"],
)
def test_registered_growlocal_keeps_paper_schedule_where_cut_loses(matrix):
    """Small DAGs where the cut fires but its barriers cost more than the
    parallelism it unlocks: the registered strategy returns the paper's
    schedule, bit for bit."""
    dag = dag_from_lower_csr(matrix())
    faithful = grow_local(dag, 8, frontier_widening=False)
    widened = grow_local(dag, 8, frontier_widening=True)
    assert widened.n_supersteps != faithful.n_supersteps  # the cut fired
    assert bsp_cost(dag, widened) >= bsp_cost(dag, faithful)
    kept = widened_grow_local(dag, 8)
    for name in ("pi", "sigma", "rank"):
        np.testing.assert_array_equal(
            getattr(kept, name), getattr(faithful, name), err_msg=name
        )


@pytest.mark.parametrize(
    "case, fires",
    [("ic0_L", True), ("ic0_LT", True), ("er", False), ("ic0_2d_60", False)],
)
def test_widen_cut_counter(case, fires):
    """``inspector.growlocal.widen_cut`` counts the supersteps the cut closed
    in the schedules the strategy kept: some on the 3-D grid factor, none on
    the many-source DAG or where the cut lost."""
    if case == "er":
        dag = dag_from_lower_csr(_many_source_er())
    elif case == "ic0_2d_60":
        dag = dag_from_lower_csr(ichol0(poisson2d_matrix(60)))
    else:
        lower = case == "ic0_L"
        dag = dag_from_lower_csr(mirror_to_lower(_ic0_grid(lower), lower)[0])
    buf = obs.TraceBuffer("widen_cut")
    with obs.tracing(buf):
        s = schedule(dag, 8, strategy="growlocal")
    cuts = buf.counters().get(WIDEN_CUT, 0)
    if fires:
        assert 0 < cuts < s.n_supersteps
    else:
        assert cuts == 0
