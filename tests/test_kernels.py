"""Pallas kernel tests: shape/dtype sweep, allclose vs the pure-jnp oracle
(ref.py) and vs scipy, in interpret mode (CPU container; TPU is the target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import apply_reordering, compile_plan, grow_local
from repro.kernels.ops import kernel_plan_arrays, sptrsv_kernel_solve
from repro.kernels.ref import sptrsv_ref
from repro.kernels.sptrsv import sptrsv_pallas
from repro.solver import solve_lower_scipy
from repro.sparse import dag_from_lower_csr, erdos_renyi_lower, narrow_band_lower

from _oracle import oracle_solve


def _plan_for(n, density, seed, k=8, width=None):
    L = erdos_renyi_lower(n, density, seed=seed)
    dag = dag_from_lower_csr(L)
    s = grow_local(dag, k)
    L2, s2, _, _ = apply_reordering(L, s)
    return L2, compile_plan(L2, s2, width=width)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize(
    "n,density,k,width",
    [
        (64, 0.05, 2, None),
        (200, 0.02, 4, 3),
        (450, 0.01, 8, 16),
        (300, 0.08, 16, 2),  # heavy row-splitting
    ],
)
def test_kernel_matches_oracle_sweep(n, density, k, width, dtype):
    """Sweep shapes/dtypes; kernel (interpret) == ref.py oracle exactly."""
    if dtype == jnp.float64 and not jax.config.read("jax_enable_x64"):
        pytest.skip("x64 disabled")
    L2, plan = _plan_for(n, density, seed=n + k, k=k, width=width)
    rng = np.random.default_rng(0)
    b = rng.standard_normal(n)
    arrays = kernel_plan_arrays(plan, steps_per_tile=4, dtype=dtype)
    b_pad = jnp.concatenate([jnp.asarray(b, dtype), jnp.zeros(1, dtype)])
    x_kernel = sptrsv_pallas(*arrays, b_pad, steps_per_tile=4, interpret=True)
    x_oracle = sptrsv_ref(*arrays, b_pad)
    # f32 tolerance: the kernel's sum(v*g) and the oracle's einsum may
    # reassociate the reduction; solve recurrences amplify ~1 ulp to ~1e-5.
    tol = 1e-4 if dtype == jnp.float32 else 1e-10
    np.testing.assert_allclose(
        np.asarray(x_kernel), np.asarray(x_oracle), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("steps_per_tile", [1, 2, 8, 32])
def test_kernel_tile_size_invariance(steps_per_tile):
    """The kernel's answer must not depend on the grid tiling."""
    L2, plan = _plan_for(220, 0.03, seed=42, k=4)
    rng = np.random.default_rng(1)
    b = rng.standard_normal(220)
    x = np.asarray(
        sptrsv_kernel_solve(plan, b, steps_per_tile=steps_per_tile, interpret=True)
    )
    x_ref = solve_lower_scipy(L2, b)
    assert np.abs(x - x_ref).max() / (np.abs(x_ref).max() + 1e-30) < 2e-3


def test_kernel_matches_scipy_nb():
    L = narrow_band_lower(400, 0.14, 8, seed=3)
    dag = dag_from_lower_csr(L)
    s = grow_local(dag, 8)
    L2, s2, _, _ = apply_reordering(L, s)
    plan = compile_plan(L2, s2)
    rng = np.random.default_rng(2)
    b = rng.standard_normal(400)
    x = np.asarray(sptrsv_kernel_solve(plan, b, interpret=True))
    x_ref = solve_lower_scipy(L2, b)
    assert np.abs(x - x_ref).max() / (np.abs(x_ref).max() + 1e-30) < 2e-3


@pytest.mark.parametrize("m", [1, 16], ids=["rhs1", "rhs16"])
@pytest.mark.parametrize("width", [None, 2], ids=["p95", "split"])
def test_kernel_oracle_is_scan_executor(width, m):
    """ref.py and solver.executor implement the same dataflow, bit for
    bit, on a plan with padded lanes and accum chains (the default width,
    the 95th percentile of row widths, splits the widest rows; ``width=2``
    most of them). The executor's own layout never gathers the sink row
    n + 1, and accum lanes, and only they, write to it."""
    from repro.solver.executor import (
        elastic_plan_arrays,
        plan_arrays,
        solve_with_plan,
    )

    L2, plan = _plan_for(150, 0.04, seed=9, k=4, width=width)
    assert plan.accum.any() and (plan.row_ids == plan.n).any()
    pa = plan_arrays(plan)
    ea = elastic_plan_arrays(plan, slack=8)
    sink = plan.n + 1
    for arrays in (pa, ea):
        assert int(np.asarray(arrays.cols).max()) < sink
        writes_sink = np.asarray(arrays.write_rows) == sink
        assert np.array_equal(writes_sink, np.asarray(arrays.accum))
    rng = np.random.default_rng(5)
    B = rng.standard_normal((150, m)).astype(np.float32)
    if m == 1:
        B = B[:, 0]
    x = np.asarray(solve_with_plan(pa, jnp.asarray(B)))
    assert np.array_equal(x, oracle_solve(plan, B))
