"""Multi-device tests. The shard_map executor needs >1 device, and jax locks
the host device count at first init — so these run in subprocesses with
XLA_FLAGS set (tests/_mesh.py)."""
from _mesh import run_in_mesh_subprocess


def _run(code: str, devices: int = 8, timeout: int = 600):
    return run_in_mesh_subprocess(code, devices=devices, timeout=timeout)


def test_shard_map_executor_matches_scipy():
    print(_run("""
        import numpy as np, jax
        from repro.core import apply_reordering, compile_plan, grow_local
        from repro.solver import solve_lower_scipy
        from repro.solver.distributed import run_distributed_solve
        from repro.sparse import dag_from_lower_csr, erdos_renyi_lower

        L = erdos_renyi_lower(800, 2e-3, seed=9)
        dag = dag_from_lower_csr(L)
        s = grow_local(dag, 4)
        L2, s2, _, _ = apply_reordering(L, s)
        plan = compile_plan(L2, s2)
        b = np.random.default_rng(1).standard_normal((2, 800))
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        x = run_distributed_solve(plan, b, mesh)
        for i in range(2):
            ref = solve_lower_scipy(L2, b[i])
            err = np.abs(x[i] - ref).max() / np.abs(ref).max()
            assert err < 2e-3, err
        print("dist-ok", s2.n_supersteps)
    """))


def test_distributed_lowering_counts_barriers():
    """The lowered graph must contain exactly n_supersteps all-gather groups
    per tensor exchanged — GrowLocal's barrier reduction is visible in HLO."""
    print(_run("""
        import numpy as np, jax
        from repro.core import apply_reordering, compile_plan, grow_local
        from repro.solver.distributed import dist_plan_spec, lower_distributed_solve
        from repro.sparse import dag_from_lower_csr, narrow_band_lower

        L = narrow_band_lower(600, 0.14, 8, seed=2)
        dag = dag_from_lower_csr(L)
        s = grow_local(dag, 4)
        L2, s2, _, _ = apply_reordering(L, s)
        plan = compile_plan(L2, s2)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        spec = dist_plan_spec(plan, batch=2)
        lowered = lower_distributed_solve(spec, mesh)
        txt = lowered.as_text()
        n_ag = txt.count("all_gather") + txt.count("all-gather")
        # 3 tensors exchanged per superstep (rows, values, accum flags)
        assert n_ag >= s2.n_supersteps, (n_ag, s2.n_supersteps)
        assert n_ag <= 4 * s2.n_supersteps, (n_ag, s2.n_supersteps)
        print("barriers-ok", s2.n_supersteps, n_ag)
    """))

