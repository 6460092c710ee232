"""Multi-device tests. The shard_map executor needs >1 device, and jax locks
the host device count at first init — so these run in subprocesses with
XLA_FLAGS set (tests/_mesh.py; the same isolation dryrun.py uses)."""
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


def test_train_step_lowers_on_multidevice_mesh():
    """Reduced-config train step lowers + compiles on a (2, 2) mesh with the
    production sharding rules (miniature of the 512-chip dry-run)."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_reduced
        from repro.distributed.meshes import resolve_spec
        from repro.launch.mesh import make_local_mesh
        from repro.models import abstract_params, logical_specs, param_specs
        from repro.train import AdamWConfig, make_train_step
        from repro.train.train_loop import TrainState

        cfg = get_reduced("deepseek_moe_16b")
        mesh = make_local_mesh(model=2, data=2)
        specs = param_specs(cfg)
        logical = logical_specs(specs)
        abst = abstract_params(specs, dtype=jnp.float32)
        is_leaf = lambda x: isinstance(x, tuple) and all(
            isinstance(e, str) or e is None for e in x)
        sds = jax.tree_util.tree_map(
            lambda log, a: jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=jax.sharding.NamedSharding(
                    mesh, resolve_spec(mesh, log, a.shape))),
            logical, abst, is_leaf=is_leaf)
        f32 = lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32,
                                             sharding=a.sharding)
        state = TrainState(params=sds, opt_state={
            "mu": jax.tree_util.tree_map(f32, sds),
            "nu": jax.tree_util.tree_map(f32, sds),
            "step": jax.ShapeDtypeStruct((), jnp.int32)})
        batch = {
            "tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
            "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32),
        }
        step = make_train_step(cfg, AdamWConfig(), microbatches=2)
        with mesh:
            compiled = jax.jit(step).lower(state, batch).compile()
        assert compiled.cost_analysis() is not None
        print("lower-ok")
    """))


def test_elastic_mesh_restore_multidevice(tmp_path):
    """Save on an 8-device mesh, restore onto a 4-device mesh (elastic)."""
    print(_run(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import restore_checkpoint, save_checkpoint

        mesh8 = jax.make_mesh((8,), ("data",))
        x = jax.device_put(np.arange(64, dtype=np.float32).reshape(8, 8),
                           NamedSharding(mesh8, P("data")))
        tree = {{"w": x}}
        save_checkpoint(r"{tmp_path}/ck", tree, step=5)

        devs = np.array(jax.devices()[:4]).reshape(4)
        mesh4 = jax.sharding.Mesh(devs, ("data",))
        sh = {{"w": NamedSharding(mesh4, P("data"))}}
        restored, meta = restore_checkpoint(r"{tmp_path}/ck",
                                            template=tree, shardings=sh)
        assert meta["step"] == 5
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(x))
        assert restored["w"].sharding.mesh.shape["data"] == 4
        print("elastic-ok")
    """))
