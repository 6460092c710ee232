"""Subprocess CPU-mesh conformance for the row-sharded solve
(``shard="rows"``): the partitioned executor must be *bitwise* equal to
the single-chip scan executor — the partitioner only relabels rows into
local slots; every float op runs in the same order on the same values
(``solver/executor.py``'s fixed-order lane reduction makes that hold at
any shard count). The grid covers corpus x orientation x RHS shape on
two mesh shapes, plus the elastic fused-exchange path, the
update_values contract, describe() telemetry and the timed
per-exchange-round path. Host-side partitioner properties live in
``test_rowshard.py``. Every solve is also held to ``kernels/ref.py``'s
oracle (``tests/_oracle.py``), bit for bit."""
import textwrap
from pathlib import Path

from _mesh import run_in_mesh_subprocess

# makes tests/_oracle.py importable in the subprocess
_TESTS = f"import sys; sys.path.insert(0, {str(Path(__file__).parent)!r})\n"


def _run(code: str, devices: int = 8, timeout: int = 600):
    return run_in_mesh_subprocess(
        _TESTS + textwrap.dedent(code), devices=devices, timeout=timeout
    )


def test_rowshard_bitwise_conformance_grid():
    """Corpus x lower/upper x 1/multi-RHS x two mesh shapes: the sharded
    solve matches the scan backend bit for bit, and the repo's canonical
    ``direct_reference`` replay agrees the same way."""
    print(_run("""
        import numpy as np, jax
        from repro.pipeline import PlanCache, TriangularSolver
        from repro.serve.service import direct_reference
        from repro.sparse import transpose_csr
        from repro.sparse.generators import erdos_renyi_lower, narrow_band_lower
        from _oracle import solver_oracle

        mats = {
            "er": erdos_renyi_lower(700, 2.5e-3, seed=9),
            "band": narrow_band_lower(700, 0.12, 7, seed=2),
        }
        cache = PlanCache()
        for mesh_shape in [(2, 4), (1, 8)]:
            mesh = jax.make_mesh(mesh_shape, ("data", "model"))
            for name, L in mats.items():
                for lower in (True, False):
                    a = L if lower else transpose_csr(L)
                    ref = TriangularSolver.plan(
                        a, k=8, lower=lower, backend="scan", cache=cache)
                    s = TriangularSolver.plan(
                        a, k=8, lower=lower, backend="distributed",
                        mesh=mesh, shard="rows", cache=cache,
                        validate="fast")
                    d = s.bound.describe()
                    assert d["shard"] == "rows", d
                    assert d["n_shards"] == mesh_shape[1], d
                    rng = np.random.default_rng(7)
                    b1 = rng.standard_normal(700).astype(np.float32)
                    B = rng.standard_normal((700, 3)).astype(np.float32)
                    x1 = np.asarray(s.solve(b1))
                    assert np.array_equal(x1, np.asarray(ref.solve(b1))), (
                        mesh_shape, name, lower, "rhs1")
                    assert np.array_equal(x1, solver_oracle(ref, b1)), (
                        mesh_shape, name, lower, "rhs1 oracle")
                    X = np.asarray(s.solve(B))
                    assert np.array_equal(X, np.asarray(ref.solve(B))), (
                        mesh_shape, name, lower, "mrhs")
                    assert np.array_equal(X, solver_oracle(ref, B)), (
                        mesh_shape, name, lower, "mrhs oracle")
                    # canonical same-compiled-family replay, bit for bit
                    assert np.array_equal(
                        x1, np.asarray(direct_reference(s, b1))
                    ), (mesh_shape, name, lower, "direct_reference")
        print("rowshard-conformance-ok")
    """))


def test_rowshard_elastic_fused_exchange_bitwise():
    """mode="elastic" on shard="rows" executes the fused-barrier
    certificate as fewer exchange rounds — still bitwise equal to the
    single-chip solve, and describe() reports the fusion."""
    print(_run("""
        import numpy as np, jax
        from repro.pipeline import TriangularSolver
        from repro.sparse.generators import narrow_band_lower

        a = narrow_band_lower(900, 0.1, 6, seed=4)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        ref = TriangularSolver.plan(a, k=8, backend="scan")
        s = TriangularSolver.plan(
            a, k=8, backend="distributed", mesh=mesh, shard="rows",
            mode="elastic", slack=8, validate="fast")
        bulk = TriangularSolver.plan(
            a, k=8, backend="distributed", mesh=mesh, shard="rows",
            validate="fast")
        d = s.bound.describe()
        db = bulk.bound.describe()
        ex, exb = d["exchange"], db["exchange"]
        assert ex["rounds"] <= exb["rounds"], (ex, exb)
        assert ex["executed_fusion"] >= 1.0
        b = np.random.default_rng(3).standard_normal(900).astype(np.float32)
        xr = np.asarray(ref.solve(b))
        assert np.array_equal(np.asarray(s.solve(b)), xr)
        assert np.array_equal(np.asarray(bulk.solve(b)), xr)
        print("rowshard-elastic-ok", exb["rounds"], "->", ex["rounds"])
    """))


def test_rowshard_update_values_and_timed():
    """Device-side value refresh equals a fresh bind bitwise; the timed
    path (one dispatch per exchange round) returns the same bits as the
    fused solve and reports per-round halo traffic."""
    print(_run("""
        import numpy as np, jax
        from repro.pipeline import TriangularSolver
        from repro.sparse.generators import erdos_renyi_lower
        from _oracle import solver_oracle

        a = erdos_renyi_lower(600, 3e-3, seed=11)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        s = TriangularSolver.plan(
            a, k=8, backend="distributed", mesh=mesh, shard="rows",
            validate="fast")
        b = np.random.default_rng(5).standard_normal(600).astype(np.float32)
        x0 = np.asarray(s.solve(b))
        assert np.array_equal(x0, solver_oracle(s, b))

        # timed path: same bits, one entry per exchange round
        x_t, steps = s.solve_timed(b)
        assert np.array_equal(np.asarray(x_t), x0)
        ex = s.bound.describe()["exchange"]
        assert len(steps) == ex["rounds"], (len(steps), ex["rounds"])
        assert all("us" in st and "halo_values" in st for st in steps)
        assert sum(st["halo_values"] for st in steps) == \\
            ex["halo_values_per_solve"]

        # numeric refresh == fresh bind, bitwise
        import dataclasses
        rng = np.random.default_rng(12)
        a2 = dataclasses.replace(
            a, data=a.data * rng.uniform(0.5, 2.0, a.nnz))
        s.numeric_update(a2)
        fresh = TriangularSolver.plan(
            a2, k=8, backend="distributed", mesh=mesh, shard="rows")
        x1 = np.asarray(s.solve(b))
        assert np.array_equal(x1, np.asarray(fresh.solve(b)))
        assert np.array_equal(x1, solver_oracle(fresh, b))
        assert not np.array_equal(x1, x0)
        print("rowshard-update-timed-ok")
    """))


def test_rowshard_describe_comm_telemetry():
    """describe() carries the halo comm model next to the all-gather
    baseline; on a banded instance the halo traffic is far below it
    (the acceptance bound: <= 25%)."""
    print(_run("""
        import numpy as np, jax
        from repro.pipeline import TriangularSolver
        from repro.sparse.generators import narrow_band_lower

        a = narrow_band_lower(800, 0.1, 8, seed=6)
        mesh = jax.make_mesh((1, 8), ("data", "model"))
        s = TriangularSolver.plan(
            a, k=8, backend="distributed", mesh=mesh, shard="rows",
            validate="fast")
        d = s.bound.describe()
        assert d["backend"] == "distributed" and d["shard"] == "rows"
        ex = d["exchange"]
        for key in ("mode", "rounds", "halo_pairs",
                    "halo_values_per_solve", "halo_bytes_per_solve",
                    "allgather_values", "allgather_bytes", "halo_ratio",
                    "comm_values_per_solve", "comm_bytes_per_solve"):
            assert key in ex, key
        assert ex["mode"] == "ring"
        assert ex["halo_ratio"] <= 0.25, ex["halo_ratio"]
        assert ex["comm_values_per_solve"] == ex["halo_values_per_solve"]
        assert s.info()["shard"] == "rows"
        print("rowshard-describe-ok", round(ex["halo_ratio"], 4))
    """))
