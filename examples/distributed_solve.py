"""Distributed SpTRSV: the BSP schedule executed across a device mesh,
barriers realized as all-gathers (DESIGN.md §3). Runs on 8 forced host
devices — the same code path a TPU mesh runs. The whole
matrix -> plan -> mesh binding is one ``TriangularSolver.plan`` call with
``backend="distributed"``.

    PYTHONPATH=src python examples/distributed_solve.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.pipeline import TriangularSolver  # noqa: E402
from repro.solver import solve_lower_scipy  # noqa: E402
from repro.sparse import erdos_renyi_lower  # noqa: E402

K_DEVICES = 4  # 'model' axis: schedule cores = devices
BATCH = 2  # RHS batch over 'data'

L = erdos_renyi_lower(2000, 1e-3, seed=7)
mesh = jax.make_mesh((2, K_DEVICES), ("data", "model"))
solver = TriangularSolver.plan(
    L, strategy="growlocal", backend="distributed", k=K_DEVICES, mesh=mesh
)
print(f"n={L.n_rows} nnz={L.nnz} supersteps={solver.n_supersteps} "
      f"(= all-gathers in the lowered graph)")

# multi-RHS: solver.solve takes f[n, m]; the batch shards over 'data'
b = np.random.default_rng(0).standard_normal((L.n_rows, BATCH))
x = np.asarray(solver.solve(b))

for i in range(BATCH):
    ref = solve_lower_scipy(L, b[:, i])
    err = np.abs(x[:, i] - ref).max() / np.abs(ref).max()
    print(f"rhs {i}: rel err {err:.2e}")
    assert err < 1e-3
print("OK")
