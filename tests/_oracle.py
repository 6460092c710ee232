"""The bit-for-bit reference of the scan executor's step.

``kernels/ref.py::sptrsv_ref`` walks an ExecPlan's own [T, k, W]
tensors with the step the scan executor ran before its step layout: W
gathers of x, b and ``x[rows]`` read inside the step, one scatter. The
executor's one gather, hoisted b and sink row must reproduce it bit for
bit, so every executor path (bulk, elastic, timed, banked, resident,
row-sharded, after a value refresh) is checked against it here, one rhs
column at a time.
"""
from __future__ import annotations

from functools import partial

import jax.numpy as jnp
import numpy as np

from repro.kernels.ref import sptrsv_ref


def oracle_solve(plan, b) -> np.ndarray:
    """x with L x = b in plan row order, for b f[n] or f[n, m], at the
    plan's f32 value dtype."""
    b = np.asarray(b, np.float32)
    f32 = jnp.float32
    ref = partial(
        sptrsv_ref, plan.row_ids, plan.col_idx, jnp.asarray(plan.vals, f32),
        jnp.asarray(plan.diag, f32), plan.accum,
    )
    cols = b.reshape(plan.n, -1).T
    x = np.stack(
        [np.asarray(ref(jnp.append(c, f32(0))))[: plan.n] for c in cols],
        axis=1,
    )
    return x.reshape(b.shape)


def solver_oracle(solver, b) -> np.ndarray:
    """``oracle_solve`` through a ``TriangularSolver``'s permutation: b
    and x in the caller's row order."""
    b = np.asarray(b, np.float32)
    x = oracle_solve(solver.exec_plan, b[np.asarray(solver._perm)])
    return x[np.asarray(solver._inv)]
