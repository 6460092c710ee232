"""jit'd public wrappers around the Pallas kernels.

``sptrsv_kernel_solve(plan, b)`` is the drop-in replacement for
``solver.executor.solve_with_plan`` backed by the Pallas kernel; on a TPU
it lowers through Mosaic, on any other backend it runs in interpret mode
(the kernel body executes as ordinary JAX ops).

This module is the device half of the ``pallas`` entry in
``repro.backends`` — bind through the registry
(``get_backend("pallas").bind(plan)``) unless you need the raw pieces.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import ExecPlan
from repro.kernels.sptrsv import sptrsv_pallas


def _pad_steps(a: np.ndarray, mult: int, fill):
    T = a.shape[0]
    pad = (-T) % mult
    if pad == 0:
        return a
    padding = np.full((pad, *a.shape[1:]), fill, dtype=a.dtype)
    return np.concatenate([a, padding], axis=0)


def kernel_plan_arrays(plan: ExecPlan, *, steps_per_tile: int = 8, dtype=jnp.float32):
    """Plan tensors padded to a multiple of the kernel tile, as jax arrays."""
    row_ids = _pad_steps(plan.row_ids, steps_per_tile, plan.n)
    col_idx = _pad_steps(plan.col_idx, steps_per_tile, plan.n)
    vals = _pad_steps(plan.vals.astype(np.dtype(dtype)), steps_per_tile, 0)
    diag = _pad_steps(plan.diag.astype(np.dtype(dtype)), steps_per_tile, 1)
    accum = _pad_steps(plan.accum.astype(np.dtype(dtype)), steps_per_tile, 0)
    return (
        jnp.asarray(row_ids, jnp.int32),
        jnp.asarray(col_idx, jnp.int32),
        jnp.asarray(vals),
        jnp.asarray(diag),
        jnp.asarray(accum),
    )


def solve_with_kernel_arrays(
    arrays, b, *, n: int, steps_per_tile: int, interpret: bool, dtype
):
    """The kernel-calling convention in one place: cast ``b``, append the
    scratch row, run ``sptrsv_pallas`` over pre-built (tile-padded) plan
    ``arrays``, drop the scratch row. Shared by ``bind_kernel_solver``
    and the ``pallas`` entry of ``repro.backends``."""
    b = jnp.asarray(b, dtype=dtype)
    pad = jnp.zeros((1, *b.shape[1:]), dtype=dtype)
    x = sptrsv_pallas(
        *arrays,
        jnp.concatenate([b, pad]),
        steps_per_tile=steps_per_tile,
        interpret=interpret,
    )
    return x[:n]


def bind_kernel_solver(
    plan: ExecPlan,
    *,
    steps_per_tile: int = 8,
    dtype=jnp.float32,
    interpret: bool | None = None,
):
    """Bind the plan tensors once; returns ``solve(b) -> x`` where ``b`` is
    f[n] or f[n, m] (batched multi-RHS)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    arrays = kernel_plan_arrays(plan, steps_per_tile=steps_per_tile, dtype=dtype)
    n = plan.n

    def solve(b):
        return solve_with_kernel_arrays(
            arrays, b, n=n, steps_per_tile=steps_per_tile,
            interpret=interpret, dtype=dtype,
        )

    return solve


def sptrsv_kernel_solve(
    plan: ExecPlan,
    b,
    *,
    steps_per_tile: int = 8,
    dtype=jnp.float32,
    interpret: bool | None = None,
):
    """Solve L x = b with the Pallas kernel. ``b``: f[n] (returns x f[n]) or
    f[n, m] for a batched multi-RHS solve (returns x f[n, m])."""
    solve = bind_kernel_solver(
        plan, steps_per_tile=steps_per_tile, dtype=dtype, interpret=interpret
    )
    return solve(b)
