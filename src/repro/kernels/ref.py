"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are tested against (interpret=True on
CPU, real lowering on TPU): numerically identical algorithms written with
plain jnp ops, no pallas primitives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sptrsv_ref(row_ids, col_idx, vals, diag, accum, b_pad):
    """Oracle for the superstep SpTRSV kernel.

    Shapes: row_ids int32[T,k]; col_idx int32[T,k,W]; vals f[T,k,W];
    diag f[T,k]; accum bool[T,k]; b_pad f[n+1]. Returns x f[n+1] (the last
    slot is scratch). Sequential over T, vectorized over k — the same
    dataflow the kernel implements with its grid.
    """
    n1 = b_pad.shape[0]
    x0 = jnp.zeros(n1, dtype=b_pad.dtype)
    acc0 = jnp.zeros(row_ids.shape[1], dtype=b_pad.dtype)

    def step(carry, inp):
        x, acc = carry
        rows, cols, v, d, a = inp
        # fixed left-to-right lane reduction, matching the scan executor's
        # _values_single exactly — elementwise IEEE ops per lane keep the
        # oracle bitwise shape-independent (see solver/executor.py)
        for w in range(v.shape[1]):
            acc = acc + v[:, w] * x[cols[:, w]]
        xv = (b_pad[rows] - acc) / d
        x = x.at[rows].set(jnp.where(a, x[rows], xv))
        acc = jnp.where(a, acc, 0.0)
        return (x, acc), None

    (x, _), _ = jax.lax.scan(step, (x0, acc0), (row_ids, col_idx, vals, diag, accum))
    return x


def spmv_block_ref(x_block, idx, vals):
    """Oracle for the gather-SpMV kernel: y[r] = sum_w vals[r,w]*x[idx[r,w]].
    x_block f[m]; idx int32[R,W]; vals f[R,W] -> y f[R]."""
    # repro: blessed-reduction — SpMV oracle, outside the solve's
    # bitwise contract (the solve oracle above folds in fixed order)
    return jnp.einsum("rw,rw->r", vals, x_block[idx])
