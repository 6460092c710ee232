"""Sparse matrix–vector products on the device, in fixed-width ELL form.

``ell_layout`` lays a CSR matrix (or a subset of its rows) out as two
``[w, rows]`` tables, ``w`` the widest row: entry j of each row in
column j of the table, short rows padded with column 0 and value 0. The
row axis is the tables' last, so a long, narrow operator keeps the
device's lane dimension full. ``spmv`` multiplies: one gather of x at
every slot, a multiply, and a sum over the w slots of each row, folded
in slot order. It runs
as a jitted program of its own, ``_spmv_ell``, so that its runs carry
that name in a device trace.

HPCG's 27-point stencil gives w = 27 for the whole matrix and w = 13 for
its strictly upper part. (``kernels/spmv.py`` keeps the Pallas kernel's
own row-major ELL, off the solve path.)
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.sparse.csr import CSRMatrix


class EllMatrix(NamedTuple):
    """An ``[n_rows, n_cols]`` operator on the device: ``cols`` int32
    ``[w, n_rows]`` and ``vals`` ``[w, n_rows]``; row i of the product is
    ``sum_j vals[j, i] * x[cols[j, i]]``."""

    cols: jax.Array
    vals: jax.Array
    n_cols: int

    @property
    def width(self) -> int:
        return int(self.cols.shape[0])


def ell_layout(
    a: CSRMatrix, rows: Optional[np.ndarray] = None, dtype=jnp.float32
) -> EllMatrix:
    """``a``'s rows ``rows`` (all of them by default), in their order, as
    an ``EllMatrix`` on the device. Built with whole-array index
    arithmetic, not row by row; entries keep their CSR order within a
    row."""
    rows = (np.arange(a.n_rows, dtype=np.int64) if rows is None
            else np.asarray(rows, np.int64))
    start = a.indptr[rows]
    length = a.indptr[rows + 1] - start
    w = max(int(length.max()) if rows.size else 0, 1)
    pos = np.arange(w)[:, None]
    valid = pos < length[None, :]
    ent = np.where(valid, start[None, :] + pos, 0)
    if a.nnz:
        cols = np.where(valid, a.indices[ent], 0)
        vals = np.where(valid, a.data[ent], 0.0)
    else:
        cols = np.zeros(valid.shape, np.int64)
        vals = np.zeros(valid.shape)
    return EllMatrix(
        jnp.asarray(cols, jnp.int32), jnp.asarray(vals, dtype), a.n_cols
    )


@jax.jit
def _spmv_ell(cols, vals, x):
    obs.counter_add("jit.trace.spmv")  # at trace time only
    if x.ndim == 2:
        vals = vals[..., None]
    products = vals * x[cols]
    # a fixed-order fold over the row's slots, in CSR order: the same sum
    # on every run and every backend (a reduce may reassociate)
    acc = products[0]
    for j in range(1, products.shape[0]):
        acc = acc + products[j]
    return acc


def spmv(m: EllMatrix, x) -> jax.Array:
    """``m @ x`` for x f[n_cols] or f[n_cols, k]; the enqueue only."""
    if x.shape[0] != m.n_cols:
        raise ValueError(
            f"x must have {m.n_cols} rows; got shape {tuple(x.shape)}"
        )
    return _spmv_ell(m.cols, m.vals, x)
