"""HPCG's ``ComputeSYMGS_ref`` (hpcg 3.1, src/ComputeSYMGS_ref.cpp),
transcribed row by row in float64 numpy, for the tests, with
``ComputeSPMV_ref``, ``GenerateCoarseProblem``'s ``f2cOperator`` and
``ComputeMG_ref`` around it.

It shares no code with the program: it reads a CSR matrix's raw arrays
and walks them the way the reference code walks ``mtxIndL`` and
``matrixValues``. ``ComputeMG_ref`` zeroes x before the pre-smoother, so
``symgs_from_zero`` starts from x = 0.
"""
from __future__ import annotations

import numpy as np


def compute_symgs(indptr, indices, values, r, x) -> np.ndarray:
    """One symmetric sweep on x (updated in place and returned): a forward
    loop over the rows, then a backward loop. Each row subtracts every
    entry's product, its own diagonal included, and adds the diagonal's
    product back before dividing by it, as the reference does."""
    indptr = np.asarray(indptr)
    indices = np.asarray(indices)
    values = np.asarray(values, np.float64)
    r = np.asarray(r, np.float64)
    nrow = len(indptr) - 1
    diagonal = np.empty(nrow)
    for i in range(nrow):
        for j in range(indptr[i], indptr[i + 1]):
            if indices[j] == i:
                diagonal[i] = values[j]
    for i in list(range(nrow)) + list(range(nrow - 1, -1, -1)):
        total = r[i]
        for j in range(indptr[i], indptr[i + 1]):
            total -= values[j] * x[indices[j]]
        total += x[i] * diagonal[i]
        x[i] = total / diagonal[i]
    return x


def symgs_from_zero(indptr, indices, values, r) -> np.ndarray:
    """The sweep from x = 0, for one right-hand side ``r``."""
    return compute_symgs(indptr, indices, values, r,
                         np.zeros(len(indptr) - 1))


def compute_spmv(indptr, indices, values, x) -> np.ndarray:
    """``ComputeSPMV_ref``: y = A x, row by row."""
    y = np.zeros(len(indptr) - 1)
    for i in range(len(y)):
        total = 0.0
        for j in range(indptr[i], indptr[i + 1]):
            total += values[j] * x[indices[j]]
        y[i] = total
    return y


def generate_f2c(nxf, nyf, nzf) -> np.ndarray:
    """``GenerateCoarseProblem``'s ``f2cOperator``: coarse row
    i_zc nxc nyc + i_yc nxc + i_xc sits on fine row
    izf nxf nyf + iyf nxf + ixf, with (ixf, iyf, izf) = 2 (i_xc, i_yc, i_zc)."""
    nxc, nyc, nzc = nxf // 2, nyf // 2, nzf // 2
    f2c = np.zeros(nxc * nyc * nzc, np.int64)
    for i_zc in range(nzc):
        izf = 2 * i_zc
        for i_yc in range(nyc):
            iyf = 2 * i_yc
            for i_xc in range(nxc):
                ixf = 2 * i_xc
                current_coarse_row = i_zc * nxc * nyc + i_yc * nxc + i_xc
                current_fine_row = izf * nxf * nyf + iyf * nxf + ixf
                f2c[current_coarse_row] = current_fine_row
    return f2c


def compute_mg(levels, r, out=None) -> np.ndarray:
    """``ComputeMG_ref`` (hpcg 3.1, src/ComputeMG_ref.cpp) for one
    right-hand side, one pre- and one post-smoothing sweep a level.
    ``levels`` lists each level's ``(indptr, indices, values, f2c)``,
    finest first; the coarsest level's ``f2c`` is None (no ``mgData``).
    Where ``out`` is a list, each level's result is put at the front of it
    as the level returns, so that it ends finest first."""
    indptr, indices, values, f2c = levels[0]
    r = np.asarray(r, np.float64)
    x = np.zeros(len(indptr) - 1)  # ZeroVector(x)
    compute_symgs(indptr, indices, values, r, x)
    if f2c is not None:
        axf = compute_spmv(indptr, indices, values, x)
        rc = np.zeros(len(f2c))  # ComputeRestriction_ref
        for i in range(len(f2c)):
            rc[i] = r[f2c[i]] - axf[f2c[i]]
        xc = compute_mg(levels[1:], rc, out)
        for i in range(len(f2c)):  # ComputeProlongation_ref
            x[f2c[i]] += xc[i]
        compute_symgs(indptr, indices, values, r, x)
    if out is not None:
        out.insert(0, x.copy())
    return x
