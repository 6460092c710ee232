"""HPCG's ``ComputeSYMGS_ref`` (hpcg 3.1, src/ComputeSYMGS_ref.cpp),
transcribed row by row in float64 numpy, for the tests.

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
