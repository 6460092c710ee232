"""The least time of HPCG's V-cycle on a chip, counted from its matrices.

As in ``roofline.py`` the work comes from the hierarchy alone (each
level's matrix and f2c map), never from the program's plans or layouts.
One V-cycle with one right-hand side in float32 is:

- at each level, the triangular solves of its sweeps: L + D and
  I + D^-1 U once at the coarsest level, twice at every other level (the
  pre- and the post-smoother), each ``roofline.solve_work(n, entries of
  the triangle, 1)``;
- at each level but the coarsest, two SpMVs: A's rows at the coarse points
  for the restriction, and D^-1 U (A's strictly upper part) on the whole
  level for the post-smoother. Each counts 8 bytes an entry (value and
  column), 4 (rows + 1) of row pointers, 4 bytes for each x entry its
  entries read and for each y entry written, and 2 flops an entry.

The vector updates around them (r - D s, y + s, the restriction's
subtraction and the prolongation) are left out, so the count stays a
lower bound on the work.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

from bench import roofline


def spmv_work(m) -> dict:
    """y = m x for a scipy CSR matrix ``m`` (the rows that y needs)."""
    m = sp.csr_array(m)
    rows, nnz = m.shape[0], m.nnz
    read = np.unique(m.indices).size
    return {"bytes": 8 * nnz + 4 * (rows + 1) + 4 * read + 4 * rows,
            "flops": 2 * nnz}


def parts(levels) -> list:
    """``[(kind, work), ...]`` of one V-cycle on ``[(A, f2c), ...]``
    (finest first): ``kind`` is ``"solve"`` or ``"spmv"``."""
    out = []
    for a, f2c in levels:
        a = sp.csr_array(a)
        n = a.shape[0]
        sweeps = 1 if f2c is None else 2
        for tri in (sp.tril(a), sp.triu(a)):
            out += [("solve", roofline.solve_work(n, tri.nnz, 1))] * sweeps
        if f2c is not None:
            out.append(("spmv", spmv_work(a[np.asarray(f2c)])))
            out.append(("spmv", spmv_work(sp.triu(a, k=1, format="csr"))))
    return out


def least_seconds(levels, peak: dict) -> dict:
    """The least seconds of one V-cycle (``vcycle``) and of its SpMVs
    (``spmv``), each the sum of its parts' least times, with the number
    of SpMVs (``spmv_count``) and which bound sets each part
    (``bounds``)."""
    out = {"vcycle": 0.0, "spmv": 0.0, "spmv_count": 0, "bounds": set()}
    for kind, work in parts(levels):
        s, bound = roofline.least_seconds(work, peak)
        out["vcycle"] += s
        out["bounds"].add(bound)
        if kind == "spmv":
            out["spmv"] += s
            out["spmv_count"] += 1
    out["bounds"] = sorted(out["bounds"])
    return out
