"""The plain reference: forward substitution by level sets, in numpy.

It imports nothing of the program and shares none of its code. A row's
level is one more than the highest level among the rows it reads, so all
rows of one level are solved together. Within a row the off-diagonal
products are added one after another, in column order.

``precision="float64"`` is the reference. ``precision="bfloat16"`` is the
control: the same algorithm with the matrix, the right-hand side and every
product, partial sum, difference and quotient rounded to bfloat16, the
precision below the float32 that the configurations state.

``rel_err`` is the number the benchmark compares: for each answer (a
column), the largest absolute difference over the largest absolute value
of the reference; the largest over all answers.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

PRECISIONS = ("float64", "bfloat16")


def _round(precision):
    if precision == "float64":
        return lambda a: a
    if precision == "bfloat16":
        return lambda a: np.asarray(a).astype(ml_dtypes.bfloat16).astype(
            np.float64)
    raise ValueError(f"precision must be one of {PRECISIONS}")


def _level_sets(n, rows, cols):
    """Rows grouped by level, for the strictly lower entries (rows, cols)."""
    indeg = np.bincount(rows, minlength=n)
    order = np.argsort(cols, kind="stable")
    dep_rows = rows[order]
    dep_ptr = np.concatenate([[0], np.cumsum(np.bincount(cols, minlength=n))])
    frontier = np.flatnonzero(indeg == 0)
    levels = []
    while frontier.size:
        levels.append(frontier)
        starts, ends = dep_ptr[frontier], dep_ptr[frontier + 1]
        cnt = ends - starts
        total = int(cnt.sum())
        if not total:
            break
        offs = np.repeat(starts - np.cumsum(cnt) + cnt, cnt)
        dependents = dep_rows[offs + np.arange(total)]
        uniq, hits = np.unique(dependents, return_counts=True)
        indeg[uniq] -= hits
        frontier = uniq[indeg[uniq] == 0]
    if sum(len(lv) for lv in levels) != n:
        raise ValueError("the matrix is not triangular")
    return levels


class TriangularReference:
    """Solver for one triangular matrix (a scipy CSR array), built once and
    applied to many right-hand sides."""

    def __init__(self, a, lower: bool):
        a = a.tocsr()
        a.sort_indices()
        n = a.shape[0]
        self.n = n
        # an upper-triangular matrix reversed symmetrically is lower
        # triangular: solve that, on the reversed right-hand side
        self.flip = not lower
        indptr = a.indptr.astype(np.int64)
        cols = a.indices.astype(np.int64)
        data = a.data.astype(np.float64)
        rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
        if self.flip:
            rows, cols = n - 1 - rows, n - 1 - cols
            order = np.lexsort((cols, rows))
            rows, cols, data = rows[order], cols[order], data[order]
        on_diag = rows == cols
        if np.count_nonzero(on_diag) != n or np.any(
                np.bincount(rows[on_diag], minlength=n) != 1):
            raise ValueError("the matrix needs one diagonal entry per row")
        if np.any(cols > rows):
            raise ValueError("the matrix is not "
                             + ("lower" if lower else "upper") + " triangular")
        self.diag = np.empty(n)
        self.diag[rows[on_diag]] = data[on_diag]
        off = ~on_diag
        r, c, v = rows[off], cols[off], data[off]
        per_row = np.bincount(r, minlength=n)
        start = np.concatenate([[0], np.cumsum(per_row)])
        # per level: its rows, and a [rows, widest] table of (col, value)
        # padded with (0, 0.0), in column order within each row
        self.levels = []
        for lv in _level_sets(n, r, c):
            width = int(per_row[lv].max()) if lv.size else 0
            pos = np.arange(width)
            valid = pos[None, :] < per_row[lv][:, None]
            ent = np.where(valid, start[lv][:, None] + pos[None, :], 0)
            self.levels.append((
                lv,
                np.where(valid, c[ent] if c.size else 0, 0),
                np.where(valid, v[ent] if v.size else 0.0, 0.0),
            ))

    def solve(self, b, precision: str = "float64") -> np.ndarray:
        rnd = _round(precision)
        b = np.asarray(b, np.float64)
        single = b.ndim == 1
        b2 = b[:, None] if single else b
        if self.flip:
            b2 = b2[::-1]
        b2 = rnd(b2)
        x = np.zeros_like(b2)
        diag = rnd(self.diag)
        for rows, cols, vals in self.levels:
            vals = rnd(vals)
            acc = np.zeros((rows.size, b2.shape[1]))
            for p in range(cols.shape[1]):
                acc = rnd(acc + rnd(vals[:, p, None] * x[cols[:, p]]))
            x[rows] = rnd(rnd(b2[rows] - acc) / diag[rows, None])
        if self.flip:
            x = x[::-1]
        return x[:, 0] if single else np.ascontiguousarray(x)


def chain_solve(refs, b, precision: str = "float64") -> np.ndarray:
    """Apply the operators of one call in turn (L, then L^T for PCG's
    preconditioner)."""
    for ref in refs:
        b = ref.solve(b, precision)
    return b


def rel_err(x, ref) -> float:
    x = np.asarray(x, np.float64)
    ref = np.asarray(ref, np.float64)
    x2 = x.reshape(x.shape[0], -1)
    r2 = ref.reshape(ref.shape[0], -1)
    if not np.all(np.isfinite(x2)):
        return float("inf")
    scale = np.maximum(np.abs(r2).max(axis=0), np.finfo(np.float64).tiny)
    return float((np.abs(x2 - r2).max(axis=0) / scale).max())
