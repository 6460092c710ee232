"""The benchmark's own input generators: each configuration's matrices.

Copied from the program's ``repro.sparse`` (``generators.erdos_renyi_lower``,
``generators.poisson3d_matrix``, ``ichol.ichol0``), so that no change to the
program can move the benchmark's inputs. Changes from the originals:

- CSR assembly uses ``scipy.sparse`` (sorted indices, no duplicates) in
  place of the program's ``csr_from_coo``;
- ``erdos_renyi_lower`` draws the pattern from the configuration's fixed
  ``pattern_seed`` and the values from the run's seed, so that every run
  schedules the same pattern (the same plan shapes, the same compiled
  programs) on new numbers.

A matrix here is a ``scipy.sparse.csr_array`` in float64.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def _csr(n, rows, cols, vals) -> sp.csr_array:
    a = sp.coo_array((vals, (rows.astype(np.int32), cols.astype(np.int32))),
                     shape=(n, n)).tocsr()
    a.sum_duplicates()
    a.sort_indices()
    return a


def paper_values(rng: np.random.Generator, n_off: int, n_diag: int):
    """arXiv 2503.05408 §6.2.4: off-diagonal entries ~ U[-2, 2];
    |diagonal| ~ LogUniform[1/2, 2] with a uniform random sign."""
    off = rng.uniform(-2.0, 2.0, size=n_off)
    mag = np.exp(rng.uniform(np.log(0.5), np.log(2.0), size=n_diag))
    sign = rng.choice([-1.0, 1.0], size=n_diag)
    return off, mag * sign


def erdos_renyi_lower(n: int, p: float, *, pattern_seed: int,
                      value_seed) -> sp.csr_array:
    """§6.2.4: entry (i, j), i > j, is non-zero with probability p, plus a
    full diagonal."""
    rng = np.random.default_rng(pattern_seed)
    counts = rng.binomial(np.arange(n), p)
    rows_list, cols_list = [], []
    for i in np.nonzero(counts)[0]:
        c = rng.choice(i, size=counts[i], replace=False)
        rows_list.append(np.full(len(c), i, dtype=np.int64))
        cols_list.append(c.astype(np.int64))
    rows = np.concatenate(rows_list) if rows_list else np.empty(0, np.int64)
    cols = np.concatenate(cols_list) if cols_list else np.empty(0, np.int64)
    off, diag = paper_values(np.random.default_rng(value_seed), len(rows), n)
    diag_idx = np.arange(n, dtype=np.int64)
    return _csr(n, np.concatenate([rows, diag_idx]),
                np.concatenate([cols, diag_idx]), np.concatenate([off, diag]))


def poisson3d_matrix(nx: int) -> sp.csr_array:
    """SPD 7-point Laplacian on an nx^3 grid: 6 on the diagonal, -1 to each
    grid neighbour, Dirichlet boundary (PyAMG's ``gallery.poisson`` in 3-D)."""
    n = nx ** 3
    idx = np.arange(n, dtype=np.int64).reshape(nx, nx, nx)
    rows, cols, vals = [idx.ravel()], [idx.ravel()], [np.full(n, 6.0)]
    for a, b in (
        (idx[:, :, 1:].ravel(), idx[:, :, :-1].ravel()),
        (idx[:, 1:, :].ravel(), idx[:, :-1, :].ravel()),
        (idx[1:, :, :].ravel(), idx[:-1, :, :].ravel()),
    ):
        rows.extend([a, b])
        cols.extend([b, a])
        vals.extend([np.full(len(a), -1.0)] * 2)
    return _csr(n, np.concatenate(rows), np.concatenate(cols),
                np.concatenate(vals))


def ichol0(a: sp.csr_array) -> sp.csr_array:
    """IC(0): lower-triangular L on tril(A)'s pattern with A ~ L L^T
    (up-looking, one row at a time). Raises where a pivot is not positive:
    the benchmark's matrices are SPD M-matrices, for which IC(0) exists."""
    tril = sp.tril(a, format="csr")
    tril.sort_indices()
    n = tril.shape[0]
    indptr = tril.indptr.astype(np.int64)
    indices = tril.indices.astype(np.int64)
    vals = tril.data.astype(np.float64).copy()
    rows = np.repeat(np.arange(n), np.diff(indptr))
    diag_pos = np.nonzero(indices == rows)[0]
    if len(diag_pos) != n:
        raise ValueError("IC(0) needs a structurally full diagonal")
    for i in range(n):
        lo = int(indptr[i])
        ti = int(diag_pos[i])
        for t in range(lo, ti):
            j = int(indices[t])
            # L[i,j] = (A[i,j] - sum_{k<j} L[i,k] L[j,k]) / L[j,j]
            s = vals[t]
            pi, pj = lo, int(indptr[j])
            tj = int(diag_pos[j])
            while pi < t and pj < tj:
                ci, cj = indices[pi], indices[pj]
                if ci == cj:
                    s -= vals[pi] * vals[pj]
                    pi += 1
                    pj += 1
                elif ci < cj:
                    pi += 1
                else:
                    pj += 1
            vals[t] = s / vals[tj]
        s = vals[ti] - float(np.sum(vals[lo:ti] ** 2))
        if s <= 0.0:
            raise np.linalg.LinAlgError(f"IC(0) pivot {i} is not positive")
        vals[ti] = np.sqrt(s)
    return sp.csr_array((vals, tril.indices, tril.indptr), shape=(n, n))
