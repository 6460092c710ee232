"""HPCG's symmetric Gauss–Seidel smoother on one rank's block
(hpcg 3.1: ``GenerateProblem_ref``, ``ComputeSYMGS_ref``, ``ComputeMG_ref``).

The matrix is the 27-point stencil on an nx × ny × nz block: row
``ix + nx (iy + ny iz)``, 26 on the diagonal, -1 to each in-box neighbour
of the 3 × 3 × 3 cube. This is the benchmark's own copy of that assembly,
in scipy. ``ComputeMG_ref`` zeroes x before the pre-smoother, and from
x = 0 the sweep is two triangular solves: the forward one with L + D, then
the backward one with D + U applied to D x1, that is with I + D^-1 U. One
call applies both. The matrix depends on the block alone; the run's seed
moves only the right-hand sides.
"""
import numpy as np
import scipy.sparse as sp


def stencil27(nx: int, ny: int, nz: int) -> sp.csr_array:
    n = nx * ny * nz
    iz, iy, ix = (g.ravel() for g in np.meshgrid(
        np.arange(nz), np.arange(ny), np.arange(nx), indexing="ij"))
    rows, cols, vals = [], [], []
    for dz in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                ok = ((ix + dx >= 0) & (ix + dx < nx) & (iy + dy >= 0)
                      & (iy + dy < ny) & (iz + dz >= 0) & (iz + dz < nz))
                r = np.flatnonzero(ok)
                rows.append(r)
                cols.append(r + dx + nx * (dy + ny * dz))
                vals.append(np.full(r.size, 26.0 if dx == dy == dz == 0
                                    else -1.0))
    a = sp.coo_array((np.concatenate(vals),
                      (np.concatenate(rows).astype(np.int32),
                       np.concatenate(cols).astype(np.int32))),
                     shape=(n, n)).tocsr()
    a.sort_indices()
    return a


def operators(spec: dict, value_seed) -> list:
    """``[(L + D, True), (I + D^-1 U, False)]`` of the block's matrix."""
    del value_seed  # HPCG's matrix is fixed by its block
    a = stencil27(int(spec["nx"]), int(spec["ny"]), int(spec["nz"]))
    lower = sp.tril(a, format="csr")
    upper = sp.csr_array(sp.diags_array(1.0 / a.diagonal())
                         @ sp.triu(a, format="csr"))
    for m in (lower, upper):
        m.sort_indices()
    return [(lower, True), (upper, False)]
