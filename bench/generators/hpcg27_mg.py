"""HPCG's multigrid hierarchy on one rank's block (hpcg 3.1:
``GenerateProblem_ref``, ``GenerateCoarseProblem``, ``ComputeMG_ref``).

Level 0 is the 27-point stencil on the nx × ny × nz block
(``hpcg27.stencil27``); each coarser level is the same stencil on a block
of half the sides, generated geometrically as HPCG does (no Galerkin
product), down to ``levels`` levels. Coarse point (ixc, iyc, izc) sits on
fine point (2 ixc, 2 iyc, 2 izc): HPCG's ``f2cOperator``, written here
from ``GenerateCoarseProblem``'s loop. The hierarchy depends on the block
alone; a run's seed moves only the right-hand sides.
"""
import numpy as np

from bench.generators.hpcg27 import stencil27


def f2c(nx: int, ny: int, nz: int) -> np.ndarray:
    """The fine row of each coarse row: row ``izc nxc nyc + iyc nxc + ixc``
    of the coarse block maps to ``2 izc nx ny + 2 iyc nx + 2 ixc``."""
    nxc, nyc, nzc = nx // 2, ny // 2, nz // 2
    izc = np.arange(nzc)[:, None, None]
    iyc = np.arange(nyc)[None, :, None]
    ixc = np.arange(nxc)[None, None, :]
    return (2 * izc * nx * ny + 2 * iyc * nx + 2 * ixc).ravel()


def levels(spec: dict) -> list:
    """``[(A, f2c), ...]`` finest first: each level's scipy CSR matrix in
    float64, and its f2c map (int64[n_coarse]; None at the coarsest)."""
    shape = [int(spec[k]) for k in ("nx", "ny", "nz")]
    count = int(spec["levels"])
    step = 1 << (count - 1)
    if any(s % step for s in shape):
        raise ValueError(f"{count} levels need sides divisible by {step}; "
                         f"got {shape}")
    out = []
    for i in range(count):
        nx, ny, nz = (s >> i for s in shape)
        out.append((stencil27(nx, ny, nz),
                    f2c(nx, ny, nz) if i < count - 1 else None))
    return out
