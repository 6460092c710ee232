"""IC(0) factor of the 7-point 3-D Poisson matrix on an ``nx``^3 grid.

One call applies PCG's preconditioner: a lower solve with the factor L,
then an upper solve with L^T. The factor depends on ``nx`` alone; the run's
seed moves only the right-hand sides.
"""
from bench.matrices import ichol0, poisson3d_matrix


def operators(spec: dict, value_seed) -> list:
    lf = ichol0(poisson3d_matrix(int(spec["nx"])))
    lt = lf.T.tocsr()
    lt.sort_indices()
    return [(lf, True), (lt, False)]
