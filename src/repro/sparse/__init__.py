"""Sparse-matrix substrate: CSR structures, generators, IC(0), DAG utilities.

All of this is "inspector side": pure numpy, runs on the host, amortized over
many solves (cf. paper §7.7). Executor-side (JAX/Pallas) code lives in
``repro.solver`` and ``repro.kernels``.
"""
from repro.sparse.csr import (
    CSRMatrix,
    csr_from_coo,
    csr_from_dense,
    csr_to_dense,
    lower_triangle_of,
    pattern_fingerprint,
    permute_symmetric,
    transpose_csr,
)
from repro.sparse.dag import (
    SolveDAG,
    dag_from_lower_csr,
    wavefronts,
    longest_path_length,
    average_wavefront_size,
)
from repro.sparse.generators import (
    erdos_renyi_lower,
    hpcg_f2c,
    mg_block_shapes,
    narrow_band_lower,
    poisson2d_matrix,
    poisson3d_matrix,
    random_spd_band,
    shifted_coupling_lower,
    stencil27_matrix,
)
from repro.sparse.ichol import ichol0

__all__ = [
    "CSRMatrix",
    "csr_from_coo",
    "csr_from_dense",
    "csr_to_dense",
    "lower_triangle_of",
    "pattern_fingerprint",
    "permute_symmetric",
    "transpose_csr",
    "SolveDAG",
    "dag_from_lower_csr",
    "wavefronts",
    "longest_path_length",
    "average_wavefront_size",
    "erdos_renyi_lower",
    "hpcg_f2c",
    "mg_block_shapes",
    "narrow_band_lower",
    "poisson2d_matrix",
    "poisson3d_matrix",
    "random_spd_band",
    "shifted_coupling_lower",
    "stencil27_matrix",
    "ichol0",
]
