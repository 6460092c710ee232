"""The front door for every triangular solve in the repo.

One call chain replaces the hand-wired matrix -> DAG -> scheduler ->
reorder -> ``compile_plan`` -> executor plumbing that used to be copied
into every example, benchmark and the CG driver:

    from repro.pipeline import TriangularSolver, PlanCache, factor_pair

    cache = PlanCache()
    solver = TriangularSolver.plan(L, strategy="funnel-gl", k=8, cache=cache)
    x = solver.solve(b)           # b: f[n] or batched f[n, m]

    fwd, bwd = factor_pair(Lf)    # L y = b, then L^T x = y (PCG's M^{-1})
    fwd, bwd = gauss_seidel_pair(A)      # x = bwd.solve(fwd.solve(r)): one
                                         # symmetric Gauss–Seidel sweep
                                         # from x = 0

``strategy="auto"`` hands the choice to the autotuner (``repro.autotune``:
DAG features -> rule shortlist -> §2.2 cost model; ``tune=True`` adds
measured trials); the outcome is memoized in the ``PlanCache``.

Module map:

  * ``registry``  — named scheduling strategies behind one signature
  * ``solver``    — ``TriangularSolver`` / ``factor_pair`` /
                    ``gauss_seidel_pair`` (plan + bind)
  * ``cache``     — sparsity-pattern-keyed plan cache with hit/miss stats
"""
from repro.pipeline.cache import CacheStats, PlanCache
from repro.pipeline.registry import (
    ScheduleOptions,
    available_strategies,
    get_scheduler,
    register_scheduler,
    schedule,
)
from repro.pipeline.solver import (
    GroupBank,
    TriangularSolver,
    factor_pair,
    gauss_seidel_pair,
    grouped_solve,
)

# the cheap pattern handle (re-exported so serving clients can fingerprint
# once and submit by handle without importing the sparse layer)
from repro.sparse.csr import pattern_fingerprint

__all__ = [
    "CacheStats",
    "PlanCache",
    "pattern_fingerprint",
    "ScheduleOptions",
    "available_strategies",
    "get_scheduler",
    "register_scheduler",
    "schedule",
    "GroupBank",
    "TriangularSolver",
    "factor_pair",
    "gauss_seidel_pair",
    "grouped_solve",
]
