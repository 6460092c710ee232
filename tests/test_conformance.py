"""Cross-strategy conformance: every registered strategy must produce a
valid schedule AND a correct solve on every scenario-corpus matrix, in
both orientations, for single and batched right-hand sides.

This is the safety net under ``strategy="auto"``: the selector may pick
*any* registry strategy for *any* matrix, so every (strategy, scenario)
cell has to work — including ``block`` and ``serial``, which the scheduler
unit tests exercise only lightly. Solves are checked against the serial
reference oracle (``repro.solver.reference`` via scipy's
``spsolve_triangular``).

The solve grid iterates the ``repro.backends`` registry, NOT a
hard-coded backend list: the in-process cells run every single-device
backend (``scan``, plus ``pallas`` in interpret mode — this container
has no TPU; interpret executes the same kernel logic through the Pallas
interpreter, so grid coverage carries to the kernel path), and the
``distributed`` backend runs its corpus sweep in a subprocess with a
forced multi-device CPU mesh (jax locks the device count at first init —
the same isolation tests/test_distributed.py uses). The solve grids are
corpus-wide (7 strategies x 9 matrices x 2 orientations x 2 RHS shapes
per in-process backend) and therefore ``slow``-marked; plans are shared
through one module-level ``PlanCache`` so each (strategy, matrix,
orientation, backend) is scheduled and compiled once across the RHS
parametrization. The schedule-validity grid (7 x 9 schedules, each
checked by ``check_validity``) takes seconds, so it runs in tier-1.
"""
import numpy as np
import pytest
from _mesh import run_in_mesh_subprocess

from repro.autotune import corpus_entry, corpus_names
from repro.backends import available_backends
from repro.core import check_validity
from repro.pipeline import (
    PlanCache,
    TriangularSolver,
    available_strategies,
    schedule,
)
from repro.sparse import dag_from_lower_csr, transpose_csr

STRATEGIES = available_strategies()  # all 7 registered strategies
# every registered backend is covered: single-device ones in-process,
# multi-device ones (their own mesh requirement) in the subprocess sweep
IN_PROCESS_BACKENDS = [
    b for b in available_backends() if b != "distributed"
]
K = 8
RTOL = 1e-3  # f32 executor vs f64 reference, relative to max |x|

# one cache for the whole module: the 1-RHS and multi-RHS cells of a
# (strategy, matrix, orientation) triple share a single compiled plan
_CACHE = PlanCache()


def _solver(
    name: str, strategy: str, lower: bool, backend: str = "scan"
) -> TriangularSolver:
    L = corpus_entry(name).matrix()
    a = L if lower else transpose_csr(L)
    kw = {"interpret": True} if backend == "pallas" else {}
    # every freshly built grid cell passes the independent static
    # verifier (repro.analysis) before it solves; cache hits skip it
    return TriangularSolver.plan(
        a, strategy=strategy, k=K, lower=lower, cache=_CACHE,
        backend=backend, validate="fast", **kw,
    )


def _reference(name: str, lower: bool, b: np.ndarray) -> np.ndarray:
    from scipy.sparse.linalg import spsolve_triangular

    L = corpus_entry(name).matrix()
    a = L if lower else transpose_csr(L)
    return spsolve_triangular(a.to_scipy().tocsr(), b, lower=lower)


def test_grid_is_complete():
    """The suite really covers all 7 registered strategies AND all 3
    registered backends (a new registry entry must extend the corpus
    grid, not silently skip it)."""
    assert len(STRATEGIES) == 7
    assert set(STRATEGIES) == {
        "block", "funnel-gl", "growlocal", "hdagg", "serial", "spmp",
        "wavefront",
    }
    assert set(available_backends()) == {"scan", "pallas", "distributed"}
    assert set(IN_PROCESS_BACKENDS) == {"scan", "pallas"}


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("name", corpus_names())
def test_schedule_validity(name, strategy):
    """(a) Def. 2.1 validity for every (strategy, scenario) cell."""
    dag = dag_from_lower_csr(corpus_entry(name).matrix())
    s = schedule(dag, K, strategy=strategy)
    check_validity(dag, s)
    assert s.n == dag.n and s.n_supersteps >= 1


@pytest.mark.slow
@pytest.mark.parametrize("backend", IN_PROCESS_BACKENDS)
@pytest.mark.parametrize("n_rhs", [1, 3], ids=["rhs1", "mrhs"])
@pytest.mark.parametrize("lower", [True, False], ids=["lower", "upper"])
@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("name", corpus_names())
def test_solve_matches_reference(name, strategy, lower, n_rhs, backend):
    """(b) every cell solves to tolerance against the reference oracle,
    on the scan executor and the Pallas kernel (interpret mode)."""
    solver = _solver(name, strategy, lower, backend)
    # str hash is salted per process — derive the seed from the stable
    # corpus order instead so a near-tolerance failure is reproducible
    rng = np.random.default_rng(
        corpus_names().index(name) * 4 + 2 * int(lower) + int(n_rhs > 1)
    )
    n = solver.n
    b = rng.standard_normal((n, n_rhs)) if n_rhs > 1 else rng.standard_normal(n)
    x = np.asarray(solver.solve(b))
    assert x.shape == b.shape
    B = b.reshape(n, -1)
    X = x.reshape(n, -1)
    for j in range(B.shape[1]):
        ref = _reference(name, lower, B[:, j])
        scale = max(np.abs(ref).max(), 1e-30)
        assert np.abs(X[:, j] - ref).max() / scale < RTOL, (
            f"{strategy} on {name} ({'lower' if lower else 'upper'}, "
            f"rhs {j}) exceeded tolerance"
        )


# ------------------------------------------- distributed backend (3rd cell)
@pytest.mark.slow
def test_distributed_backend_conformance_grid():
    """The distributed executor's corpus sweep — the third registered
    backend joins the conformance grid (ROADMAP open item). Needs a
    multi-device mesh, so the whole sweep runs in ONE subprocess with a
    forced 8-CPU-device count: every corpus matrix x {growlocal, serial}
    x both orientations, single- and multi-RHS, solved through
    ``TriangularSolver.plan(backend="distributed")`` on a (2, 4) mesh and
    checked against the scipy oracle. hdagg rides along on the
    shallow-wide matrices (its distributed-relevant regime; on the deep
    corpus shapes its superstep count makes the per-superstep-unrolled
    graph prohibitively slow to compile, and the scan/pallas grid already
    covers it corpus-wide)."""
    out = run_in_mesh_subprocess("""
        import numpy as np, jax
        from scipy.sparse.linalg import spsolve_triangular
        from repro.autotune import corpus_entry, corpus_names
        from repro.pipeline import PlanCache, TriangularSolver
        from repro.sparse import transpose_csr

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cache = PlanCache()
        cells = [(n, s) for n in corpus_names()
                 for s in ("growlocal", "serial")]
        cells += [(n, "hdagg") for n in ("er_sparse", "star", "independent")]
        ran = 0
        for name, strategy in cells:
            L = corpus_entry(name).matrix()
            for lower in (True, False):
                a = L if lower else transpose_csr(L)
                solver = TriangularSolver.plan(
                    a, strategy=strategy, k=4, lower=lower, cache=cache,
                    backend="distributed", mesh=mesh, validate="fast",
                )
                rng = np.random.default_rng(
                    corpus_names().index(name) * 2 + int(lower)
                )
                n = solver.n
                for n_rhs in (1, 3):
                    b = (rng.standard_normal((n, n_rhs)) if n_rhs > 1
                         else rng.standard_normal(n))
                    x = np.asarray(solver.solve(b))
                    assert x.shape == b.shape
                    B, X = b.reshape(n, -1), x.reshape(n, -1)
                    for j in range(B.shape[1]):
                        ref = spsolve_triangular(
                            a.to_scipy().tocsr(), B[:, j], lower=lower
                        )
                        scale = max(np.abs(ref).max(), 1e-30)
                        err = np.abs(X[:, j] - ref).max() / scale
                        assert err < 1e-3, (name, strategy, lower, j, err)
                    ran += 1
        print("dist-grid-ok", ran)
    """, timeout=1800)
    # (9 corpus x 2 strategies + 3 hdagg cells) x 2 orientations x 2 RHS
    assert "dist-grid-ok 84" in out
