"""The plain reference against scipy, and the control against the limit."""
import json

import numpy as np
import pytest
import scipy.sparse.linalg as sla

from bench import reference
from bench.matrices import erdos_renyi_lower, ichol0, poisson3d_matrix
from bench.tests.tiny import LIMITS


def _matrices():
    lf = ichol0(poisson3d_matrix(5))
    lt = lf.T.tocsr()
    lt.sort_indices()
    er = erdos_renyi_lower(2000, 3e-3, pattern_seed=3, value_seed=4)
    return {"ic0_lower": (lf, True), "ic0_upper": (lt, False),
            "er_lower": (er, True)}


@pytest.mark.parametrize("name", ["ic0_lower", "ic0_upper", "er_lower"])
@pytest.mark.parametrize("m", [1, 5])
def test_reference_matches_scipy(name, m):
    a, lower = _matrices()[name]
    rng = np.random.default_rng(0)
    b = rng.standard_normal(a.shape[0] if m == 1 else (a.shape[0], m))
    want = sla.spsolve_triangular(a.tocsr(), b, lower=lower)
    got = reference.TriangularReference(a, lower).solve(b)
    assert reference.rel_err(got, want) < 1e-13


@pytest.mark.parametrize("name", ["ic0_lower", "er_lower"])
def test_control_fails_the_limit(name):
    """bfloat16, the precision below the configurations' float32, reads
    far above the limit; float32 reads far below it."""
    a, lower = _matrices()[name]
    ref = reference.TriangularReference(a, lower)
    b = np.random.default_rng(1).standard_normal((a.shape[0], 8))
    want = ref.solve(b)
    assert reference.rel_err(ref.solve(b, "bfloat16"), want) > 10 * LIMITS["rel_err"]
    f32 = sla.spsolve_triangular(a.tocsr().astype(np.float32),
                                 b.astype(np.float32), lower=lower)
    assert reference.rel_err(f32, want) < LIMITS["rel_err"] / 10


def test_control_readings_of_a_cell(tmp_path):
    from bench.control import readings
    from bench.spec import Spec
    from bench.tests.tiny import make_root

    spec = Spec(make_root(tmp_path))
    for cell in ("ic0_tiny.precond", "er_tiny.block16"):
        r = readings(spec, cell, 2**31 + 5)
        assert r["correct"] is False
        assert r["checks"]["rel_err"]["value"] > r["checks"]["rel_err"]["limit"]
        json.dumps(r)


def test_not_triangular_is_refused():
    a, _ = _matrices()["ic0_upper"]
    with pytest.raises(ValueError):
        reference.TriangularReference(a, True)


def test_rel_err_of_a_non_finite_answer():
    assert reference.rel_err(np.array([np.nan, 1.0]), np.ones(2)) == np.inf
