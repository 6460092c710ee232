"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests must see the real
single-device CPU; multi-device tests spawn subprocesses (see
tests/_mesh.py and tests/test_distributed.py)."""
import numpy as np
import pytest

from repro.sparse import (
    dag_from_lower_csr,
    erdos_renyi_lower,
    ichol0,
    narrow_band_lower,
    poisson2d_matrix,
)


@pytest.fixture(scope="session")
def er_matrix():
    return erdos_renyi_lower(700, 2e-3, seed=11)


@pytest.fixture(scope="session")
def nb_matrix():
    return narrow_band_lower(700, 0.14, 10, seed=12)


@pytest.fixture(scope="session")
def ichol_matrix():
    return ichol0(poisson2d_matrix(24))


@pytest.fixture(scope="session", params=["er", "nb", "ichol"])
def any_matrix(request, er_matrix, nb_matrix, ichol_matrix):
    return {"er": er_matrix, "nb": nb_matrix, "ichol": ichol_matrix}[request.param]


@pytest.fixture(scope="session")
def any_dag(any_matrix):
    return dag_from_lower_csr(any_matrix)
