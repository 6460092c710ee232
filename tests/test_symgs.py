"""HPCG's symmetric Gauss–Seidel smoother: the 27-point stencil and the
forward/backward pair of ``gauss_seidel_pair``, against a row-by-row
transcription of ``ComputeSYMGS_ref`` (``tests/_symgs_ref.py``)."""
import numpy as np
import pytest

from _symgs_ref import symgs_from_zero
from repro.pipeline import PlanCache, gauss_seidel_pair
from repro.sparse import CSRMatrix, lower_triangle_of, stencil27_matrix

# float32 solves against a float64 sweep. Every row of these matrices is
# strictly diagonally dominant, so a rounding error made in one row is
# damped, not amplified, in the rows that read it: the error stays a small
# multiple of float32's unit roundoff (6e-8) times the 27 terms a row adds,
# in each of the two solves and the D^-1 U scaling. 1e-5 of the largest
# |x| leaves that estimate (27 * 2 * 6e-8 ≈ 3e-6) threefold room (these
# sweeps read about 1e-7) and sits far under any mistake in order or
# orientation (O(1e-2) and up).
RTOL = 1e-5

PLANS = [
    pytest.param(dict(strategy="hdagg", k=8), id="hdagg-k8"),
    pytest.param(dict(strategy="wavefront", k=32), id="wavefront-k32"),
]


def _dominant_values(a: CSRMatrix, seed: int) -> CSRMatrix:
    """The pattern of ``a`` with seeded values, not symmetric: each
    off-diagonal ~ U[-1, 1], each diagonal the row's absolute sum plus
    U[0.5, 1.5] with a random sign (strictly diagonally dominant)."""
    rng = np.random.default_rng(seed)
    rows = a.row_of_entry()
    diag = a.indices == rows
    data = rng.uniform(-1.0, 1.0, a.nnz)
    data[diag] = 0.0
    dom = np.bincount(rows, weights=np.abs(data), minlength=a.n_rows)
    dom += rng.uniform(0.5, 1.5, a.n_rows)
    dom *= rng.choice([-1.0, 1.0], a.n_rows)
    data[diag] = dom[rows[diag]]
    return CSRMatrix(a.n_rows, a.n_cols, a.indptr, a.indices, data)


def _sweep(pair, r):
    """The sweep from x = 0: the backward solve of the forward's output."""
    fwd, bwd = pair
    return np.asarray(bwd.solve(fwd.solve(r)), np.float64)


MATRICES = {
    "hpcg": lambda: stencil27_matrix(5, 6, 7),
    "dominant": lambda: _dominant_values(stencil27_matrix(5, 6, 7), 11),
}


@pytest.mark.parametrize("plan_kw", PLANS)
@pytest.mark.parametrize("matrix", sorted(MATRICES))
def test_symgs_matches_compute_symgs_ref(matrix, plan_kw):
    a = MATRICES[matrix]()
    pair = gauss_seidel_pair(a, **plan_kw)
    assert pair[0].lower and not pair[1].lower
    rng = np.random.default_rng(3)
    for _ in range(2):
        r = rng.standard_normal(a.n_rows)
        want = symgs_from_zero(a.indptr, a.indices, a.data, r)
        got = _sweep(pair, r)
        assert np.abs(got - want).max() <= RTOL * np.abs(want).max()


def test_symgs_multi_rhs_columns_are_single_sweeps():
    a = MATRICES["dominant"]()
    pair = gauss_seidel_pair(a, strategy="hdagg", k=8)
    rhs = np.random.default_rng(4).standard_normal((a.n_rows, 3))
    got = _sweep(pair, rhs)
    for j in range(3):
        want = symgs_from_zero(a.indptr, a.indices, a.data, rhs[:, j])
        assert np.abs(got[:, j] - want).max() <= RTOL * np.abs(want).max()


def test_pair_triangles_and_shared_cache():
    """``fwd`` holds L + D, ``bwd`` I + D^-1 U; both plan into the one
    cache, and planning the pair again builds no plan."""
    a = MATRICES["dominant"]()
    cache = PlanCache()
    fwd, bwd = gauss_seidel_pair(a, strategy="wavefront", k=8, cache=cache)
    assert cache.stats.hits + cache.stats.misses == 2
    dense = a.to_scipy().toarray()
    d = np.diag(dense)
    x = np.random.default_rng(5).standard_normal(a.n_rows)
    lower = np.tril(dense)
    unit_upper = np.triu(dense) / d[:, None]
    np.testing.assert_allclose(np.asarray(fwd.solve(lower @ x)), x,
                               rtol=0, atol=1e-4)
    np.testing.assert_allclose(np.asarray(bwd.solve(unit_upper @ x)), x,
                               rtol=0, atol=1e-4)
    misses = cache.stats.misses
    gauss_seidel_pair(a, strategy="wavefront", k=8, cache=cache)
    assert cache.stats.misses == misses
    assert cache.stats.hits + cache.stats.misses == 4


def test_sweep_inverts_the_symgs_splitting():
    """From x = 0 the sweep applies M^-1 with HPCG's symmetric
    Gauss–Seidel splitting M = (L + D) D^-1 (D + U), as a preconditioner
    does: M times the sweep's output gives r back."""
    a = MATRICES["dominant"]()
    dense = a.to_scipy().toarray()
    d = np.diag(np.diag(dense))
    m = np.tril(dense) @ np.linalg.inv(d) @ np.triu(dense)
    r = np.random.default_rng(6).standard_normal(a.n_rows)
    got = _sweep(gauss_seidel_pair(a, strategy="hdagg", k=8), r)
    assert np.abs(m @ got - r).max() <= RTOL * np.abs(m).sum(1).max() * (
        np.abs(got).max())


def test_zero_diagonal_is_refused():
    a = stencil27_matrix(2, 2, 2)
    data = a.data.copy()
    data[a.indices == a.row_of_entry()] = 0.0
    with pytest.raises(ValueError):
        gauss_seidel_pair(CSRMatrix(a.n_rows, a.n_cols, a.indptr,
                                    a.indices, data))


def _tril_count(nx, ny, nz):
    """Entries in each triangle (diagonal included), counted by hand: per
    axis of length m, a row and a neighbour offset of -1, 0 or +1 pair up
    (m - 1) + m + (m - 1) = 3m - 2 times, so the whole stencil has
    (3nx - 2)(3ny - 2)(3nz - 2) entries; the matrix is symmetric, so each
    triangle holds half the off-diagonal ones and the diagonal."""
    n = nx * ny * nz
    return ((3 * nx - 2) * (3 * ny - 2) * (3 * nz - 2) + n) // 2


@pytest.mark.parametrize("shape", [(1, 1, 1), (2, 3, 4), (5, 6, 7), (7, 3, 2)])
def test_stencil27_entry_count(shape):
    a = stencil27_matrix(*shape)
    assert lower_triangle_of(a).nnz == _tril_count(*shape)
    s = a.to_scipy()
    assert abs(s - s.T).max() == 0
    assert a.nnz == 2 * _tril_count(*shape) - a.n_rows


def test_stencil27_entry_count_at_hpcg_default_block():
    """hpcg.dat's 104 x 104 x 104 block: n rows, and the entries of each
    triangle that the benchmark's configuration states."""
    assert 104 ** 3 == 1_124_864
    assert _tril_count(104, 104, 104) == 15_457_932


def test_stencil27_rows_as_generate_problem_ref():
    """Row id ix + nx (iy + ny iz); 26 on the diagonal, -1 to each in-box
    neighbour; columns ascending. Row lengths at a corner, an edge, a face
    and an interior point of a 4 x 5 x 6 block: 8, 12, 18 and 27."""
    nx, ny, nz = 4, 5, 6
    a = stencil27_matrix(nx, ny, nz)

    def row(ix, iy, iz):
        return ix + nx * (iy + ny * iz)

    for (ix, iy, iz), length in [((0, 0, 0), 8), ((3, 4, 5), 8),
                                 ((1, 0, 0), 12), ((0, 2, 5), 12),
                                 ((1, 2, 0), 18), ((3, 1, 2), 18),
                                 ((1, 2, 3), 27), ((2, 3, 4), 27)]:
        i = row(ix, iy, iz)
        cols, vals = a.row(i)
        assert len(cols) == length
        assert np.all(np.diff(cols) > 0)
        assert vals[cols == i].tolist() == [26.0]
        assert np.all(vals[cols != i] == -1.0)
        # every column is a neighbour of the 3 x 3 x 3 cube inside the block
        cx, cy, cz = cols % nx, (cols // nx) % ny, cols // (nx * ny)
        assert np.all(np.abs(cx - ix) <= 1)
        assert np.all(np.abs(cy - iy) <= 1)
        assert np.all(np.abs(cz - iz) <= 1)
