"""Elastic execution mode (``mode="elastic"``, ``core.elastic``).

Three layers of guarantees:

* **Certificate invariants** — ``elastic_transform`` emits a staleness
  certificate (per-step readiness, wave ids inside each slack window,
  fused superstep bounds). The invariants checked here are exactly what
  the executors rely on: steps sharing a wave are mutually independent,
  a step's dependencies are all written in earlier macro-steps or
  earlier waves of the same macro-step, and partial-sum (accum) chains
  never share a wave with their consumer.
* **Bitwise conformance** — an elastic solve must equal the
  bulk-synchronous solve of the SAME backend bit for bit (the macro-step
  bodies replay the identical op sequence; waves only reorder provably
  independent steps). Fast subset in-process; the corpus x orientation x
  RHS x backend grid is ``slow``-marked.
* **Selection** — ``strategy="auto"`` turns elastic on exactly where the
  step-granular cost rule says it pays: deep-DAG regimes ("serial",
  "banded") on elastic-capable backends, never when ``mode="bsp"`` or
  on the distributed backend.
"""
import numpy as np
import pytest
from _hyp import given, settings, strategies as st
from _oracle import solver_oracle

from repro.autotune import clear_selection_memo, corpus_entry, corpus_names
from repro.autotune.corpus import chain_lower
from repro.core import DEFAULT_SLACK, elastic_transform, step_dependencies
from repro.core.plan import compile_plan
from repro.pipeline import PlanCache, TriangularSolver, schedule
from repro.sparse import (
    dag_from_lower_csr,
    erdos_renyi_lower,
    narrow_band_lower,
    transpose_csr,
)

K = 8

# one cache for the module: bulk and elastic plans of a (matrix,
# orientation, backend) cell are shared across the RHS parametrization
_CACHE = PlanCache()


def _plan_for(L, slack):
    s = schedule(dag_from_lower_csr(L), K, strategy="growlocal")
    return compile_plan(L, s)


def _check_certificate(plan, ep):
    """The independence/staleness invariants the executors rely on."""
    T, slack = plan.n_steps, ep.slack
    assert ep.n_macro_steps == -(-T // slack)
    assert ep.n_steps == T
    # fused superstep bounds are a monotone cover of the superstep range
    fb = ep.fused_bounds
    assert fb[0] == 0 and fb[-1] == ep.n_supersteps
    assert np.all(np.diff(fb) >= 1)
    writer_step, _, _ = step_dependencies(plan)
    wave = ep.wave_id
    for t in range(T):
        m, j = divmod(t, slack)
        w = wave[m, j]
        assert 0 <= w < ep.n_waves[m]
        # readiness: every dependency is written strictly before this
        # step's wave opens — earlier macro-step, or earlier wave here
        assert ep.ready_step[t] <= t
        cols = plan.col_idx[t][~plan.accum[t]][:, :]
        for c in np.unique(cols):
            if c >= plan.n:  # scratch/padding gather
                continue
            ws = int(writer_step[c])
            if ws < 0:
                continue
            wm, wj = divmod(ws, slack)
            assert wm < m or (wm == m and wave[wm, wj] < w), (
                f"step {t} (wave {w}) reads row {c} written at step {ws}"
            )
        # accum chains: the carried partial sum is consumed by the NEXT
        # step, which must sit in a strictly later wave (or macro-step)
        if t + 1 < T and plan.accum[t].any():
            m2, j2 = divmod(t + 1, slack)
            assert m2 > m or wave[m2, j2] > w


@pytest.mark.parametrize(
    "make",
    [
        lambda: chain_lower(200, seed=1),
        lambda: narrow_band_lower(300, 0.14, 8, seed=2),
        lambda: erdos_renyi_lower(300, 0.03, seed=3),
    ],
    ids=["chain", "band", "er"],
)
@pytest.mark.parametrize("slack", [1, 3, 8])
def test_certificate_invariants(make, slack):
    plan = _plan_for(make(), slack)
    ep = elastic_transform(plan, slack)
    _check_certificate(plan, ep)
    st_ = ep.stats()
    assert st_["slack"] == slack
    assert st_["n_macro_steps"] == -(-plan.n_steps // slack)
    assert st_["step_fusion"] == pytest.approx(
        plan.n_steps / st_["n_macro_steps"]
    )


def test_slack_validation():
    plan = _plan_for(chain_lower(50, seed=4), 1)
    with pytest.raises(ValueError):
        elastic_transform(plan, 0)
    with pytest.raises(ValueError):
        TriangularSolver.plan(chain_lower(50, seed=4), mode="nope")
    with pytest.raises(ValueError):
        TriangularSolver.plan(chain_lower(50, seed=4), mode="bsp", slack=4)
    # distributed supports elastic now (fused exchange rounds) but still
    # requires a mesh at bind time
    with pytest.raises(ValueError):
        TriangularSolver.plan(
            chain_lower(50, seed=4), backend="distributed", mode="elastic"
        )


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), slack=st.integers(1, 16))
def test_certificate_invariants_property(seed, slack):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(20, 200))
    plan = _plan_for(erdos_renyi_lower(n, 0.05, seed=seed % 1000), slack)
    _check_certificate(plan, elastic_transform(plan, slack))


def test_certificate_invariants_seeded():
    """Deterministic stand-in for the property test (hypothesis is
    optional on this container — _hyp skips @given without it)."""
    rng = np.random.default_rng(20260808)
    for seed in rng.integers(0, 1000, size=5):
        slack = int(rng.integers(1, 17))
        plan = _plan_for(erdos_renyi_lower(150, 0.05, seed=int(seed)), slack)
        _check_certificate(plan, elastic_transform(plan, slack))


# ----------------------------------------------------------- bitwise fast
def _bitwise_cell(a, backend, lower, n_rhs, *, slack=None, cache=None,
                  timed=False):
    """Elastic == bulk on ``backend``, bit for bit; on the scan backend
    both also equal ``kernels/ref.py``'s oracle, and so do the timed
    segment paths when ``timed``."""
    kw = {"interpret": True} if backend == "pallas" else {}
    bulk = TriangularSolver.plan(
        a, strategy="growlocal", k=K, lower=lower, backend=backend,
        cache=cache, **kw,
    )
    el = TriangularSolver.plan(
        a, strategy="growlocal", k=K, lower=lower, backend=backend,
        cache=cache, mode="elastic",
        **({} if slack is None else {"slack": slack}), **kw,
    )
    assert el.info()["mode"] == "elastic"
    rng = np.random.default_rng(7)
    n = a.n_rows
    b = rng.standard_normal((n, n_rhs)) if n_rhs > 1 else rng.standard_normal(n)
    xb = np.asarray(bulk.solve(b))
    xe = np.asarray(el.solve(b))
    assert xb.shape == xe.shape == b.shape
    assert np.array_equal(xb, xe), (
        f"elastic solve diverged from bulk on backend={backend} "
        f"lower={lower} n_rhs={n_rhs}"
    )
    if backend == "scan":
        assert np.array_equal(xb, solver_oracle(bulk, b))
    if timed:
        for solver in (bulk, el):
            assert np.array_equal(np.asarray(solver.solve_timed(b)[0]), xb)


@pytest.mark.parametrize("backend", ["scan", "pallas"])
@pytest.mark.parametrize(
    "make",
    [
        lambda: chain_lower(200, seed=5),
        lambda: narrow_band_lower(400, 0.14, 8, seed=6),
        lambda: erdos_renyi_lower(300, 0.03, seed=7),
    ],
    ids=["chain", "band", "er"],
)
def test_elastic_bitwise_fast(make, backend):
    a = make()
    _bitwise_cell(a, backend, True, 1, timed=True)
    _bitwise_cell(a, backend, True, 3)
    _bitwise_cell(a, backend, True, 16)


@pytest.mark.parametrize("slack", [1, 2, 5, 16])
def test_elastic_bitwise_across_slack(slack):
    """The bound holds for ANY window size, not just the calibrated
    default — slack=1 degenerates to one step per macro-step."""
    a = narrow_band_lower(300, 0.14, 8, seed=8)
    _bitwise_cell(a, "scan", True, 1, slack=slack)


def test_elastic_update_values_bitwise():
    """Refactorization on the elastic binding: same gather contract, same
    bitwise guarantee as the bulk path."""
    import dataclasses

    a = narrow_band_lower(300, 0.14, 8, seed=9)
    rng = np.random.default_rng(10)
    a2 = dataclasses.replace(a, data=a.data * rng.uniform(0.5, 2.0, a.nnz))
    b = rng.standard_normal(a.n_rows)
    for backend in ("scan", "pallas"):
        kw = {"interpret": True} if backend == "pallas" else {}
        el = TriangularSolver.plan(
            a, strategy="growlocal", k=K, backend=backend, mode="elastic",
            **kw,
        )
        fresh = TriangularSolver.plan(
            a2, strategy="growlocal", k=K, backend=backend, mode="elastic",
            **kw,
        )
        el.numeric_update(a2.data)
        x = np.asarray(el.solve(b))
        assert np.array_equal(x, np.asarray(fresh.solve(b)))
        if backend == "scan":
            assert np.array_equal(x, solver_oracle(fresh, b))


# ------------------------------------------------------ stats / selection
@pytest.mark.parametrize(
    "make",
    [lambda: chain_lower(2_000, seed=11),
     lambda: narrow_band_lower(2_000, 0.14, 10, seed=12)],
    ids=["chain", "band"],
)
def test_stats_report_step_fusion(make):
    """ExecPlan.stats() reports barrier counts before/after fusion, and
    deep-DAG plans fuse their scan steps at least 2x (ISSUE acceptance:
    n_macro_steps * 2 <= n_steps)."""
    solver = TriangularSolver.plan(make(), strategy="growlocal", k=K,
                                   mode="elastic")
    stats = solver.exec_plan.stats()
    es = stats["elastic"]
    assert es["slack"] == DEFAULT_SLACK
    assert es["n_steps"] == stats["n_steps"]
    assert es["n_macro_steps"] * 2 <= es["n_steps"]
    assert es["step_fusion"] >= 2.0
    assert es["n_supersteps"] == stats["n_supersteps"]
    assert 1 <= es["n_fused_supersteps"] <= es["n_supersteps"]
    assert es["barrier_fusion"] >= 1.0


def test_auto_selects_elastic_on_deep_regimes():
    """strategy="auto" regression: the selector turns elastic on for
    chain/banded patterns on an elastic-capable backend, leaves it off
    for wide patterns, and never enables it under mode="bsp"."""
    clear_selection_memo()
    cache = PlanCache()
    for a in (chain_lower(2_000, seed=13),
              narrow_band_lower(2_000, 0.14, 10, seed=14)):
        solver = TriangularSolver.plan(
            a, strategy="auto", backend="scan", cache=cache
        )
        sel = solver.selection
        assert sel.regime in ("serial", "banded")
        assert sel.options.slack == DEFAULT_SLACK, sel.as_dict()
        assert all(c.options.slack == DEFAULT_SLACK for c in sel.candidates)
        assert solver.info()["mode"] == "elastic"
        # cost bookkeeping is untouched: the winner's cost is still the
        # §2.2 bsp_cost minimum over the scored shortlist
        assert sel.cost == min(c.cost for c in sel.candidates)
        # and the solve stays correct (bitwise vs the same fixed strategy)
        b = np.random.default_rng(15).standard_normal(a.n_rows)
        ref = TriangularSolver.plan(a, strategy=sel.strategy, backend="scan",
                                    options=sel.options.replace(slack=0))
        assert np.array_equal(np.asarray(solver.solve(b)),
                              np.asarray(ref.solve(b)))
    # shallow/wide: the rule must NOT fire
    wide = erdos_renyi_lower(800, 0.002, seed=16)
    s_wide = TriangularSolver.plan(wide, strategy="auto", backend="scan",
                                   cache=cache)
    assert s_wide.selection.options.slack == 0
    assert s_wide.info()["mode"] == "bsp"
    # mode="bsp" gates the rule off even on a chain
    s_bsp = TriangularSolver.plan(chain_lower(2_000, seed=13),
                                  strategy="auto", backend="scan",
                                  mode="bsp", cache=cache)
    assert s_bsp.selection.options.slack == 0
    assert s_bsp.info()["mode"] == "bsp"


def test_backend_capabilities_advertise_elastic():
    from repro.backends import get_backend

    assert "elastic" in get_backend("scan").capabilities()
    assert "elastic" in get_backend("pallas").capabilities()
    # distributed executes elastic as fused exchange rounds (the fused-
    # barrier certificate, run for real) and also row-sharding
    assert "elastic" in get_backend("distributed").capabilities()
    assert "shard-rows" in get_backend("distributed").capabilities()


# --------------------------------------------------- slow: full corpus grid
@pytest.mark.slow
@pytest.mark.parametrize("backend", ["scan", "pallas"])
@pytest.mark.parametrize("n_rhs", [1, 3], ids=["rhs1", "mrhs"])
@pytest.mark.parametrize("lower", [True, False], ids=["lower", "upper"])
@pytest.mark.parametrize("name", corpus_names())
def test_elastic_conformance_grid(name, lower, n_rhs, backend):
    """Corpus-wide bitwise conformance: every scenario matrix, both
    orientations, single and batched RHS, scan AND pallas (interpret)
    backends — elastic vs bulk of the same backend, bit for bit."""
    L = corpus_entry(name).matrix()
    a = L if lower else transpose_csr(L)
    _bitwise_cell(a, backend, lower, n_rhs, cache=_CACHE)
