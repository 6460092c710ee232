"""The scan executor's step-slot layout (``solver.executor``).

x lives in step-slot order inside every single-chip scan: plan step t
writes its k results to slots [t·k, t·k + k) with one
``dynamic_update_slice``, and ``TriangularSolver`` folds the slot of
each plan row (``out_index``) into its inverse permutation. Two
guarantees are pinned here:

* **Bit for bit.** Every scan path answers what ``kernels/ref.py``'s
  ``sptrsv_ref`` answers, and what the row-layout scan (x in plan row
  order, one scatter a step: the executor before the slot layout, and
  the row-sharded executor's step today) answers, bit for bit: corpus
  matrices with ``accum`` chains, padded lanes and elastic padding
  steps, both orientations, one and three right-hand sides.
* **Structure.** The single-chip scan programs hold no scatter and one
  contiguous write per plan step; a ``TriangularSolver.solve`` gathers
  no more often than before (b[perm], b in slot order, x at each step,
  x[inv]); the row-sharded executor, which keeps its scatter, lowers to
  the same op kinds as before.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from _oracle import solver_oracle
from repro.autotune import corpus_entry
from repro.pipeline import GroupBank, TriangularSolver, grouped_solve
from repro.solver import executor
from repro.solver.executor import _values_mrhs, _values_single, pad_rhs
from repro.solver.executor import to_device
from repro.solver.rowsharded import (
    _scatter_step,
    laid_out_rows,
    walk_rows,
    with_sink,
)
from repro.sparse import transpose_csr

# small corpus matrices whose plans at k 4, W 2 split rows into accum
# chains and pad lanes, and whose step counts a slack of 6 does not
# divide (so the elastic scans walk padding steps)
MATRICES = ("er_sparse", "band_narrow", "star")
K, WIDTH, SLACK = 4, 2, 6
PATHS = ("bulk", "elastic", "timed", "timed_elastic", "grouped", "banked",
         "continuous")

_SOLVERS = {}


def _solver(name, lower, slack):
    key = (name, lower, slack)
    if key not in _SOLVERS:
        L = corpus_entry(name).matrix()
        a = L if lower else transpose_csr(L)
        _SOLVERS[key] = (a, TriangularSolver.plan(
            a, lower=lower, k=K, width=WIDTH, slack=slack
        ))
    return _SOLVERS[key]


@jax.jit
def _row_layout_scan(rows, cols, vals, diag, accum, b):
    """The scan in the row layout, x f[n + 2(, m)] in plan row order:
    padded lanes write the scratch row n, accum lanes the sink n + 1,
    one scatter a step."""
    values = _values_single if b.ndim == 1 else _values_mrhs
    b_pad = with_sink(pad_rhs(b))
    acc = jnp.zeros((rows.shape[-1], *b.shape[1:]), b.dtype)
    x, _ = walk_rows(
        _scatter_step(values), (jnp.zeros_like(b_pad), acc),
        (rows, cols, vals, diag, accum), b_pad,
    )
    return x[: b.shape[0]]


def _row_layout_solve(solver, b):
    """``_row_layout_scan`` through ``solver``'s permutation."""
    perm = np.asarray(solver._perm)
    steps = to_device(laid_out_rows(solver.exec_plan), jnp.float32)
    x = np.asarray(_row_layout_scan(*steps, jnp.asarray(b[perm])))
    return x[np.argsort(perm)]


def _through(path, solver, B):
    """Solve ``B`` f[n, m] (m = 1: f[n]) along ``path``; caller order."""
    if path in ("bulk", "elastic"):
        return solver.solve(B)
    if path in ("timed", "timed_elastic"):
        return solver.solve_timed(B)[0]
    cols = B.reshape(B.shape[0], -1)
    if path == "grouped":
        X = grouped_solve([solver] * cols.shape[1], cols)
    else:
        bank = GroupBank()
        bank.add("s", solver)
        keys = ["s"] * cols.shape[1]
        X = (bank.solve(keys, cols) if path == "banked"
             else bank.solve_resident(keys, jnp.asarray(cols)))
    return np.asarray(X).reshape(B.shape)


def _bits(x):
    return np.asarray(x, np.float32).view(np.int32)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("m", [1, 3], ids=["rhs1", "rhs3"])
@pytest.mark.parametrize("lower", [True, False], ids=["lower", "upper"])
@pytest.mark.parametrize("name", MATRICES)
def test_slot_layout_is_bitwise_the_row_layout(name, lower, m, path):
    slack = SLACK if "elastic" in path else 0
    a, s = _solver(name, lower, slack)
    plan = s.exec_plan
    assert plan.accum.any() or name == "star"
    assert (plan.row_ids == plan.n).any()
    if slack:
        assert plan.n_steps % slack  # the last macro step pads
    rng = np.random.default_rng([MATRICES.index(name), lower, m])
    B = rng.standard_normal((a.n_rows, m)).astype(np.float32)
    if m == 1:
        B = B[:, 0]
    x = _bits(_through(path, s, B))
    assert np.array_equal(x, _bits(solver_oracle(s, B)))
    assert np.array_equal(x, _bits(_row_layout_solve(s, B)))


@pytest.mark.parametrize("slack", [0, SLACK], ids=["bulk", "elastic"])
@pytest.mark.parametrize("name", MATRICES)
def test_out_index_is_the_finishing_slots(name, slack):
    """``out_index`` puts every plan row on its own finishing lane's
    slot, so it is a bijection onto the finishing slots; the solver's
    inverse permutation is ``out_index`` composed with the plan's."""
    _, s = _solver(name, True, slack)
    plan = s.exec_plan
    out = np.asarray(s.bound.out_index)
    assert np.array_equal(plan.row_ids.ravel()[out], np.arange(plan.n))
    assert not plan.accum.ravel()[out].any()
    finishing = (plan.row_ids < plan.n) & ~plan.accum
    assert np.array_equal(np.sort(out), np.flatnonzero(finishing))
    total_inv = np.argsort(np.asarray(s._perm))
    assert np.array_equal(np.asarray(s._inv), out[total_inv])
    d = s.bound.describe()
    assert d["layout"] == "slots"
    assert d["slots"] == plan.k * (-(-plan.n_steps // max(slack, 1))
                                   * max(slack, 1))


def _shapes(lead, k, W):
    s = lambda shape, dt: jax.ShapeDtypeStruct((*lead, *shape), dt)  # noqa: E731
    return (s((k,), jnp.int32), s((W * k,), jnp.int32),
            s((W, k), jnp.float32), s((k,), jnp.float32),
            s((k,), jnp.bool_))


@pytest.mark.parametrize(
    "name, m, window",
    [("_solve_scan", 1, 0), ("_solve_scan_mrhs", 3, 0),
     ("_solve_elastic", 1, 8), ("_solve_elastic_mrhs", 3, 8)],
)
def test_scan_program_writes_without_scatter(name, m, window):
    """The lowered scan holds no scatter, and one dynamic-update-slice
    for each plan step its loop body walks (the elastic body unrolls a
    window of 8)."""
    n, T, k, W = 50, 16, 4, 3
    lead = (T // window, window) if window else (T,)
    b = jax.ShapeDtypeStruct((n,) if m == 1 else (n, m), jnp.float32)
    txt = getattr(executor, name).lower(*_shapes(lead, k, W), b).as_text()
    assert "stablehlo.scatter" not in txt
    writes = re.findall(r"stablehlo\.dynamic_update_slice\b", txt)
    assert len(writes) == max(window, 1)


@pytest.mark.parametrize("m", [1, 3], ids=["rhs1", "rhs3"])
@pytest.mark.parametrize("slack", [0, SLACK], ids=["bulk", "elastic"])
def test_solve_gathers_as_often_as_before(slack, m):
    """One ``TriangularSolver.solve``, compiled whole on the CPU: the
    gathers are b[perm], b in slot order before the loop, x at every
    plan step the loop body walks, and x[inv] (folded with the slot
    order), as before the slot layout; the scan scatters nothing."""
    a, s = _solver("er_sparse", True, slack)
    b = jnp.ones((a.n_rows,) if m == 1 else (a.n_rows, m), jnp.float32)
    hlo = jax.jit(s.solve).lower(b).compile().as_text()
    assert len(re.findall(r" gather\(", hlo)) == 3 + max(slack, 1)
    assert not re.findall(r" scatter\(", hlo)


# the op kinds of the row-sharded solve's lowering before the slot
# layout, identical for one and several right-hand sides
ROWSHARD_OP_KINDS = {
    "add", "broadcast_in_dim", "compare", "concatenate", "constant",
    "convert", "divide", "dynamic_slice", "gather", "multiply", "reshape",
    "return", "scatter", "select", "slice", "subtract", "while",
}


@pytest.mark.parametrize("batch", [0, 4], ids=["rhs1", "rhs4"])
def test_rowsharded_lowering_keeps_its_op_kinds(batch):
    from repro.core.rowshard import partition_plan
    from repro.solver.rowsharded import lower_rowsharded_solve

    _, s = _solver("er_sparse", True, 0)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    rsp = partition_plan(s.exec_plan, 1)
    txt = lower_rowsharded_solve(rsp, mesh, batch=batch).as_text()
    assert set(re.findall(r"stablehlo\.([a-z_]+)\b", txt)) == ROWSHARD_OP_KINDS


@pytest.mark.parametrize("slack", [0, SLACK], ids=["bulk", "elastic"])
def test_bind_span_carries_the_slot_count(slack):
    """The ``backend.bind`` span reports the x carry's step slots and
    their overhead over n: the memory the layout costs."""
    from repro import obs

    a = corpus_entry("er_sparse").matrix()
    buf = obs.TraceBuffer("slots")
    with obs.tracing(buf):
        s = TriangularSolver.plan(a, k=K, width=WIDTH, slack=slack)
    (rec,) = [r for r in buf.spans() if r.name == "backend.bind"]
    slots = s.bound.describe()["slots"]
    assert rec.args["slots"] == slots >= a.n_rows
    assert rec.args["slot_overhead"] == slots / a.n_rows
