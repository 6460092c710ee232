"""A run whose timed path is broken underneath comes out not correct.

Each test skips the look for a chip and drives the rest of a run of a small
cell, with ``TriangularSolver.solve`` (what every cell's window calls, the
service's batches included) broken in one of the ways a cell can be:

- an answer altered where it is produced: the largest entry of each
  answer 1% off;
- half of the batch left out: the right-hand sides of the second half of
  a multi-RHS call replaced by zeros;
- the control: the plain reference in bfloat16 put in the program's place,
  on the matrix each solver was planned for.

The same cells run sound come out correct. No cell here has a state that a
step could leave unchanged, nor an exchange between chips.
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from bench import reference, run
from bench.tests.tiny import make_root
from repro.pipeline import TriangularSolver

SOLVE = TriangularSolver.solve
PLAN = TriangularSolver.plan
CELLS = ["ic0_tiny.precond", "er_tiny.block16", "er_tiny.serve"]


def _altered(self, b):
    x = SOLVE(self, b)
    flat = jnp.ravel(x)
    i = jnp.argmax(jnp.abs(flat))
    return flat.at[i].multiply(1.01).reshape(x.shape)


def _half_left_out(self, b):
    b = jnp.asarray(b)
    if b.ndim == 2 and b.shape[1] > 1:
        b = b.at[:, b.shape[1] // 2:].set(0.0)
    return SOLVE(self, b)


def _plan_with_reference(cls, a, **kw):
    solver = PLAN(a, **kw)
    csr = sp.csr_array((a.data, a.indices, a.indptr), shape=(a.n_rows, a.n_cols))
    solver.bench_reference = reference.TriangularReference(
        csr, kw.get("lower", True))
    return solver


def _bf16_reference(self, b):
    x = self.bench_reference.solve(np.asarray(b, np.float64), "bfloat16")
    return jnp.asarray(x, jnp.float32)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def _correct(root, cell, capsys):
    argv = ["--workload", cell, "--seed", str(2**33 + 1), "--seconds", "0.5",
            "--trace", "0"]
    assert run.main(argv, root=root, require_accelerator=False) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["attempted"] > 0
    return res["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(root, cell, capsys):
    assert _correct(root, cell, capsys) is True


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_not_correct(root, cell, capsys, monkeypatch):
    monkeypatch.setattr(TriangularSolver, "solve", _altered)
    assert _correct(root, cell, capsys) is False


@pytest.mark.parametrize("cell", ["er_tiny.block16", "er_tiny.serve"])
def test_half_the_batch_left_out_is_not_correct(root, cell, capsys,
                                                monkeypatch):
    monkeypatch.setattr(TriangularSolver, "solve", _half_left_out)
    assert _correct(root, cell, capsys) is False


@pytest.mark.parametrize("cell", CELLS)
def test_bf16_control_is_not_correct(root, cell, capsys, monkeypatch):
    monkeypatch.setattr(TriangularSolver, "plan",
                        classmethod(_plan_with_reference))
    monkeypatch.setattr(TriangularSolver, "solve", _bf16_reference)
    assert _correct(root, cell, capsys) is False
