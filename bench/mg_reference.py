#!/usr/bin/env python3
"""The plain reference of HPCG's V-cycle, and the control of its cell.

``MGReference(levels)`` applies ``ComputeMG_ref`` (hpcg 3.1) in numpy on
the hierarchy of ``generators/hpcg27_mg.py``: at every level but the
coarsest, the symmetric Gauss–Seidel sweep from x = 0, the residual at the
coarse points, the next level on it, the prolongation x[f2c] += xc and the
sweep from that x; at the coarsest level the sweep from zero. It imports
nothing of the program. Each sweep's two triangular solves are
``reference.TriangularReference``'s (L + D, then I + D^-1 U); the sweep
from x0 solves them on r - D s and y + s, s = D^-1 U x0, which is
``ComputeSYMGS_ref``'s forward and backward loop (the bench tests check it
against those loops). The SpMV, restriction and prolongation are its own:
each row's products added one after another in column order.

A run compares more than z (``compare``). From each coarse level down,
the program's V-cycle on the reference's restricted residual there is held
to the reference's result on it, each against its own largest value: a
coarse level's share of z is so small (dropping the coarsest level moves a
16^3 hierarchy's z by 3e-6 of its largest value) that z alone would pass a
hierarchy whose coarse levels are wrong.

``precision="float64"`` is the reference. ``precision="bfloat16"`` is the
control: the matrices, the right-hand side and every product, partial sum,
difference and quotient rounded to bfloat16, as ``reference.py`` does.

The control's command line mirrors ``control.py`` for the V-cycle cell:

    python bench/mg_reference.py --control --workload hpcg_104.vcycle \\
        --seeds 11,12,13

For each seed it builds the cell's hierarchy and right-hand sides exactly
as a run does, answers the whole pool (one [n, pool] block) in bfloat16
and in float64, from level 0 and from each coarse level's float64
restricted residual, draws the cell's ``sample`` answers from it with the
seed, and judges them with a run's own comparison (``compare``, then
``run.checks`` and ``run.passed``): ``correct`` has to be false. The
benchmark's runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np
import scipy.sparse as sp

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import reference  # noqa: E402


class RowProducts:
    """``rows`` of a scipy CSR matrix as a table padded to its widest row,
    in column order, for y = M[rows] x one column of products at a time."""

    def __init__(self, a, rows=None):
        a = a.tocsr()
        a.sort_indices()
        rows = np.arange(a.shape[0]) if rows is None else np.asarray(rows)
        start = a.indptr[rows].astype(np.int64)
        length = a.indptr[rows + 1] - start
        width = int(length.max()) if rows.size else 0
        pos = np.arange(width)
        valid = pos[None, :] < length[:, None]
        ent = np.where(valid, start[:, None] + pos[None, :], 0)
        self.cols = np.where(valid, a.indices[ent] if a.nnz else 0, 0)
        self.vals = np.where(valid, a.data[ent] if a.nnz else 0.0, 0.0)

    def apply(self, x, rnd) -> np.ndarray:
        vals = rnd(self.vals)
        acc = np.zeros((self.cols.shape[0], x.shape[1]))
        for p in range(self.cols.shape[1]):
            acc = rnd(acc + rnd(vals[:, p, None] * x[self.cols[:, p]]))
        return acc


class SweepReference:
    """``ComputeSYMGS_ref`` on one level: from zero, or from x0."""

    def __init__(self, a):
        a = sp.csr_array(a)
        d = a.diagonal()
        inv_d = sp.diags_array(1.0 / d)
        self.d = d[:, None]
        self.fwd = reference.TriangularReference(sp.tril(a, format="csr"),
                                                 True)
        self.bwd = reference.TriangularReference(
            sp.csr_array(inv_d @ sp.triu(a, format="csr")), False)
        self.upper = RowProducts(sp.csr_array(inv_d @ sp.triu(a, k=1,
                                                              format="csr")))

    def apply(self, r, x0, precision, rnd) -> np.ndarray:
        if x0 is None:
            return self.bwd.solve(self.fwd.solve(r, precision), precision)
        s = self.upper.apply(x0, rnd)
        y = self.fwd.solve(rnd(r - rnd(rnd(self.d) * s)), precision)
        return self.bwd.solve(rnd(y + s), precision)


class MGReference:
    """``ComputeMG_ref`` on ``[(A, f2c), ...]`` (finest first; the
    coarsest level's f2c is None), built once, applied to many r."""

    def __init__(self, levels):
        self.levels = [(SweepReference(a),
                        None if f2c is None else np.asarray(f2c),
                        None if f2c is None else RowProducts(a, f2c))
                       for a, f2c in levels]

    def apply(self, r, precision: str = "float64", level: int = 0
              ) -> np.ndarray:
        """The V-cycle of levels ``level``, ``level`` + 1, ... on r, f[n_i]
        or f[n_i, m] (each column its own V-cycle): z = M^-1 r at level 0,
        at level i what level i - 1 applies to its restricted residual."""
        return self._run(r, precision, level)[1][level]

    def levels_of(self, r, precision: str = "float64"):
        """``(rhs, out)`` of the V-cycle of r, level by level, finest first:
        each level's right-hand side (r, then each restricted residual) and
        its result (z, then each correction x_c the level above adds)."""
        return self._run(r, precision, 0)

    def _run(self, r, precision, level):
        rnd = reference._round(precision)
        r = np.asarray(r, np.float64)
        single = r.ndim == 1
        rhs, out = [None] * len(self.levels), [None] * len(self.levels)
        self._cycle(level, rnd(r[:, None] if single else r), precision, rnd,
                    rhs, out)
        if single:
            rhs = [v if v is None else v[:, 0] for v in rhs]
            out = [v if v is None else v[:, 0] for v in out]
        return rhs, out

    def _cycle(self, i, r, precision, rnd, rhs, out):
        sweep, f2c, restrict = self.levels[i]
        rhs[i] = r
        x = sweep.apply(r, None, precision, rnd)
        if f2c is not None:
            rc = rnd(r[f2c] - restrict.apply(x, rnd))
            x = x.copy()
            x[f2c] = rnd(x[f2c] + self._cycle(i + 1, rc, precision, rnd,
                                              rhs, out))
            x = sweep.apply(r, x, precision, rnd)
        out[i] = x
        return x


def compare(answers, want, got_levels: dict, want_levels: list) -> dict:
    """A run's comparison. ``answers`` holds ``(j, z)``, the timed answers
    to pool entries j, and ``want`` the reference's z, [n, pool].
    ``got_levels`` maps each distinct sampled j to the program's V-cycle
    from each coarse level i (1, 2, ...) on the reference's restricted
    residual there, and ``want_levels`` holds the reference's results at
    those levels, ``[n_i, pool]`` each. Each reading is
    ``reference.rel_err`` (widest gap over the largest reference value):
    ``answer``, the widest over the answers; ``levels``, for each coarse
    level the widest over the entries. ``rel_err`` is the widest of all,
    so that a coarse level that is wrong fails the run even where its
    share of z is too small to show in the answer."""
    answer = max(reference.rel_err(x, want[:, j]) for j, x in answers)
    levels = [max(reference.rel_err(got[i], want_levels[i][:, j])
                  if len(got) == len(want_levels) else float("inf")
                  for j, got in got_levels.items())
              for i in range(len(want_levels))]
    return {"rel_err": max([answer] + levels), "answer": answer,
            "levels": levels}


def control(spec, workload: str, seed: int) -> dict:
    """The bfloat16 reference in the program's place, judged as a run is."""
    from bench import harness, run

    cell = spec.cell(workload)
    config = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    generator = spec.plugin("generators", config["matrix"]["generator"])
    levels = generator.levels(config["matrix"])
    ref = MGReference(levels)
    pool = harness.rhs_pool(seed, int(traffic["pool"]), levels[0][0].shape[0])
    rhs, want = ref.levels_of(pool.T)
    z = ref.apply(pool.T, "bfloat16")
    pick = harness.rng(seed, 2).integers(0, len(pool), int(traffic["sample"]))
    coarse = [ref.apply(rhs[i], "bfloat16", i) for i in range(1, len(rhs))]
    cmp = compare([(j, z[:, j]) for j in pick], want[0],
                  {j: [x[:, j] for x in coarse] for j in set(pick.tolist())},
                  want[1:])
    chk = run.checks({"failed": 0, "checks": {
        "rel_err": cmp["rel_err"], "answers_compared": len(pick)}},
        spec.limits(workload))
    return {"workload": workload, "seed": seed,
            "correct": run.passed(chk), "checks": chk,
            "answer_rel_err": cmp["answer"], "level_rel_err": cmp["levels"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--control", action="store_true", required=True,
                    help="run the bfloat16 control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from bench.spec import Spec

    spec = Spec(REPO)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(control(spec, args.workload, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
