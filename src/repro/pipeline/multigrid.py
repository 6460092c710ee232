"""HPCG's multigrid preconditioner (hpcg 3.1, ``ComputeMG_ref``) on the
device, through the pipeline's triangular solvers.

    vc = VCycle.build((104, 104, 104), levels=4,
                      plans=dict(strategy="hdagg", k=128, slack=8))
    z = vc(r)        # z = M^-1 r: one V-cycle, enqueued, never synced
    xc = vc.from_level(1, rc)  # what level 0 applies to its residual rc

Each level is HPCG's 27-point stencil on a block whose sides halve from
level to level (``GenerateCoarseProblem``: geometric, not a Galerkin
product), linked by HPCG's ``f2cOperator``. A V-cycle applies
``ComputeMG_ref`` literally:

    x = 0; SymGS(r, x)                       # pre-smoother, from zero
    rc = r[f2c] - (A x)[f2c]                 # residual, by injection
    xc = V-cycle of the next level on rc
    x[f2c] += xc                             # prolongation
    SymGS(r, x)                              # post-smoother, from x

and the coarsest level only ``SymGS(r, x)`` from zero. On four levels that
is 14 triangular solves and 6 SpMVs, every solve a
``TriangularSolver.solve`` and every SpMV a ``repro.solver.ell`` program;
nothing returns to the host inside a V-cycle.

``SymGSSweep`` is the symmetric Gauss–Seidel sweep from any x with the
pair of ``gauss_seidel_pair``. With D the diagonal, L and U the strict
triangles and s = D^-1 U x0, ``ComputeSYMGS_ref``'s forward loop solves
(L + D) y = r - U x0 = r - D s, and its backward loop (D + U) x = r - L y
= D y + U x0, that is (I + D^-1 U) x = y + s. So

    y = fwd.solve(r - D s);  x = bwd.solve(y + s)

with the two plans the from-zero sweep already has, and one SpMV more.

Tracing (``repro.obs``): span ``mg.build`` per level in ``build`` (args
``level``, ``n``; the coarse matrix, f2c, SpMV layouts and device puts,
not the plans, which keep their own spans); span ``mg.vcycle`` around the
host's enqueue of one V-cycle; counters ``mg.levels`` (levels built),
``mg.spmv`` (SpMV dispatches), ``mg.sweep.from_x`` (sweeps from x != 0)
and ``jit.trace.spmv`` (traces of the SpMV program).
"""
from __future__ import annotations

from typing import Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.pipeline.cache import PlanCache
from repro.pipeline.solver import TriangularSolver, gauss_seidel_pair
from repro.solver.ell import EllMatrix, ell_layout, spmv
from repro.sparse.csr import CSRMatrix, csr_from_coo
from repro.sparse.generators import hpcg_f2c, mg_block_shapes, stencil27_matrix


def _spmv(m: EllMatrix, x) -> jax.Array:
    obs.counter_add("mg.spmv")
    return spmv(m, x)


@jax.jit
def _gs_rhs(r, d, s):
    return r - (d if r.ndim == 1 else d[:, None]) * s


@jax.jit
def _restrict(r, f2c, axc):
    return r[f2c] - axc


@jax.jit
def _prolong(x, f2c, xc):
    return x.at[f2c].add(xc)


class SymGSSweep:
    """One symmetric Gauss–Seidel sweep on ``a`` (HPCG's
    ``ComputeSYMGS_ref``) from x = 0 or from any x: ``fwd`` and ``bwd``
    are ``gauss_seidel_pair``'s solvers, ``d`` the diagonal and ``upper``
    the strict part of ``I + D^-1 U`` (D^-1 U) on the device. ``VCycle.build``
    builds every sweep; on one level it is the sweep of any matrix ``a``
    with a block's row count:
    ``VCycle.build(shape, a=a, levels=1).levels[0].sweep``."""

    def __init__(self, fwd: TriangularSolver, bwd: TriangularSolver,
                 d: jax.Array, upper: EllMatrix):
        self.fwd, self.bwd, self.d, self.upper = fwd, bwd, d, upper

    @staticmethod
    def operators(a: CSRMatrix, dtype=jnp.float32):
        """``(d, D^-1 U)`` of ``a`` on the device."""
        d = a.diagonal()
        if np.any(d == 0):
            raise ValueError("Gauss–Seidel needs a non-zero diagonal")
        rows = a.row_of_entry()
        strict = a.indices > rows
        upper = csr_from_coo(a.n_rows, a.n_cols, rows[strict],
                             a.indices[strict],
                             a.data[strict] / d[rows[strict]])
        return jnp.asarray(d, dtype), ell_layout(upper, dtype=dtype)

    def __call__(self, r, x0=None) -> jax.Array:
        """The sweep of ``x0`` (x = 0 where None) for right-hand side
        ``r``, f[n] or f[n, m]."""
        if x0 is None:
            return self.bwd.solve(self.fwd.solve(r))
        obs.counter_add("mg.sweep.from_x")
        s = _spmv(self.upper, x0)
        y = self.fwd.solve(_gs_rhs(r, self.d, s))
        return self.bwd.solve(y + s)


class MGLevel:
    """One level of the hierarchy: its block, its sweep and, above the
    coarsest level, the f2c map (int32[n_coarse] on the device) and A's
    rows at the coarse points (``restrict``: the residual needs only
    those)."""

    def __init__(self, shape, sweep: SymGSSweep,
                 f2c: Optional[jax.Array], restrict: Optional[EllMatrix]):
        self.shape = tuple(shape)
        self.n = int(np.prod(self.shape))
        self.sweep, self.f2c, self.restrict = sweep, f2c, restrict


class VCycle:
    """HPCG's multigrid V-cycle over ``levels`` (finest first)."""

    def __init__(self, levels: Sequence[MGLevel], dtype=jnp.float32):
        self.levels = list(levels)
        self.dtype = dtype

    @property
    def n(self) -> int:
        return self.levels[0].n

    def solvers(self) -> list:
        """Every level's ``(fwd, bwd)`` solvers, finest level first."""
        return [s for lv in self.levels for s in (lv.sweep.fwd, lv.sweep.bwd)]

    @classmethod
    def build(
        cls,
        shape,
        *,
        a: Optional[CSRMatrix] = None,
        levels: int = 4,
        plans=None,
        cache: Optional[PlanCache] = None,
        dtype=jnp.float32,
    ) -> "VCycle":
        """The hierarchy of block ``shape`` (nx, ny, nz): ``levels``
        levels, each side halved from one to the next (refused where
        ``2^(levels - 1)`` does not divide a side). ``a`` is the finest
        level's matrix (``stencil27_matrix(*shape)`` where None; its rows
        are the block's points, x fastest). ``plans`` holds the
        ``TriangularSolver.plan`` options: one mapping for every level, or
        a sequence of one per level. Every plan goes into ``cache`` (one
        ``PlanCache``, made here where None)."""
        shapes = mg_block_shapes(*shape, levels)
        if plans is None or isinstance(plans, Mapping):
            plans = [dict(plans or {})] * levels
        if len(plans) != levels:
            raise ValueError(
                f"plans: one per level ({levels}); got {len(plans)}")
        cache = PlanCache() if cache is None else cache
        built = []
        for i, blk in enumerate(shapes):
            n = int(np.prod(blk))
            coarsest = i == levels - 1
            with obs.span("mg.build", cat="mg", level=i, n=n):
                m = a if (i == 0 and a is not None) else stencil27_matrix(*blk)
                if m.n_rows != n:
                    raise ValueError(
                        f"the matrix has {m.n_rows} rows; a {blk} block has {n}")
                d, upper = SymGSSweep.operators(m, dtype)
                f2c = restrict = None
                if not coarsest:
                    f2c_host = hpcg_f2c(*blk)
                    f2c = jnp.asarray(f2c_host)
                    restrict = ell_layout(m, f2c_host, dtype)
            fwd, bwd = gauss_seidel_pair(m, cache=cache, dtype=dtype,
                                         **plans[i])
            built.append(MGLevel(blk, SymGSSweep(fwd, bwd, d, upper),
                                 f2c, restrict))
        obs.counter_add("mg.levels", levels)
        return cls(built, dtype)

    def __call__(self, r) -> jax.Array:
        """z = M^-1 r for r f[n] or f[n, m]: one V-cycle, enqueued on the
        device (the caller syncs)."""
        with obs.span("mg.vcycle", cat="mg", n=self.n,
                      levels=len(self.levels)):
            return self._cycle(0, jnp.asarray(r, self.dtype))

    def from_level(self, i: int, r) -> jax.Array:
        """The V-cycle of levels i, i + 1, ... on r (f[n_i] or f[n_i, m]):
        what level i - 1 applies to its restricted residual, with the same
        programs; ``from_level(0, r)`` is ``vc(r)``, enqueued alike."""
        if not 0 <= i < len(self.levels):
            raise ValueError(f"level {i}: the hierarchy has {len(self.levels)}")
        return self._cycle(i, jnp.asarray(r, self.dtype))

    def _cycle(self, i: int, r) -> jax.Array:
        lv = self.levels[i]
        x = lv.sweep(r)
        if lv.f2c is None:
            return x
        rc = _restrict(r, lv.f2c, _spmv(lv.restrict, x))
        x = _prolong(x, lv.f2c, self._cycle(i + 1, rc))
        return lv.sweep(r, x)
