"""``TriangularSolver`` — plan once, solve many times.

``TriangularSolver.plan(L)`` runs the full inspector pipeline

    DAG build -> schedule (registry strategy) -> §5 reordering ->
    ``compile_plan`` -> backend binding (``repro.backends`` registry)

and returns a bound solver whose ``solve(b)`` applies and undoes every
permutation internally — callers never see reordered indices. ``b`` may be
``f[n]`` or batched ``f[n, m]`` (multi-RHS; one plan traversal).

Backends come from ``repro.backends.registry`` (scan | pallas |
distributed built in; register your own), and every binding is a
``BoundSolve``: numeric refreshes go through its device-side
``update_values`` gather — no plan tensor ever round-trips host memory
after the first bind.

``lower=False`` solves an *upper*-triangular system via the
reverse-permutation trick (an upper-triangular matrix reversed
symmetrically is lower triangular again), which together with
``factor_pair`` gives the forward/backward pair PCG needs:

    fwd, bwd = factor_pair(Lf)        # Lf y = b, then Lf^T x = y

Pass a ``PlanCache`` to amortize the inspector across solves that share a
sparsity pattern — hits skip scheduling entirely and only refresh the
numeric values (paper §7.7's regime).
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import apply_reordering, compile_plan
from repro.core.plan import ExecPlan
from repro.pipeline.cache import PlanCache
from repro.pipeline.registry import ScheduleOptions, get_scheduler
from repro.sparse.csr import (
    CSRMatrix,
    csr_from_coo,
    lower_triangle_of,
    pattern_fingerprint,
    permute_symmetric,
    transpose_csr,
)
from repro.sparse.dag import dag_from_lower_csr


def mesh_fingerprint(mesh) -> tuple | None:
    """Structural mesh identity for cache keys (axes + device list) —
    not ``id()``: CPython reuses freed ids (a dead mesh's id can alias a
    new, different mesh), and a rebuilt identical Mesh should hit the
    same entry. Shared with the autotuner's tune-memo binding key."""
    if mesh is None:
        return None
    return (
        tuple(sorted(mesh.shape.items())),
        tuple(str(d) for d in np.asarray(mesh.devices).ravel()),
    )


def binding_fingerprint(
    *, backend, dtype, width, steps_per_tile, interpret, mesh, slack=0,
    shard="model",
) -> tuple:
    """The backend-binding part of a plan's identity — everything beyond
    (pattern, strategy, options, orientation) that changes the compiled
    solver. One helper shared by ``plan()``'s cache key and the
    autotuner's tune-memo key so the two can never drift apart.
    ``slack > 0`` marks an elastic (macro-step) binding — a different
    compiled graph from the bulk-synchronous one, so it must key (and
    split width classes) even though the plan tensors match. ``shard``
    keys the mesh decomposition the same way: ``"rows"`` row-partitions
    the plan across the mesh (``core.rowshard``), a completely different
    sharded graph from the default ``"model"`` core sharding."""
    return (
        backend,
        np.dtype(dtype).str,
        width if width is not None else "auto",
        steps_per_tile,
        interpret,
        mesh_fingerprint(mesh),
        slack,
        shard,
    )


def mirror_to_lower(a: CSRMatrix, lower: bool):
    """``(m0, outer)``: the lower-triangular matrix the schedulers actually
    see, plus the outer reverse permutation (None when ``lower=True``).
    Reversed symmetrically, an upper-triangular matrix is lower triangular
    again (the L^T trick, paper §5 footnote). Shared by ``plan()`` and the
    autotuner's ``resolve_auto`` so feature extraction and candidate
    scoring always describe the DAG that is actually scheduled."""
    # ValueError, not assert: a wrongly-oriented matrix planned under
    # python -O would otherwise produce silently-garbage solutions
    if lower:
        if not a.is_lower_triangular():
            raise ValueError("expected a lower-triangular matrix")
        return a, None
    if not bool(np.all(a.indices >= a.row_of_entry())):
        raise ValueError("lower=False expects an upper-triangular matrix")
    outer = np.arange(a.n_rows, dtype=np.int64)[::-1].copy()
    return permute_symmetric(a, outer), outer


def _entry_permutation(m: CSRMatrix, perm: np.ndarray) -> np.ndarray:
    """``e`` such that ``permute_symmetric(m, perm).data == m.data[e]``.

    Pure scatter/argsort passes — two relabel gathers and one ``lexsort``
    — instead of riding entry ids through ``permute_symmetric`` on a
    float64 carrier matrix (the old inspector hot spot: it re-ran the
    full ``csr_from_coo`` duplicate-merge machinery per plan). The
    ``lexsort`` key order (cols minor, rows major) matches
    ``csr_from_coo`` exactly and the (row, col) pairs of a CSR pattern
    are unique, so the result is identical entry-for-entry.
    """
    perm = np.asarray(perm, dtype=np.int64)
    inv = np.empty(m.n_rows, dtype=np.int64)
    inv[perm] = np.arange(m.n_rows, dtype=np.int64)
    return np.lexsort((inv[m.indices], inv[m.row_of_entry()]))


class TriangularSolver:
    """A bound, permutation-transparent triangular solver. Construct via
    :meth:`plan` (or :func:`factor_pair`), not directly."""

    def __init__(
        self,
        *,
        exec_plan: ExecPlan,
        total_perm: np.ndarray,
        backend: str,
        dtype,
        fingerprint: str,
        strategy: str,
        lower: bool,
        inspector_seconds: float,
        mesh=None,
        steps_per_tile: int = 8,
        interpret: Optional[bool] = None,
        slack: int = 0,
        shard: str = "model",
        timed: bool = False,
    ):
        self.exec_plan = exec_plan
        self.backend = backend
        self.dtype = dtype
        self.fingerprint = fingerprint
        self.strategy = strategy
        self.lower = lower
        self.inspector_seconds = inspector_seconds
        self._mesh = mesh
        self._steps_per_tile = steps_per_tile
        self._interpret = interpret
        self._slack = slack  # > 0: elastic (macro-step) execution mode
        self._shard = shard  # mesh decomposition ("model" | "rows")
        # per-step timed execution (observability toggle, NOT part of the
        # plan identity — flip it any time; results are identical, only
        # dispatch granularity and telemetry change)
        self.timed = bool(timed)
        self.last_step_timings: Optional[list] = None
        self._source_data: Optional[np.ndarray] = None  # set by plan()
        self._selection = None  # autotune Selection, set by plan(auto)
        self.plan_key = None  # concrete plan-cache key, set by plan()
        self._bind(total_perm)

    # ---------------------------------------------------------- binding
    def _bind(self, total_perm: np.ndarray) -> None:
        """Bind device-resident plan tensors through the
        ``repro.backends`` registry — called once at construction.
        Numeric refreshes never come back here: they go through the
        bound solve's device-side ``update_values`` gather. The
        ``backend.bind`` span covers all of it: the elastic certificate
        (when ``plan()`` has not attached it for the verifier), the row
        permutations' and the backend's device puts.

        ``_inv`` maps each caller row to its place in the bound's
        ``solve_raw`` result: the inverse row permutation composed with
        the bound's ``out_index`` (the scan's step-slot order), so a
        solve issues one gather back to the caller's order, not two."""
        from repro.backends import get_backend

        plan = self.exec_plan
        with obs.span(
            "backend.bind",
            cat="backend",
            backend=self.backend,
            n=plan.n,
            slack=self._slack,
            shard=self._shard,
        ) as sp:
            if self._slack > 0 and (
                plan.elastic is None or plan.elastic.slack != self._slack
            ):
                from repro.core import elastic_transform

                # attached, so that ExecPlan.stats() reads the same one
                plan.elastic = elastic_transform(plan, self._slack)
            self._bound = get_backend(self.backend).bind(
                plan,
                dtype=self.dtype,
                steps_per_tile=self._steps_per_tile,
                interpret=self._interpret,
                mesh=self._mesh,
                slack=self._slack,
                shard=self._shard,
            )
            total_inv = np.empty_like(total_perm)
            total_inv[total_perm] = np.arange(len(total_perm))
            self._perm = jnp.asarray(total_perm, jnp.int32)
            self._inv = jnp.asarray(total_inv, jnp.int32)
            out = self._bound.out_index
            if out is not None:
                self._inv = out[self._inv]
                slots = self._bound.describe()["slots"]
                sp.set(slots=slots, slot_overhead=slots / plan.n)

    @property
    def bound(self):
        """The backend ``BoundSolve`` this solver executes through
        (telemetry via ``bound.describe()``)."""
        return self._bound

    @property
    def width_class(self) -> tuple:
        """Structural identity of this solver's compiled solve graph:
        two solvers with equal width classes execute identically-shaped
        ``ExecPlan`` tensors through the same backend binding, so they
        share every compiled XLA variant — and, when the backend
        supports it, their requests can ride one grouped dispatch
        (``grouped_solve``; the serve layer's cross-pattern batching).
        Orientation (``lower``) is deliberately excluded: it only
        changes the host-side permutation, never the solve graph."""
        p = self.exec_plan
        return (
            p.n,
            p.n_steps,
            p.k,
            p.W,
            tuple(int(x) for x in p.step_bounds),
        ) + binding_fingerprint(
            backend=self.backend,
            dtype=self.dtype,
            width=p.W,
            steps_per_tile=self._steps_per_tile,
            interpret=self._interpret,
            mesh=self._mesh,
            slack=self._slack,
            shard=self._shard,
        )

    @property
    def supports_grouping(self) -> bool:
        """True when this solver's backend can serve width-class grouped
        solves (one fused dispatch, one plan per column)."""
        return bool(getattr(self._bound, "supports_grouped", False))

    # ---------------------------------------------------------- solving
    def _check_b(self, b):
        b = jnp.asarray(b, self.dtype)
        # XLA clamps out-of-range gather indices, so a mis-sized b would
        # silently produce garbage — reject it here.
        if b.ndim not in (1, 2) or b.shape[0] != self.n:
            raise ValueError(
                f"b must be [n] or [n, m] with n={self.n}; got {b.shape}"
            )
        return b

    def solve(self, b):
        """Solve the planned system for ``b``: f[n] or f[n, m] (multi-RHS).
        Input/output live in the caller's original row ordering. With the
        ``timed`` toggle on, routes through :meth:`solve_timed` (per-step
        device timings land in ``last_step_timings`` and the active trace
        buffer)."""
        if self.timed:
            return self.solve_timed(b)[0]
        b = self._check_b(b)
        # the enqueue: JAX returns before the device has finished
        with obs.span("executor.dispatch", cat="executor", n=self.n):
            x = self._bound.solve_raw(b[self._perm])
            return x[self._inv]

    def solve_timed(self, b):
        """``solve`` with per-step device timing: returns ``(x, steps)``
        where ``steps`` holds one JSON-ready dict per superstep (bulk) /
        macro-step (elastic) at the finest granularity the backend can
        observe (``BoundSolve.solve_timed``). The last timing list is
        kept on ``last_step_timings``; per-step spans land in the active
        trace buffer when tracing is enabled."""
        b = self._check_b(b)
        with obs.span(
            "executor.dispatch", cat="executor", n=self.n, timed=True
        ):
            x, steps = self._bound.solve_timed(b[self._perm])
            x = x[self._inv]
        self.last_step_timings = steps
        return x, steps

    __call__ = solve

    def numeric_update(self, a) -> None:
        """Refresh values from ``a`` — a CSRMatrix with the planned sparsity
        pattern, or its raw ``.data`` — without rescheduling. Mutates THIS
        solver in place (plan-cache hits clone instead, so solvers returned
        from earlier ``plan`` calls are never touched behind their backs)."""
        if isinstance(a, CSRMatrix):
            if pattern_fingerprint(a) != self.fingerprint:
                raise ValueError(
                    "numeric_update requires the sparsity pattern the plan "
                    "was built for (pattern fingerprint mismatch)"
                )
            data = a.data
        else:
            data = np.asarray(a)
        # host mirror: bind() reads the host plan tensors, so they must
        # stay a faithful source for any future (re)bind of this plan —
        # letting them go stale would make such a bind silently solve
        # with old values. A deliberate O(plan) host cost per refresh.
        self.exec_plan.numeric_update(data)
        self._source_data = np.array(data)
        # device refresh: an O(nnz) gather through val_src/diag_src — the
        # plan's index tensors stay on device, nothing retransfers
        self._bound = self._bound.update_values(data)

    def _with_values(self, data: np.ndarray) -> "TriangularSolver":
        """A sibling solver with new numeric values: shares the (read-only)
        schedule/index structure, owns its value tensors and binding."""
        import copy
        import dataclasses

        new = copy.copy(self)
        new.exec_plan = dataclasses.replace(
            self.exec_plan,
            vals=self.exec_plan.vals.copy(),
            diag=self.exec_plan.diag.copy(),
        )
        new.numeric_update(data)
        return new

    def clone_with_values(self, a) -> "TriangularSolver":
        """Public sibling-with-new-values: ``a`` is a CSRMatrix with the
        planned pattern (fingerprint-checked) or its raw ``.data``. THIS
        solver is untouched — the live-refactorization primitive
        ``repro.serve`` version-swaps with (in-flight batches keep reading
        the old solver's tensors)."""
        if isinstance(a, CSRMatrix):
            if pattern_fingerprint(a) != self.fingerprint:
                raise ValueError(
                    "clone_with_values requires the sparsity pattern the "
                    "plan was built for (pattern fingerprint mismatch)"
                )
            data = a.data
        else:
            data = np.asarray(a)
        return self._with_values(data)

    @property
    def source_values(self) -> Optional[np.ndarray]:
        """The caller-order entry values this solver was built/refreshed
        from (read-only view — used to detect value changes cheaply)."""
        return self._source_data

    @property
    def n(self) -> int:
        return self.exec_plan.n

    @property
    def n_supersteps(self) -> int:
        return self.exec_plan.n_supersteps

    def info(self) -> dict:
        out = {
            "strategy": self.strategy,
            "backend": self.backend,
            "mode": "elastic" if self._slack else "bsp",
            "slack": self._slack,
            "shard": self._shard,
            "timed": self.timed,
            "lower": self.lower,
            "n_supersteps": self.n_supersteps,
            "inspector_seconds": self.inspector_seconds,
            "plan": self.exec_plan.stats(),
            "binding": self._bound.describe(),
        }
        if self._selection is not None:
            out["selection"] = self._selection.as_dict()
        return out

    @property
    def selection(self):
        """The autotuner's ``Selection`` recorded when this solver object
        was *built* by a ``strategy="auto"`` plan (None when it was built
        with a fixed strategy). Cached solvers are never mutated after the
        fact: an auto plan that cache-hits an entry originally built by a
        fixed-strategy plan returns it with ``selection`` still None — the
        resolved outcome remains available via the cache's selection memo.
        """
        return self._selection

    # ---------------------------------------------------------- planning
    @classmethod
    def plan(
        cls,
        a: CSRMatrix,
        *,
        strategy: str = "growlocal",
        backend: str = "scan",
        lower: bool = True,
        k: Optional[int] = None,
        dtype=jnp.float32,
        width: Optional[int] = None,
        options: Optional[ScheduleOptions] = None,
        cache: Optional[PlanCache] = None,
        mesh=None,
        steps_per_tile: int = 8,
        interpret: Optional[bool] = None,
        sched=None,
        tune: bool = False,
        mode: Optional[str] = None,
        shard: str = "model",
        timed: bool = False,
        validate: Optional[str] = None,
        **opts,
    ) -> "TriangularSolver":
        """Plan a solver for triangular ``a`` (lower, or upper with
        ``lower=False``). With ``cache``, a repeated sparsity pattern skips
        the inspector: identical values return the cached solver as-is; new
        values return a clone with refreshed numerics (solvers from earlier
        calls are never mutated). ``sched`` bypasses the registry with a
        pre-built Schedule (never cached — the cache cannot key on
        arbitrary schedules).

        ``mode`` selects the execution mode: ``"bsp"`` (bulk-synchronous,
        the default) or ``"elastic"`` — bounded-slack macro-step execution
        (``core.elastic``; bitwise-identical results, fewer scan/grid
        steps on deep DAGs). ``mode="elastic"`` uses the staleness window
        from ``slack=...`` (a ``ScheduleOptions`` knob) or the calibrated
        ``core.DEFAULT_SLACK``; passing ``slack > 0`` alone also enables
        elastic. The backend must advertise the ``"elastic"`` capability.

        ``shard`` selects the mesh decomposition for distributed
        backends: ``"model"`` (default — lanes sharded, x replicated via
        all-gather) or ``"rows"`` — the plan is row-partitioned across
        the mesh's ``"model"`` axis (``core.rowshard``) with per-superstep
        halo exchange instead of O(n) all-gathers. Requires the backend
        to advertise ``"shard-rows"``.

        ``strategy="auto"`` lets the autotuner choose: DAG features ->
        rule-based shortlist -> §2.2 cost model (``repro.autotune``); with
        ``tune=True`` the shortlisted plans are additionally compiled and
        *timed* on the real backend. When the backend supports elastic
        (and ``mode`` does not force ``"bsp"``), the selector may also
        turn elastic mode on via its step-granular cost rule. The
        resolved config is memoized per sparsity fingerprint (inside
        ``cache`` when given), and the plan is cached under the resolved
        *concrete* key — so repeated auto plans on one pattern skip both
        selection and scheduling.

        ``validate`` runs the independent static verifier
        (``repro.analysis``) over the freshly built artifacts —
        schedule, reorder permutation, plan tensors, elastic
        certificate, and (``shard="rows"``) the halo partition:
        ``"fast"`` is the vectorized invariant set, ``"full"`` adds
        value provenance and per-shard audits, ``"off"`` (default)
        skips. ``None`` defers to the ``REPRO_VALIDATE`` env var. A
        finding raises ``analysis.VerificationError`` with the findings
        table. Build-time only: cache hits return the already-verified
        entry without re-checking.

        ``timed=True`` turns on per-step timed execution (``repro.obs``):
        every ``solve`` routes through ``solve_timed`` and records
        per-superstep / per-macro-step device timings. Deliberately NOT
        part of the plan identity — it is a mutable observability toggle
        on the solver (``solver.timed``), so a cache hit returns the same
        entry with the toggle set to THIS call's value."""
        # normalize once: the registry is case-insensitive, and the raw
        # string enters the plan-cache key ("GrowLocal" vs "growlocal"
        # must not schedule twice); also makes strategy="Auto" work
        strategy = strategy.lower()
        # resolve (and reject) the validation level before any scheduling
        # work; "off" keeps the verifier entirely off the build path
        from repro.analysis import resolve_level

        check_level = resolve_level(validate)
        # fail fast on an unknown backend — before any scheduling work and
        # with the registry (not a hard-coded tuple) naming the options
        from repro.backends import get_backend

        backend_caps = get_backend(backend).capabilities()
        if tune and (strategy != "auto" or sched is not None):
            raise ValueError(
                "tune=True runs measured trials to refine an auto "
                "selection; it requires strategy='auto' (and no pre-built "
                "sched)"
            )
        o = options or ScheduleOptions()
        if k is not None:
            o = o.replace(k=k)
        if opts:
            o = o.replace(**opts)
        if mode is not None and mode not in ("bsp", "elastic"):
            raise ValueError(
                f"mode must be 'bsp' or 'elastic'; got {mode!r}"
            )
        if mode == "elastic" and o.slack == 0:
            from repro.core import DEFAULT_SLACK

            o = o.replace(slack=DEFAULT_SLACK)
        if mode == "bsp" and o.slack > 0:
            raise ValueError(
                f"mode='bsp' conflicts with slack={o.slack}; drop one"
            )
        if o.slack > 0 and "elastic" not in backend_caps:
            raise ValueError(
                f"backend {backend!r} does not support mode='elastic' "
                f"(requested slack={o.slack}, no 'elastic' capability)"
            )
        if shard != "model" and f"shard-{shard}" not in backend_caps:
            raise ValueError(
                f"backend {backend!r} does not support shard={shard!r} "
                f"(no 'shard-{shard}' capability)"
            )
        # the selector may only turn elastic ON when the binding can run
        # it and the caller did not force bulk-synchronous
        elastic_ok = mode != "bsp" and "elastic" in backend_caps

        fp = pattern_fingerprint(a)
        selection = None
        pre_sched = None  # winning Schedule the selector already computed
        pre_solver = None  # winner's trial solver (tune=True measured run)
        if strategy == "auto" and sched is None:
            from repro.autotune.selector import resolve_auto_full

            # the DAG, features and scoring: scheduling under "auto"
            with obs.span(
                "inspector.schedule", cat="inspector", strategy="auto",
                n=a.n_rows,
            ) as sp:
                selection, pre_sched, pre_solver = resolve_auto_full(
                    a,
                    options=o,
                    lower=lower,
                    tune=tune,
                    cache=cache,
                    fp=fp,
                    allow_elastic=elastic_ok,
                    plan_kwargs=dict(
                        backend=backend, dtype=dtype, width=width,
                        mesh=mesh, steps_per_tile=steps_per_tile,
                        interpret=interpret, shard=shard,
                    ),
                )
                sp.set(picked=selection.strategy)
            strategy, o = selection.strategy, selection.options
        # o (a frozen dataclass) covers every scheduling knob incl. k,
        # reorder and the elastic slack; binding params (mesh identity,
        # tile size, interpret, slack again) also change the built solver
        # and must key too.
        key = (fp, strategy, o, lower) + binding_fingerprint(
            backend=backend, dtype=dtype, width=width,
            steps_per_tile=steps_per_tile, interpret=interpret, mesh=mesh,
            slack=o.slack, shard=shard,
        )

        def build() -> "TriangularSolver":
            t0 = time.perf_counter()
            n = a.n_rows
            m0, outer = mirror_to_lower(a, lower)

            if sched is not None:
                s = sched
            elif pre_sched is not None:
                s = pre_sched  # already computed while scoring candidates
            else:
                with obs.span("inspector.dag", cat="inspector", n=n):
                    dag = dag_from_lower_csr(m0)
                with obs.span(
                    "inspector.schedule", cat="inspector",
                    strategy=strategy, n=n, k=o.k,
                ):
                    s = get_scheduler(strategy)(dag, o)
            if o.reorder:
                with obs.span("inspector.reorder", cat="inspector", n=n):
                    m2, s2, _, r = apply_reordering(m0, s)
                inner = r.perm
            else:
                m2, s2, inner = m0, s, np.arange(n, dtype=np.int64)

            plan = compile_plan(m2, s2, width=width, dtype=np.dtype(dtype))
            if check_level != "off":
                if o.slack > 0:
                    # the verifier audits the slack certificate, so it is
                    # made here; the bind reuses it (else the bind makes
                    # and attaches it)
                    from repro.core import elastic_transform

                    plan.elastic = elastic_transform(plan, o.slack)
                # verify against m2 BEFORE the val_src rebase below —
                # the provenance audit matches sources against the
                # matrix the plan was actually compiled from
                from repro import analysis

                analysis.verify_artifacts(
                    analysis.Artifacts(
                        L=m2, sched=s2, plan=plan,
                        perm=inner if o.reorder else None,
                        sched_pre=s if o.reorder else None,
                    ),
                    level=check_level,
                ).raise_if_failed()

            # rebase the plan's value-source maps onto a's entry order so
            # numeric_update() consumes a.data directly
            entry_map = _entry_permutation(m0, inner)  # m2 entry -> m0 entry
            if outer is not None:
                entry_map = _entry_permutation(a, outer)[entry_map]
            vmask = plan.val_src >= 0
            plan.val_src[vmask] = entry_map[plan.val_src[vmask]]
            dmask = plan.diag_src >= 0
            plan.diag_src[dmask] = entry_map[plan.diag_src[dmask]]

            total_perm = inner if outer is None else outer[inner]
            solver = cls(
                exec_plan=plan,
                total_perm=total_perm,
                backend=backend,
                dtype=dtype,
                fingerprint=fp,
                strategy=strategy if sched is None else "(prebuilt)",
                lower=lower,
                inspector_seconds=time.perf_counter() - t0,
                mesh=mesh,
                steps_per_tile=steps_per_tile,
                interpret=interpret,
                slack=o.slack,
                shard=shard,
            )
            solver._source_data = np.array(a.data)
            # selection is recorded at build time only — cached solvers are
            # never mutated after being handed out (see the property doc)
            solver._selection = selection
            if check_level != "off" and shard == "rows":
                # the halo partition is produced at backend bind time;
                # audit it against the plan it was cut from (the value
                # check deliberately skips the rebased source maps)
                from repro import analysis

                rsp = getattr(solver.bound, "_rsp", None)
                if rsp is not None:
                    analysis.verify_rowshard_report(
                        plan, rsp, level=check_level
                    ).raise_if_failed()
            return solver

        # the tuned winner was compiled+warmed during the measured trials
        # (against a private cache) and carries its Selection — use it as
        # the builder so the work is not redone; it enters the shared
        # cache fully formed, so no published solver is ever mutated
        builder = build if pre_solver is None else (lambda: pre_solver)
        if cache is None or sched is not None:
            solver = builder()
            if sched is None:  # prebuilt schedules have no cacheable key
                solver.plan_key = key
            solver.timed = timed
            return solver
        solver, hit = cache.get_or_build(key, builder)
        # idempotent on hits (the key IS the entry's key); lets callers
        # pin/unpin the entry (PlanCache.pin) without recomputing the key
        solver.plan_key = key
        if hit and not np.array_equal(solver._source_data, a.data):
            # same pattern, new values: clone with refreshed numerics (the
            # cached entry — and anyone holding it — stays untouched), then
            # make the clone canonical so repeats of THESE values are free
            solver = solver._with_values(a.data)
            cache.replace(key, solver)
            cache.note_numeric_update()
        solver.timed = timed
        return solver


def grouped_solve(solvers, B) -> jnp.ndarray:
    """Solve column j of ``B`` f[n, m] with ``solvers[j]`` — one fused
    width-class dispatch (``BoundSolve.solve_grouped``), each column
    against its own plan tensors (pattern AND values may differ per
    column; only the tensor shapes must match — equal ``width_class``).

    Per-column permutations are applied/undone here, so columns may even
    mix orientations. The compiled variant is cached per (width class,
    group width): a serving mix of structurally-identical patterns pays
    for log2(max_batch) compilations total, not per pattern.

    Bitwise contract: vmap lanes are data-independent, so a column's
    bits depend only on its own (plan, b) — never on what the neighbor
    columns hold. The replay reference for a grouped result is therefore
    the same call with the request's own solver replicated into every
    lane (``repro.serve.service.GroupReplay``)."""
    if not solvers:
        raise ValueError("grouped_solve needs at least one solver")
    wc = solvers[0].width_class
    for s in solvers[1:]:
        if s.width_class != wc:
            raise ValueError(
                "grouped_solve requires one width class; got solvers "
                f"with {s.width_class} vs {wc}"
            )
    bound0 = solvers[0]._bound
    if not getattr(bound0, "supports_grouped", False):
        raise NotImplementedError(
            f"backend {solvers[0].backend!r} does not support width-class "
            "grouped solves"
        )
    B = jnp.asarray(B, solvers[0].dtype)
    if B.ndim != 2 or B.shape[0] != solvers[0].n or B.shape[1] != len(solvers):
        raise ValueError(
            f"B must be [n={solvers[0].n}, m={len(solvers)}] (one column "
            f"per solver); got {B.shape}"
        )
    b_cols = jnp.stack(
        [B[:, j][s._perm] for j, s in enumerate(solvers)]
    )
    X = type(bound0).solve_grouped([s._bound for s in solvers], b_cols)
    return jnp.stack(
        [X[j][s._inv] for j, s in enumerate(solvers)], axis=1
    )


class GroupBank:
    """Device-side bank of one width class's live plans — the serving
    fast path for cross-pattern grouped batches.

    ``grouped_solve`` restacks plan tensors on every call (fine for
    replay/verification); a bank stacks each member ONCE
    (``executor.stack_plan_bank``) and lets every microbatch index its
    lanes inside a single jitted call (``executor.solve_with_bank``) —
    bitwise-identical results, an order of magnitude less per-dispatch
    overhead. Members are keyed by caller-chosen hashable keys (the
    serve layer uses ``(fingerprint, version)``); adding or dropping a
    member invalidates the stack, which is rebuilt lazily on the next
    solve (lane count pads to a power of two, bounding compile churn as
    plan versions come and go).

    Backend-agnostic: the bank dispatches through the ``BoundSolve``
    bank contract (``stack_bank``/``solve_bank``), which every backend
    advertising ``supports_grouped`` must implement — today that is the
    scan backend, the one whose compiled graph is shape-only."""

    def __init__(self):
        self._lock = threading.Lock()
        self._solvers: Dict = {}  # key -> solver; dict order = lane order
        self._index: Dict = {}
        self._bank = None
        self.rebuilds = 0  # telemetry: restacks actually performed

    def __len__(self) -> int:
        with self._lock:
            return len(self._solvers)

    def add(self, key, solver: "TriangularSolver") -> None:
        """Register ``solver`` under ``key`` (idempotent). The solver
        must support grouping and share the bank's width class."""
        if not solver.supports_grouping:
            raise NotImplementedError(
                f"backend {solver.backend!r} does not support width-class "
                "grouped solves"
            )
        with self._lock:
            if key in self._solvers:
                return
            if self._solvers:
                wc0 = next(iter(self._solvers.values())).width_class
                if solver.width_class != wc0:
                    raise ValueError(
                        "GroupBank requires one width class; got "
                        f"{solver.width_class} vs {wc0}"
                    )
            self._solvers[key] = solver
            self._bank = None

    def drop(self, key) -> None:
        with self._lock:
            if self._solvers.pop(key, None) is not None:
                self._bank = None

    def prune(self, keep) -> None:
        """Drop every member whose key fails ``keep(key)`` — the serve
        layer retires lanes of superseded, drained plan versions.
        ``keep`` runs under the bank lock, serialized with concurrent
        ``add``s (callers rely on that for liveness checks)."""
        with self._lock:
            dead = [k for k in self._solvers if not keep(k)]
            for k in dead:
                del self._solvers[k]
            if dead:
                self._bank = None

    def _ensure_locked(self):
        if self._bank is None:
            solvers = list(self._solvers.values())
            cls = type(solvers[0]._bound)
            self._bank = cls.stack_bank(
                [s._bound for s in solvers],
                [s._perm for s in solvers],
                [s._inv for s in solvers],
            )
            self._bound_cls = cls
            self._index = {k: i for i, k in enumerate(self._solvers)}
            self.rebuilds += 1
        return self._bound_cls, self._bank, self._index

    def solve(self, keys, B) -> jnp.ndarray:
        """Solve column j of ``B`` f[n, m] (caller row order) against
        the member registered under ``keys[j]``; returns x f[n, m].
        Bitwise-identical to ``grouped_solve`` on the same members
        (property-tested), so ``GroupReplay`` remains the replay
        reference for bank-served results."""
        with self._lock:
            cls, bank, index = self._ensure_locked()
            lane_idx = np.fromiter(
                (index[k] for k in keys), np.int32, count=len(keys)
            )
        return cls.solve_bank(bank, lane_idx, B)

    def solve_resident(self, keys, B_res) -> jnp.ndarray:
        """One continuous-mode dispatch pass: solve column j of the
        *device-resident* ``B_res`` f[n, S] against the member under
        ``keys[j]`` — bitwise-identical to :meth:`solve` on the same
        keys (``BoundSolve.solve_resident`` delegates to the same banked
        kernel), but ``B_res`` never re-uploads: the continuous serve
        engine (``repro.serve.slots``) mutates it slot-by-slot with
        ``insert_lane`` and keeps it on device across passes."""
        with self._lock:
            cls, bank, index = self._ensure_locked()
            lane_idx = np.fromiter(
                (index[k] for k in keys), np.int32, count=len(keys)
            )
        return cls.solve_resident(bank, lane_idx, B_res)

    def describe(self) -> dict:
        with self._lock:
            return {
                "n_lanes": len(self._solvers),
                "rebuilds": self.rebuilds,
            }


def factor_pair(lf: CSRMatrix, *, cache: Optional[PlanCache] = None, **kw):
    """Plan the (L, L^T) solver pair of a factorization: ``fwd`` solves
    ``Lf y = b``, ``bwd`` solves ``Lf^T x = y`` — together an application of
    ``(Lf Lf^T)^{-1}``, PCG's preconditioner."""
    fwd = TriangularSolver.plan(lf, lower=True, cache=cache, **kw)
    bwd = TriangularSolver.plan(transpose_csr(lf), lower=False, cache=cache, **kw)
    return fwd, bwd


def gauss_seidel_pair(a: CSRMatrix, *, cache: Optional[PlanCache] = None,
                      **kw):
    """Plan the two sweeps of a symmetric Gauss–Seidel smoother on ``a``
    (``D`` its diagonal, ``L``/``U`` its strictly lower/upper parts):
    ``fwd`` solves with ``L + D``, ``bwd`` with ``I + D^-1 U``.

    From ``x = 0`` the symmetric sweep (HPCG's ``ComputeSYMGS``: a forward
    sweep over the rows, then a backward one) is exactly these two solves,
    ``x = bwd.solve(fwd.solve(r))``: the forward sweep gives
    ``x1 = (L + D)^-1 r``, and since ``(L + D) x1 = r`` the backward sweep
    gives ``x2 = (D + U)^-1 (r - L x1) = (D + U)^-1 D x1 = (I + D^-1 U)^-1 x1``.
    Only from ``x = 0``: a sweep from any other ``x`` is not this pair."""
    d = a.diagonal()
    if np.any(d == 0):
        raise ValueError("Gauss–Seidel needs a non-zero diagonal")
    rows = a.row_of_entry()
    upper = a.indices >= rows
    unit_upper = csr_from_coo(
        a.n_rows, a.n_cols, rows[upper], a.indices[upper],
        a.data[upper] / d[rows[upper]],
    )
    fwd = TriangularSolver.plan(lower_triangle_of(a), lower=True,
                                cache=cache, **kw)
    bwd = TriangularSolver.plan(unit_upper, lower=False, cache=cache, **kw)
    return fwd, bwd
