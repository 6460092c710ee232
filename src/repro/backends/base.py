"""The execution-backend protocol: ``Backend.bind(plan) -> BoundSolve``.

One contract replaces the three divergent device-tensor conversions that
used to live in ``solver/executor.py`` (scan), ``kernels/ops.py``
(pallas tile setup) and ``solver/distributed.py`` (mesh sharding), and
the ``if/elif`` binding block in ``pipeline/solver.py``:

  * ``Backend`` — a named, stateless factory. ``bind(exec_plan,
    **params)`` transfers the plan tensors to the device(s) once and
    returns a ``BoundSolve``. Binding parameters every backend receives
    (and ignores if irrelevant): ``dtype``, ``steps_per_tile``,
    ``interpret``, ``mesh``.
  * ``BoundSolve`` — an immutable bound solver:
      - ``solve(b)`` for ``b`` f[n] or f[n, m] (multi-RHS), x in plan
        row order;
      - ``solve_raw(b)`` — x in the bound's own output order, where
        ``out_index`` (int32[n], or None for plan order) puts each plan
        row; a caller that permutes x anyway folds ``out_index`` into
        its own gather and skips the bound's;
      - ``update_values(data) -> BoundSolve`` refreshes the numeric
        values *on device* — a gather of the new entry data through the
        plan's ``val_src``/``diag_src`` maps — and returns a NEW bound
        solve sharing the (read-only) index tensors. The old bound keeps
        serving in-flight work untouched (the live-refactorization
        primitive ``repro.serve`` version-swaps on), and nothing
        round-trips the full [T, k, W] plan tensors through host memory;
      - ``describe()`` — a JSON-ready dict for bench/serve telemetry.

The value contract ``update_values`` must honor (conformance-tested on
every registered backend): a solve after ``update_values(data)`` is
bitwise-identical to a solve on a fresh ``bind`` of a plan compiled from
the same pattern with ``data``.

Register implementations with ``repro.backends.register_backend``; every
consumer (``TriangularSolver``, the conformance grid, the autotuner's
trial runner, serve telemetry) iterates the registry, so a new backend is
one registry entry — never another ``elif``.
"""
from __future__ import annotations

import abc
from typing import Tuple

import numpy as np

from repro import obs


def _masked_value_gather_jit():
    """Build (once) the jitted gather+mask kernel — jit fuses the two
    gathers and selects into one pass per tensor instead of four eager
    dispatches, and the compiled variant is cached per plan shape."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gather(data, val_src, vals_old, diag_src, diag_old):
        # negative indices are masked by the where(); jax clamps them in
        # the gather, so no out-of-bounds access happens either way
        vals = jnp.where(val_src >= 0, data[val_src], vals_old)
        diag = jnp.where(diag_src >= 0, data[diag_src], diag_old)
        return vals, diag

    return gather


_GATHER = None


def masked_value_gather(data, val_src, vals_old, diag_src, diag_old):
    """The shared device-side numeric refresh: gather ``data`` (the new
    matrix's entry values, already cast to the plan dtype) through the
    source maps, keeping the old value wherever the map says padding
    (``src < 0``). Returns ``(vals, diag)`` as new device arrays.

    Bitwise-identical to the host path (``ExecPlan.numeric_update`` +
    retransfer): the f64 -> plan-dtype cast happens per element on the
    host, and the gather itself moves bits unchanged; padding slots keep
    their original contents exactly as the in-place host mutate does.
    """
    global _GATHER
    if _GATHER is None:
        _GATHER = _masked_value_gather_jit()
    return _GATHER(data, val_src, vals_old, diag_src, diag_old)


def expected_entry_count(exec_plan) -> int:
    """Length the ``update_values`` data vector must have: the planned
    pattern's entry count, recovered from the source maps (every stored
    entry of a full-diagonal matrix is referenced by exactly one of
    them, so the max index + 1 is the nnz)."""
    hi = -1
    if exec_plan.val_src is not None and exec_plan.val_src.size:
        hi = max(hi, int(exec_plan.val_src.max()))
    if exec_plan.diag_src is not None and exec_plan.diag_src.size:
        hi = max(hi, int(exec_plan.diag_src.max()))
    return hi + 1


class BoundSolve(abc.ABC):
    """A plan bound to one execution backend. Immutable: value refreshes
    return a new instance (see module docstring)."""

    backend: str  # registry name of the backend that produced this
    n: int  # problem size (scratch row excluded)
    n_entries: int  # entry count update_values data must match

    # width-class grouping: True when this bound can solve one rhs per
    # plan in a single fused dispatch. Requires the compiled solve graph
    # to depend only on the plan tensor SHAPES — the scan backend
    # qualifies (step_bounds never enter its trace); backends whose
    # graph bakes in plan contents (distributed superstep bounds, pallas
    # grids) must leave this False. Advertising it is a THREE-method
    # contract: ``solve_grouped`` (stack-per-call; the replay/reference
    # path) plus ``stack_bank``/``solve_bank`` (the serving fast path —
    # ``repro.pipeline.GroupBank`` dispatches through them, so a backend
    # that only implements ``solve_grouped`` must not set this flag).
    supports_grouped: bool = False

    # where ``solve_raw`` puts each plan row: a device int32[n], or None
    # when its result is in plan order (then ``solve_raw`` is ``solve``)
    out_index = None

    @classmethod
    def solve_grouped(cls, bounds, b_cols):
        """Solve lane j of ``b_cols`` f[g, n] (plan row order) against
        ``bounds[j]`` — one dispatch for the whole group. All bounds must
        share one width class (identical plan tensor shapes, same dtype).
        Returns x f[g, ·], lane j in ``bounds[j]``'s ``solve_raw``
        order. Only meaningful when ``supports_grouped``."""
        raise NotImplementedError(
            f"backend {cls.backend!r} does not support width-class "
            "grouped solves"
        )

    @classmethod
    def stack_bank(cls, bounds, perms, invs):
        """Stack one width class's bounds into an opaque device bank
        (lane axis first) with per-lane row permutations ``perms``
        (caller row order -> plan row order) and ``invs`` (caller row ->
        its position in the lane's ``solve_raw`` result) — restacked
        only when membership changes. Returned
        value is backend-defined; it is only ever passed back to
        ``solve_bank``."""
        raise NotImplementedError(
            f"backend {cls.backend!r} does not support width-class "
            "grouped solves (no bank support)"
        )

    @classmethod
    def solve_bank(cls, bank, lane_idx, B):
        """Solve column j of ``B`` f[n, m] (caller row order) against
        bank lane ``lane_idx[j]``; returns x f[n, m] (caller order),
        bitwise-identical to ``solve_grouped`` on the same lanes."""
        raise NotImplementedError(
            f"backend {cls.backend!r} does not support width-class "
            "grouped solves (no bank support)"
        )

    # resident RHS slots — the continuous-batching serve contract
    # (capability ``"slots"``). Four classmethods on top of the bank
    # contract: a device-resident rhs bank f[n, S] that admission writes
    # into slot-by-slot (``insert_lane``), the always-running dispatch
    # loop solves at the fixed width S (``solve_resident`` — bitwise-
    # identical to ``solve_bank`` on the same lanes), and completion
    # reads out of (``extract_lane``). All three device ops move bits
    # unchanged and must not perturb neighbor slots.
    @classmethod
    def blank_rhs(cls, n, slots, dtype):
        """A zeroed device-resident rhs bank f[n, slots]."""
        raise NotImplementedError(
            f"backend {cls.backend!r} does not support resident RHS "
            "slots (no 'slots' capability)"
        )

    @classmethod
    def insert_lane(cls, B_res, lane, b):
        """A NEW resident bank with column ``lane`` replaced by ``b``
        f[n]; every other column's bits are untouched and the input
        bank is not mutated (in-flight passes keep their snapshot)."""
        raise NotImplementedError(
            f"backend {cls.backend!r} does not support resident RHS "
            "slots (no 'slots' capability)"
        )

    @classmethod
    def extract_lane(cls, X, lane):
        """Column ``lane`` of a pass result ``X`` f[n, S] as f[n],
        bits unchanged."""
        raise NotImplementedError(
            f"backend {cls.backend!r} does not support resident RHS "
            "slots (no 'slots' capability)"
        )

    @classmethod
    def solve_resident(cls, bank, lane_idx, B_res):
        """One continuous-mode dispatch pass: solves the first
        ``len(lane_idx)`` columns of the resident bank (the engine's
        pow2 occupied-lane prefix — lightly-loaded banks never pay the
        full-S solve), bitwise-identical to ``solve_bank`` on that
        prefix; ``B_res`` is already on device, so nothing re-uploads."""
        raise NotImplementedError(
            f"backend {cls.backend!r} does not support resident RHS "
            "slots (no 'slots' capability)"
        )

    def _check_data(self, data: np.ndarray) -> np.ndarray:
        """Reject mis-sized update data. The device gather clamps
        out-of-range indices (same hazard solve() guards against for b),
        so without this check a wrong-pattern data vector would silently
        produce garbage values instead of raising."""
        data = np.asarray(data)
        if data.ndim != 1 or data.shape[0] != self.n_entries:
            raise ValueError(
                f"update_values expects the planned pattern's entry data "
                f"f[{self.n_entries}]; got shape {data.shape}"
            )
        return data

    @abc.abstractmethod
    def solve(self, b):
        """Solve for ``b`` f[n] or f[n, m]; returns x shaped like b."""

    def solve_raw(self, b):
        """``solve`` with x in the bound's own output order (see
        ``out_index``)."""
        return self.solve(b)

    def solve_timed(self, b):
        """``solve_raw`` plus per-step device timings: returns ``(x,
        steps)`` where ``steps`` is a list of JSON-ready dicts, finest
        granularity the backend can observe. This base fallback times
        the whole blocked solve as ONE step (backends without segmented
        dispatch — pallas tiles, shard_map supersteps — still report a
        synchronized wall-clock); the scan backend overrides it with
        per-superstep (bulk) / per-macro-step (elastic) segments."""
        import time as _time

        with obs.span("executor.solve_timed", cat="executor", n=self.n):
            t0 = _time.perf_counter_ns()
            x = self.solve_raw(b)
            try:
                x.block_until_ready()
            except AttributeError:  # plain ndarray result
                pass
            dur = _time.perf_counter_ns() - t0
        return x, [{"step": 0, "n_steps": None, "us": round(dur / 1e3, 2)}]

    @abc.abstractmethod
    def update_values(self, data: np.ndarray) -> "BoundSolve":
        """Device-side numeric refresh from ``data`` (the ``.data`` of a
        matrix with the planned pattern, in plan entry order). Returns a
        NEW BoundSolve sharing index tensors; self is untouched."""

    @abc.abstractmethod
    def describe(self) -> dict:
        """JSON-ready binding telemetry (backend, shapes, device bytes)."""


class Backend(abc.ABC):
    """A named execution backend — a ``BoundSolve`` factory."""

    name: str

    @abc.abstractmethod
    def bind(
        self,
        exec_plan,
        *,
        dtype=np.float32,
        steps_per_tile: int = 8,
        interpret=None,
        mesh=None,
        slack: int = 0,
        shard: str = "model",
    ) -> BoundSolve:
        """Transfer ``exec_plan``'s tensors and return a ``BoundSolve``.
        Irrelevant parameters are accepted and ignored so callers can
        pass one uniform binding-parameter set to every backend.

        ``slack > 0`` requests ``mode="elastic"`` (bounded-slack
        macro-step execution, see ``core.elastic``); backends that do
        not advertise the ``"elastic"`` capability must raise a clear
        error rather than silently fall back to bulk-synchronous.

        ``shard`` selects the mesh decomposition for multi-device
        backends: ``"model"`` (default — k schedule cores over the
        model axis) or ``"rows"`` (row partition + halo exchange,
        capability ``"shard-rows"``). Backends that do not advertise
        the requested mode must raise, not silently rebind."""

    def requires(self) -> Tuple[str, ...]:
        """Names of binding params this backend cannot run without
        (e.g. ``("mesh",)`` for the distributed backend)."""
        return ()

    def capabilities(self) -> Tuple[str, ...]:
        """Optional feature names this backend's bounds implement beyond
        the core contract. Known capabilities: ``"grouped"`` — the bound
        solves one rhs per plan in a single width-class dispatch
        (``BoundSolve.solve_grouped``; the serve layer's cross-pattern
        microbatching keys on it); ``"elastic"`` — ``bind(slack=s)``
        executes the bounded-slack macro-step mode (``core.elastic``),
        bitwise-identical to the bulk-synchronous bound; ``"slots"`` —
        persistent device-resident RHS slots on the stacked bank
        (``blank_rhs``/``insert_lane``/``extract_lane``/
        ``solve_resident``; the continuous-batching serve engine,
        ``repro.serve.slots``, requires it); ``"shard-rows"`` —
        ``bind(shard="rows")`` row-partitions one plan across the
        mesh's ``model`` axis with halo exchange instead of per-core
        sharding (``core.rowshard`` / ``solver.rowsharded``)."""
        return ()
