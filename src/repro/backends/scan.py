"""Scan backend — the single-chip `lax.scan` executor behind the
``Backend`` protocol (device work in ``repro.solver.executor``)."""
from __future__ import annotations

import numpy as np

from repro import obs
from repro.backends.base import (
    Backend,
    BoundSolve,
    expected_entry_count,
    masked_value_gather,
)
from repro.backends.registry import register_backend


class ScanBoundSolve(BoundSolve):
    backend = "scan"
    # the scan trace reads only the plan tensor shapes (step_bounds never
    # enter it), so structurally-identical plans can share one vmapped
    # dispatch — the serve layer's width-class cross-pattern batching
    supports_grouped = True

    def __init__(self, pa, val_src, diag_src, np_dtype, n_entries):
        self._pa = pa  # solver.executor.PlanArrays (device-resident)
        self._val_src = val_src  # int32[T, W, k] device (step layout)
        self._diag_src = diag_src  # int32[T, k] device
        self._np_dtype = np_dtype
        self.n = pa.n
        self.n_entries = n_entries

    def solve(self, b):
        from repro.solver.executor import solve_with_plan

        return solve_with_plan(self._pa, b)

    def solve_timed(self, b):
        """Per-superstep timed solve: one jitted segment per superstep
        of the plan (see ``solver.executor.solve_with_plan_timed``)."""
        from repro.solver.executor import solve_with_plan_timed

        return solve_with_plan_timed(self._pa, b)

    @classmethod
    def solve_grouped(cls, bounds, b_cols):
        from repro.solver.executor import solve_with_plan_group

        return solve_with_plan_group([b._pa for b in bounds], b_cols)

    @classmethod
    def stack_bank(cls, bounds, perms, invs):
        from repro.solver.executor import stack_plan_bank

        return stack_plan_bank([b._pa for b in bounds], perms, invs)

    @classmethod
    def solve_bank(cls, bank, lane_idx, B):
        from repro.solver.executor import solve_with_bank

        return solve_with_bank(bank, lane_idx, B)

    # resident RHS slots ("slots" capability) — the continuous-batching
    # serve engine's device contract, all thin wrappers over the jitted
    # executor ops (one compiled variant per (n, S) shape)
    @classmethod
    def blank_rhs(cls, n, slots, dtype):
        from repro.solver.executor import blank_rhs

        return blank_rhs(n, slots, dtype)

    @classmethod
    def insert_lane(cls, B_res, lane, b):
        from repro.solver.executor import insert_lane

        return insert_lane(B_res, lane, b)

    @classmethod
    def extract_lane(cls, X, lane):
        from repro.solver.executor import extract_lane

        return extract_lane(X, lane)

    @classmethod
    def solve_resident(cls, bank, lane_idx, B_res):
        from repro.solver.executor import solve_resident

        return solve_resident(bank, lane_idx, B_res)

    def update_values(self, data: np.ndarray) -> "ScanBoundSolve":
        import jax.numpy as jnp

        with obs.span(
            "backend.update_values", cat="backend", backend=self.backend
        ):
            data = jnp.asarray(
                self._check_data(data).astype(self._np_dtype)
            )
            vals, diag = masked_value_gather(
                data,
                self._val_src,
                self._pa.vals,
                self._diag_src,
                self._pa.diag,
            )
        new = ScanBoundSolve(
            self._pa._replace(vals=vals, diag=diag),
            self._val_src,  # index tensors shared, read-only
            self._diag_src,
            self._np_dtype,
            self.n_entries,
        )
        return new

    def describe(self) -> dict:
        T, W, k = self._pa.vals.shape
        return {
            "backend": self.backend,
            "n": self.n,
            "n_steps": T,
            "k": k,
            "W": W,
            "dtype": np.dtype(self._np_dtype).name,
            "device_bytes": int(
                sum(a.size * a.dtype.itemsize
                    for a in self._pa[:5] + (self._val_src, self._diag_src))
            ),
        }


class ElasticScanBoundSolve(BoundSolve):
    """The ``mode="elastic"`` scan bound: ``ceil(T / slack)`` fused
    macro-steps instead of T scan steps (``core.elastic``), bitwise-
    identical to ``ScanBoundSolve`` on the same plan."""

    backend = "scan"
    # the macro-step tensors bake the slack window into the trace shape
    # and the elastic bound has no banked/grouped twin — width-class
    # grouping stays on the bulk-synchronous bound
    supports_grouped = False

    def __init__(self, ea, elastic, val_src, diag_src, np_dtype, n_entries):
        self._ea = ea  # solver.executor.ElasticArrays (device-resident)
        self._elastic = elastic  # core.elastic.ElasticPlan certificate
        self._val_src = val_src  # int32[M, S, W, k] device (-1 padded)
        self._diag_src = diag_src  # int32[M, S, k] device (-1 padded)
        self._np_dtype = np_dtype
        self.n = ea.n
        self.n_entries = n_entries
        # runtime side of the elastic certificate: what timed solves
        # actually executed, reported by describe() next to the
        # certificate's predicted fusion ratios (fresh per bound; an
        # update_values swap starts a new runtime history)
        self._runtime = {"timed_solves": 0, "macro_steps_executed": 0}

    def solve(self, b):
        from repro.solver.executor import solve_with_elastic

        return solve_with_elastic(self._ea, b)

    def solve_timed(self, b):
        """Per-macro-step timed elastic solve; records the actual
        macro-step count into the bound's runtime telemetry so
        ``describe()`` can put measured execution next to the
        certificate's predicted ``barrier_fusion``."""
        from repro.solver.executor import solve_with_elastic_timed

        x, steps = solve_with_elastic_timed(self._ea, b)
        self._runtime["timed_solves"] += 1
        self._runtime["macro_steps_executed"] += len(steps)
        return x, steps

    def update_values(self, data: np.ndarray) -> "ElasticScanBoundSolve":
        import jax.numpy as jnp

        with obs.span(
            "backend.update_values", cat="backend", backend=self.backend
        ):
            data = jnp.asarray(
                self._check_data(data).astype(self._np_dtype)
            )
            vals, diag = masked_value_gather(
                data,
                self._val_src,
                self._ea.vals,
                self._diag_src,
                self._ea.diag,
            )
        return ElasticScanBoundSolve(
            self._ea._replace(vals=vals, diag=diag),
            self._elastic,
            self._val_src,  # index tensors shared, read-only
            self._diag_src,
            self._np_dtype,
            self.n_entries,
        )

    def describe(self) -> dict:
        M, S, W, k = self._ea.vals.shape
        cert = self._elastic.stats() if self._elastic is not None else {}
        rt = dict(self._runtime)
        if rt["timed_solves"]:
            rt["macro_steps_per_solve"] = round(
                rt["macro_steps_executed"] / rt["timed_solves"], 2
            )
        return {
            "backend": self.backend,
            "mode": "elastic",
            "n": self.n,
            "n_steps": self._ea.n_steps,
            "n_macro_steps": M,
            "slack": S,
            "k": k,
            "W": W,
            "dtype": np.dtype(self._np_dtype).name,
            "device_bytes": int(
                sum(a.size * a.dtype.itemsize
                    for a in self._ea[:5] + (self._val_src, self._diag_src))
            ),
            # certificate (predicted) vs runtime (measured, from
            # solve_timed): the elastic fused-barrier claim, executed
            "runtime": {
                **rt,
                "predicted_macro_steps": M,
                "predicted_barrier_fusion": cert.get("barrier_fusion"),
                "predicted_step_fusion": cert.get("step_fusion"),
            },
        }


@register_backend
class ScanBackend(Backend):
    """One `lax.scan` over the plan; superstep barriers are free on a
    single chip, so `step_bounds` is ignored here. ``bind(slack=s)``
    switches to the elastic macro-step executor (``"elastic"``
    capability)."""

    name = "scan"

    def capabilities(self):
        return ("grouped", "elastic", "slots")

    def bind(self, exec_plan, *, dtype=np.float32, steps_per_tile=8,
             interpret=None, mesh=None, slack=0,
             shard="model") -> BoundSolve:
        if shard != "model":
            raise ValueError(
                f"backend='scan' does not support shard={shard!r} "
                "(no 'shard-rows' capability); use backend='distributed'"
            )
        import jax.numpy as jnp

        from repro.solver.executor import plan_arrays, w_major

        assert exec_plan.val_src is not None and exec_plan.diag_src is not None
        if slack > 0:
            from repro.core.elastic import elastic_transform
            from repro.solver.executor import (
                _pad_to_window,
                elastic_plan_arrays,
            )

            ep = exec_plan.elastic
            if ep is None or ep.slack != slack:
                ep = elastic_transform(exec_plan, slack)
            ea = elastic_plan_arrays(exec_plan, slack=slack, dtype=dtype)
            M, S = ea.write_rows.shape[:2]
            pad = M * S - exec_plan.n_steps
            # source maps ride the same window padding and step layout;
            # -1 marks padding so device-side refreshes leave those slots
            # untouched
            val_src = w_major(_pad_to_window(exec_plan.val_src, pad, -1))
            diag_src = _pad_to_window(exec_plan.diag_src, pad, -1)
            return ElasticScanBoundSolve(
                ea,
                ep,
                jnp.asarray(val_src.reshape(M, S, *val_src.shape[1:]),
                            jnp.int32),
                jnp.asarray(diag_src.reshape(M, S, *diag_src.shape[1:]),
                            jnp.int32),
                np.dtype(dtype),
                expected_entry_count(exec_plan),
            )
        pa = plan_arrays(exec_plan, dtype=dtype)
        return ScanBoundSolve(
            pa,
            jnp.asarray(w_major(exec_plan.val_src), jnp.int32),
            jnp.asarray(exec_plan.diag_src, jnp.int32),
            np.dtype(dtype),
            expected_entry_count(exec_plan),
        )
