"""Pallas backend — the TPU kernel executor behind the ``Backend``
protocol (kernel in ``repro.kernels.sptrsv``, tile padding shared with
``repro.kernels.ops``)."""
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


class PallasBoundSolve(BoundSolve):
    """The kernel bound to one plan. ``elastic`` (the certificate from
    ``core.elastic``) marks a ``mode="elastic"`` binding: the same kernel
    with the tile size set to the slack window, so one grid step runs one
    macro-step; bitwise-identical to the bulk binding of the plan."""

    backend = "pallas"

    def __init__(self, arrays, val_src, diag_src, *, n, n_entries,
                 np_dtype, steps_per_tile, interpret, elastic=None):
        # arrays = (row_ids, col_idx, vals, diag, accum_mask), tile-padded
        self._arrays = arrays
        self._val_src = val_src  # int32[T_pad, k, W] device (-1 padded)
        self._diag_src = diag_src  # int32[T_pad, k] device (-1 padded)
        self.n = n
        self.n_entries = n_entries
        self._np_dtype = np_dtype
        self._steps_per_tile = steps_per_tile
        self._interpret = interpret
        self._elastic = elastic
        # runtime side of the elastic certificate (cf. the scan elastic
        # bound): the kernel grid runs exactly n_macro_steps tiles per
        # solve, so a timed solve records that many executed macro-steps
        self._runtime = {"timed_solves": 0, "macro_steps_executed": 0}

    def solve(self, b):
        from repro.kernels.ops import solve_with_kernel_arrays

        return solve_with_kernel_arrays(
            self._arrays, b, n=self.n,
            steps_per_tile=self._steps_per_tile,
            interpret=self._interpret, dtype=self._np_dtype,
        )

    def solve_timed(self, b):
        """Whole-solve timing (the kernel grid is one dispatch — there
        is no host-visible per-tile boundary), plus the elastic runtime
        bookkeeping ``describe()`` reports against the certificate."""
        x, steps = super().solve_timed(b)
        if self._elastic is not None:
            self._runtime["timed_solves"] += 1
            self._runtime["macro_steps_executed"] += (
                self._elastic.n_macro_steps
            )
        return x, steps

    def update_values(self, data: np.ndarray) -> "PallasBoundSolve":
        import jax.numpy as jnp

        with obs.span(
            "backend.update_values", cat="backend", backend=self.backend
        ):
            data = jnp.asarray(
                self._check_data(data).astype(self._np_dtype)
            )
            row_ids, col_idx, vals, diag, accum = self._arrays
            vals, diag = masked_value_gather(
                data, self._val_src, vals, self._diag_src, diag
            )
        return PallasBoundSolve(
            (row_ids, col_idx, vals, diag, accum),
            self._val_src,
            self._diag_src,
            n=self.n,
            n_entries=self.n_entries,
            np_dtype=self._np_dtype,
            steps_per_tile=self._steps_per_tile,
            interpret=self._interpret,
            elastic=self._elastic,
        )

    def describe(self) -> dict:
        T, k = self._arrays[0].shape
        W = self._arrays[1].shape[-1]
        out = {
            "backend": self.backend,
            "n": self.n,
            "n_steps": T,  # tile-padded
            "k": k,
            "W": W,
            "dtype": np.dtype(self._np_dtype).name,
            "steps_per_tile": self._steps_per_tile,
            "interpret": bool(self._interpret),
            "device_bytes": int(
                sum(a.size * a.dtype.itemsize
                    for a in self._arrays + (self._val_src, self._diag_src))
            ),
        }
        ep = self._elastic
        if ep is None:
            return out
        cert = ep.stats()
        rt = dict(self._runtime)
        if rt["timed_solves"]:
            rt["macro_steps_per_solve"] = round(
                rt["macro_steps_executed"] / rt["timed_solves"], 2
            )
        out.update(
            mode="elastic",
            n_macro_steps=ep.n_macro_steps,
            slack=ep.slack,
            runtime={
                **rt,
                "predicted_macro_steps": ep.n_macro_steps,
                "predicted_barrier_fusion": cert.get("barrier_fusion"),
                "predicted_step_fusion": cert.get("step_fusion"),
            },
        )
        return out


@register_backend
class PallasBackend(Backend):
    """Grid-of-tiles Pallas kernel; x resident in VMEM, plan tensors
    streamed per tile. On a TPU the kernel always lowers through Mosaic;
    elsewhere ``interpret`` defaults to True and the Pallas interpreter
    executes the same kernel logic. ``bind(slack=s)`` runs the kernel
    with the tile size set to the slack window (``"elastic"``
    capability)."""

    name = "pallas"

    def capabilities(self):
        return ("elastic",)

    def bind(self, exec_plan, *, dtype=np.float32, steps_per_tile=8,
             interpret=None, mesh=None, slack=0,
             shard="model") -> BoundSolve:
        if shard != "model":
            raise ValueError(
                f"backend='pallas' does not support shard={shard!r} "
                "(no 'shard-rows' capability); use backend='distributed'"
            )
        import jax
        import jax.numpy as jnp

        from repro.kernels.ops import _pad_steps, kernel_plan_arrays

        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        assert exec_plan.val_src is not None and exec_plan.diag_src is not None
        ep = None
        if slack > 0:
            from repro.core.elastic import elastic_transform

            ep = exec_plan.elastic
            if ep is None or ep.slack != slack:
                ep = elastic_transform(exec_plan, slack)
            # one grid step per macro-step: the tile IS the slack window
            steps_per_tile = slack
        arrays = kernel_plan_arrays(
            exec_plan, steps_per_tile=steps_per_tile, dtype=dtype
        )
        # source maps ride the same tile padding; -1 marks padding slots so
        # device-side refreshes leave them untouched
        val_src = _pad_steps(exec_plan.val_src, steps_per_tile, -1)
        diag_src = _pad_steps(exec_plan.diag_src, steps_per_tile, -1)
        return PallasBoundSolve(
            arrays,
            jnp.asarray(val_src, jnp.int32),
            jnp.asarray(diag_src, jnp.int32),
            n=exec_plan.n,
            n_entries=expected_entry_count(exec_plan),
            np_dtype=np.dtype(dtype),
            steps_per_tile=steps_per_tile,
            interpret=interpret,
            elastic=ep,
        )
