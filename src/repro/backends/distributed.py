"""Distributed backend — the shard_map executors behind the ``Backend``
protocol (device work in ``repro.solver.distributed`` and
``repro.solver.rowsharded``).

Two mesh decompositions of one plan:

  * ``shard="model"`` (default): the k schedule cores are k devices on
    the mesh's ``model`` axis; every barrier ``all_gather``s the
    superstep's solved values. Simple, but a solve must fit one
    device's plan and barrier traffic is O(k·T) values per device.
  * ``shard="rows"`` (capability ``"shard-rows"``): the plan's rows are
    partitioned into contiguous core blocks (``core.rowshard``), each
    device runs its shard's local scan against a resident x-shard, and
    barriers exchange ONLY the boundary values other shards read —
    static ``ppermute`` rings (or one sparse ``psum``) instead of the
    O(n) all-gather. Also lifts the k <= model-axis restriction (each
    device simulates ``k_local`` lanes).

Both modes execute ``bind(slack=s)`` elastically (capability
``"elastic"``): the fused-run certificate (``core.elastic``) collapses
greedy superstep runs into single exchange rounds — the certificate
guarantees no cross-device read of a value written inside a fused run,
so the fused barrier schedule is exactly as correct as the
per-superstep one. ``describe()`` reports executed vs predicted fusion.

The RHS batch shards over ``data`` in both modes. Jitted solves are
cached per padded batch size, and that cache is SHARED across
``update_values`` clones — the lowered graph is shape-only, so a live
refactorization never recompiles, it only swaps the value operands.
"""
from __future__ import annotations

import dataclasses
import threading

import numpy as np

from repro import obs
from repro.backends.base import (
    Backend,
    BoundSolve,
    expected_entry_count,
    masked_value_gather,
)
from repro.backends.registry import register_backend


class DistributedBoundSolve(BoundSolve):
    backend = "distributed"

    def __init__(self, spec, mesh, args, val_src, diag_src, np_dtype,
                 n_entries, jitted=None, jit_lock=None, exchange_info=None):
        # args = (row_ids, col_idx, vals, diag, accum_mask) device arrays
        self._spec = spec  # solver.distributed.DistPlanSpec (batch unset)
        self._mesh = mesh
        self._args = args
        self._val_src = val_src
        self._diag_src = diag_src
        self._np_dtype = np_dtype
        # static comm telemetry (executed/predicted barrier fusion, comm
        # volume model) merged into describe()["exchange"]
        self._exchange_info = exchange_info
        # padded-batch -> jitted solve; shape-only, shared across value
        # refreshes so serve version swaps reuse every compiled variant.
        # The lock rides along with it: serve worker threads insert while
        # telemetry threads snapshot (describe()).
        self._jitted = {} if jitted is None else jitted
        self._jit_lock = threading.Lock() if jit_lock is None else jit_lock
        self.n = spec.n
        self.n_entries = n_entries

    def solve(self, b):
        import jax
        import jax.numpy as jnp

        from repro.solver.distributed import build_distributed_solver

        b2 = np.asarray(b)
        single = b2.ndim == 1
        b2 = b2[None, :] if single else np.ascontiguousarray(b2.T)
        B = b2.shape[0]
        # the batch shards over 'data': pad it to a multiple
        data_ax = self._mesh.shape["data"]
        Bp = -(-B // data_ax) * data_ax
        b2 = np.concatenate([b2, np.zeros((Bp - B, b2.shape[1]), b2.dtype)])
        b_pad = np.concatenate([b2, np.zeros((Bp, 1), b2.dtype)], axis=1)
        with self._jit_lock:
            fn = self._jitted.get(Bp)
        if fn is None:
            spec = dataclasses.replace(self._spec, batch=Bp)
            fn = jax.jit(build_distributed_solver(spec, self._mesh))
            with self._jit_lock:
                fn = self._jitted.setdefault(Bp, fn)
        with self._mesh:
            x = fn(*self._args, jnp.asarray(b_pad, self._np_dtype))
        # slice/transpose on device — pulling the sharded result through
        # np.asarray and re-uploading it would round-trip host memory per
        # batch; the caller materializes the returned array exactly once
        # (return type consistent with the scan/pallas backends)
        x = x[:, : self.n]
        return x[0] if single else x[:B].T

    def update_values(self, data: np.ndarray) -> "DistributedBoundSolve":
        import jax.numpy as jnp

        with obs.span(
            "backend.update_values", cat="backend", backend=self.backend
        ):
            data = jnp.asarray(
                self._check_data(data).astype(self._np_dtype)
            )
            row_ids, col_idx, vals, diag, accum = self._args
            vals, diag = masked_value_gather(
                data, self._val_src, vals, self._diag_src, diag
            )
        return DistributedBoundSolve(
            self._spec,
            self._mesh,
            (row_ids, col_idx, vals, diag, accum),
            self._val_src,
            self._diag_src,
            self._np_dtype,
            self.n_entries,
            jitted=self._jitted,  # shapes unchanged -> reuse compilations
            jit_lock=self._jit_lock,
            exchange_info=self._exchange_info,
        )

    def describe(self) -> dict:
        with self._jit_lock:  # solve() may be inserting concurrently
            compiled = sorted(self._jitted)
        n_sup = len(self._spec.step_bounds) - 1
        rounds = (
            len(self._spec.exchange_steps) - 1
            if self._spec.exchange_steps is not None
            else n_sup
        )
        # comm-volume model per device per RHS: every barrier gathers
        # each core's xv for the run's steps -> k * T values per solve
        ag_values = int(self._spec.k * self._spec.T)
        exchange = {
            "mode": "all_gather",
            "shard": "model",
            "rounds": rounds,
            "n_supersteps": n_sup,
            "executed_fusion": round(n_sup / max(rounds, 1), 4),
            "comm_values_per_solve": ag_values,
            "comm_bytes_per_solve": ag_values
            * np.dtype(self._np_dtype).itemsize,
        }
        if self._exchange_info:
            exchange.update(self._exchange_info)
        return {
            "backend": self.backend,
            "shard": "model",
            "n": self.n,
            "n_steps": self._spec.T,
            "k": self._spec.k,
            "W": self._spec.W,
            "n_supersteps": n_sup,
            "dtype": np.dtype(self._np_dtype).name,
            "mesh": dict(self._mesh.shape),
            "compiled_batch_sizes": compiled,
            "device_bytes": int(
                sum(a.size * a.dtype.itemsize
                    for a in self._args + (self._val_src, self._diag_src))
            ),
            "exchange": exchange,
        }


class RowShardedBoundSolve(BoundSolve):
    """The ``shard="rows"`` bound: per-device local plans with resident
    x-shards and halo exchange (``core.rowshard`` partition,
    ``solver.rowsharded`` executor). ``update_values`` gathers new entry
    data through the stacked GLOBAL-entry source maps — each shard's
    local plan keeps the caller's entry ids, so a refactorization is one
    device gather, no repartition."""

    backend = "distributed"

    def __init__(self, rsp, mesh, mode, plan_args, halo_args, val_src,
                 diag_src, np_dtype, n_entries, exchange_info=None,
                 jitted=None, jit_lock=None):
        self._rsp = rsp  # core.rowshard.RowShardPlan (host tensors)
        self._mesh = mesh
        self._mode = mode  # "ring" | "psum"
        self._plan_args = plan_args  # stacked [n_shards, T, ...], step layout
        self._halo_args = halo_args  # flat int32 exchange tables
        self._val_src = val_src  # stacked GLOBAL entry ids
        self._diag_src = diag_src
        self._np_dtype = np_dtype
        self._exchange_info = exchange_info
        # padded-batch -> jitted solve (0 = single RHS); shared across
        # update_values clones like the model-axis bound. The timed path
        # keeps its per-round fns under negative-keyed entries.
        self._jitted = {} if jitted is None else jitted
        self._jit_lock = threading.Lock() if jit_lock is None else jit_lock
        self.n = rsp.n
        self.n_entries = n_entries
        self._comm = rsp.comm_stats(np.dtype(np_dtype).itemsize)

    # ---------------------------------------------------------- helpers
    def _spec(self, batch: int):
        from repro.solver.rowsharded import rowshard_spec

        return rowshard_spec(self._rsp, mode=self._mode, batch=batch)

    def _scatter_b(self, b2, mp):
        """Host-scatter the rhs into per-shard local slots. b2 f[n, mp]
        or f[n] -> f[n_shards, slots(, mp)] (halo/scratch slots zero)."""
        rsp = self._rsp
        slots = rsp.slots
        if b2.ndim == 1:
            bl = np.zeros(rsp.n_shards * slots, self._np_dtype)
            bl[rsp.b_scatter] = b2
            return bl.reshape(rsp.n_shards, slots)
        bl = np.zeros((rsp.n_shards * slots, mp), self._np_dtype)
        bl[rsp.b_scatter] = b2
        return bl.reshape(rsp.n_shards, slots, mp)

    def _gather_x(self, out, m=None):
        """Stacked owned regions -> global row order (device-side)."""
        import jax.numpy as jnp

        rsp = self._rsp
        gather = jnp.asarray(rsp.x_gather, jnp.int32)
        if m is None:
            return out.reshape(rsp.n_shards * rsp.n_loc)[gather]
        return out.reshape(rsp.n_shards * rsp.n_loc, -1)[gather]

    def _count_comm(self, n_rhs: int):
        per = (
            self._comm["halo_values_psum"]
            if self._mode == "psum"
            else self._comm["halo_values_per_solve"]
        )
        obs.counter_add("rowshard.halo_values", per * n_rhs)
        obs.counter_add(
            "rowshard.halo_bytes",
            per * n_rhs * np.dtype(self._np_dtype).itemsize,
        )

    # ------------------------------------------------------------ solve
    def solve(self, b):
        import jax
        import jax.numpy as jnp

        from repro.solver.rowsharded import build_rowsharded_solver

        b2 = np.asarray(b).astype(self._np_dtype)
        single = b2.ndim == 1
        if single:
            key, mp = 0, None
        else:
            m = b2.shape[1]
            data_ax = self._mesh.shape["data"]
            mp = -(-m // data_ax) * data_ax
            if mp > m:
                b2 = np.concatenate(
                    [b2, np.zeros((b2.shape[0], mp - m), b2.dtype)], axis=1
                )
            key = mp
        with self._jit_lock:
            fn = self._jitted.get(key)
        if fn is None:
            spec = self._spec(0 if single else mp)
            fn = jax.jit(build_rowsharded_solver(spec, self._mesh))
            with self._jit_lock:
                fn = self._jitted.setdefault(key, fn)
        b_loc = jnp.asarray(self._scatter_b(b2, mp))
        self._count_comm(1 if single else mp)
        with obs.span(
            "rowshard.solve",
            cat="backend",
            n=self.n,
            n_shards=self._rsp.n_shards,
            mode=self._mode,
            halo_bytes=self._comm["halo_bytes_per_solve"],
        ):
            with self._mesh:
                out = fn(*self._plan_args, *self._halo_args, b_loc)
            x = self._gather_x(out, m=None if single else mp)
        return x if single else x[:, : m]

    def solve_timed(self, b):
        """Per-exchange-round device timing: each round (local scan +
        its halo exchange) runs as one shard-mapped call on a carried
        global x, host-timed around ``block_until_ready`` — the runtime
        side of the halo-vs-all_gather comm claim. Numerically identical
        to ``solve`` (same step bodies, same exchange ops; the per-round
        accumulator re-zeroes are exact because virtual-row chains never
        span a superstep barrier)."""
        import time as _time

        import jax
        import jax.numpy as jnp

        from repro.solver.rowsharded import (
            build_rowsharded_round,
            halo_args_for_round,
        )

        rsp = self._rsp
        b2 = np.asarray(b).astype(self._np_dtype)
        single = b2.ndim == 1
        if single:
            mp = None
            batch = 0
        else:
            m = b2.shape[1]
            data_ax = self._mesh.shape["data"]
            mp = -(-m // data_ax) * data_ax
            if mp > m:
                b2 = np.concatenate(
                    [b2, np.zeros((b2.shape[0], mp - m), b2.dtype)], axis=1
                )
            batch = mp
        spec = self._spec(batch)
        b_loc = jnp.asarray(self._scatter_b(b2, mp))
        shape = (
            (rsp.n_shards, spec.slots)
            if single
            else (rsp.n_shards, spec.slots, mp)
        )
        x_glob = jnp.zeros(shape, self._np_dtype)
        self._count_comm(1 if single else mp)
        sb, eb = spec.step_bounds, spec.exchange_bounds
        steps = []
        itemsize = np.dtype(self._np_dtype).itemsize
        n_rhs = 1 if single else mp
        with self._mesh:
            for r in range(spec.n_rounds):
                key = (-1, r, batch)
                with self._jit_lock:
                    fn = self._jitted.get(key)
                if fn is None:
                    fn = jax.jit(
                        build_rowsharded_round(spec, self._mesh, r)
                    )
                    with self._jit_lock:
                        fn = self._jitted.setdefault(key, fn)
                halo = (
                    halo_args_for_round(rsp, r, self._mode)
                    if r < spec.n_rounds - 1
                    else ()
                )
                hv = (
                    rsp.rounds[r].ring_values
                    if self._mode == "ring"
                    else rsp.rounds[r].buf_size
                ) if r < spec.n_rounds - 1 else 0
                with obs.span(
                    "executor.exchange_round",
                    cat="executor",
                    round=r,
                    supersteps=eb[r + 1] - eb[r],
                    halo_bytes=hv * itemsize * n_rhs,
                ):
                    t0 = _time.perf_counter_ns()
                    x_glob = fn(
                        *self._plan_args, *halo, b_loc, x_glob
                    )
                    x_glob.block_until_ready()
                    dur = _time.perf_counter_ns() - t0
                steps.append(
                    {
                        "round": r,
                        "n_steps": sb[eb[r + 1]] - sb[eb[r]],
                        "halo_values": hv * n_rhs,
                        "halo_bytes": hv * itemsize * n_rhs,
                        "us": round(dur / 1e3, 2),
                    }
                )
            x = self._gather_x(
                x_glob[:, : rsp.n_loc], m=None if single else mp
            )
        return (x if single else x[:, : m]), steps

    def update_values(self, data: np.ndarray) -> "RowShardedBoundSolve":
        import jax.numpy as jnp

        with obs.span(
            "backend.update_values", cat="backend", backend=self.backend
        ):
            data = jnp.asarray(
                self._check_data(data).astype(self._np_dtype)
            )
            rows, cols, vals, diag, accum = self._plan_args
            vals, diag = masked_value_gather(
                data, self._val_src, vals, self._diag_src, diag
            )
        return RowShardedBoundSolve(
            self._rsp,
            self._mesh,
            self._mode,
            (rows, cols, vals, diag, accum),
            self._halo_args,
            self._val_src,
            self._diag_src,
            self._np_dtype,
            self.n_entries,
            exchange_info=self._exchange_info,
            jitted=self._jitted,  # shapes unchanged -> reuse compilations
            jit_lock=self._jit_lock,
        )

    def describe(self) -> dict:
        with self._jit_lock:
            compiled = sorted(
                k for k in self._jitted if not isinstance(k, tuple)
            )
        rsp = self._rsp
        n_sup = len(rsp.step_bounds) - 1
        exchange = {
            "mode": self._mode,
            "shard": "rows",
            "rounds": rsp.n_rounds,
            "n_supersteps": n_sup,
            "executed_fusion": round(n_sup / max(rsp.n_rounds, 1), 4),
            "comm_values_per_solve": (
                self._comm["halo_values_psum"]
                if self._mode == "psum"
                else self._comm["halo_values_per_solve"]
            ),
            "comm_bytes_per_solve": (
                self._comm["halo_values_psum"]
                if self._mode == "psum"
                else self._comm["halo_values_per_solve"]
            ) * np.dtype(self._np_dtype).itemsize,
            **{
                k: self._comm[k]
                for k in (
                    "halo_pairs",
                    "halo_values_per_solve",
                    "halo_bytes_per_solve",
                    "halo_values_max_round",
                    "allgather_values",
                    "allgather_bytes",
                    "halo_ratio",
                    "active_exchanges",
                )
            },
        }
        if self._exchange_info:
            exchange.update(self._exchange_info)
        return {
            "backend": self.backend,
            "shard": "rows",
            "n": self.n,
            "n_steps": rsp.T,
            "k": rsp.n_shards * rsp.k_local,
            "k_local": rsp.k_local,
            "W": rsp.W,
            "n_shards": rsp.n_shards,
            "n_loc": rsp.n_loc,
            "n_halo": rsp.n_halo,
            "n_supersteps": n_sup,
            "dtype": np.dtype(self._np_dtype).name,
            "mesh": dict(self._mesh.shape),
            "compiled_batch_sizes": compiled,
            "device_bytes": int(
                sum(
                    a.size * a.dtype.itemsize
                    for a in self._plan_args
                    + self._halo_args
                    + (self._val_src, self._diag_src)
                )
            ),
            "exchange": exchange,
        }


def _auto_axes(mesh):
    """``mesh`` with every axis typed ``AxisType.Auto``. A plain
    ``jax.make_mesh(...)`` types its axes Explicit, under which the
    host-side slicing of a sharded result (the batch/scratch trim, the
    row-shard gather) raises instead of resolving its sharding; the
    bound solves index their results freely, so they run on an Auto
    view of the same devices."""
    from jax.sharding import AxisType, Mesh

    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(
        mesh.devices, mesh.axis_names,
        axis_types=(AxisType.Auto,) * len(mesh.axis_names),
    )


def _pad_cores(plan, model_ax: int):
    """Pad the plan's core axis UP to the mesh's ``model`` axis size so
    narrower schedules (e.g. serial's k=1 chains) shard cleanly — the
    executor assigns exactly one schedule core per model-axis device, so
    k must end up equal to it. A plan with MORE cores than devices
    cannot be executed (each device's scan walks one chain) and is
    rejected with a clear error instead of failing at trace time.
    Padding lanes follow the plan's own protocol — row id n (scratch),
    self-gathers, val 0 / diag 1, source maps -1 — so they compute
    harmless writes to the scratch slot."""
    k, kp = plan.k, model_ax
    if k > model_ax:
        raise ValueError(
            f"distributed backend: plan has k={k} schedule cores but the "
            f"mesh 'model' axis has only {model_ax} devices — schedule "
            f"with k <= mesh.shape['model'] (one core per device)"
        )
    if kp == k:
        return plan
    T, pad = plan.n_steps, kp - k

    def padk(a, fill):
        block = np.full((T, pad, *a.shape[2:]), fill, dtype=a.dtype)
        return np.concatenate([a, block], axis=1)

    return dataclasses.replace(
        plan,
        k=kp,
        row_ids=padk(plan.row_ids, plan.n),
        col_idx=padk(plan.col_idx, plan.n),
        vals=padk(plan.vals, 0),
        diag=padk(plan.diag, 1),
        accum=padk(plan.accum, False),
        val_src=None if plan.val_src is None else padk(plan.val_src, -1),
        diag_src=None if plan.diag_src is None else padk(plan.diag_src, -1),
    )


@register_backend
class DistributedBackend(Backend):
    """BSP on a device mesh: ``shard="model"`` — one all-gather barrier
    per exchange round; ``shard="rows"`` — row partition with halo
    exchange. ``bind(slack=s)`` fuses certified superstep runs into
    single exchange rounds in either mode."""

    name = "distributed"

    def requires(self):
        return ("mesh",)

    def capabilities(self):
        return ("elastic", "shard-rows")

    @staticmethod
    def _fused(exec_plan, slack):
        """The elastic certificate for ``slack`` (reuses the plan's
        attached transform when it matches)."""
        from repro.core.elastic import elastic_transform

        ep = exec_plan.elastic
        if ep is None or ep.slack != slack:
            ep = elastic_transform(exec_plan, slack)
        return ep

    def bind(self, exec_plan, *, dtype=np.float32, steps_per_tile=8,
             interpret=None, mesh=None, slack=0, shard="model"):
        if mesh is None:
            raise ValueError("backend='distributed' requires a mesh")
        if shard not in ("model", "rows"):
            raise ValueError(
                f"backend='distributed': unknown shard mode {shard!r} "
                "(expected 'model' or 'rows')"
            )
        mesh = _auto_axes(mesh)
        np_dtype = np.dtype(dtype)
        fused = self._fused(exec_plan, slack) if slack > 0 else None
        if shard == "rows":
            return self._bind_rows(exec_plan, np_dtype, mesh, fused, slack)
        return self._bind_model(exec_plan, np_dtype, mesh, fused, slack)

    def _bind_model(self, exec_plan, np_dtype, mesh, fused, slack):
        import jax.numpy as jnp

        from repro.solver.distributed import dist_plan_spec

        exec_plan = _pad_cores(exec_plan, mesh.shape["model"])
        exchange_steps = None
        exchange_info = None
        if fused is not None:
            # execute the certificate: one all-gather per fused run
            sb = np.asarray(exec_plan.step_bounds)
            exchange_steps = tuple(
                int(t) for t in sb[np.asarray(fused.fused_bounds)]
            )
            cert = fused.stats()
            exchange_info = {
                "slack": slack,
                "predicted_rounds": fused.n_fused_supersteps,
                "predicted_fusion": cert["barrier_fusion"],
            }
        spec = dist_plan_spec(
            exec_plan, batch=0, dtype=np_dtype, exchange_steps=exchange_steps
        )
        args = (
            jnp.asarray(exec_plan.row_ids, jnp.int32),
            jnp.asarray(exec_plan.col_idx, jnp.int32),
            jnp.asarray(exec_plan.vals, np_dtype),
            jnp.asarray(exec_plan.diag, np_dtype),
            jnp.asarray(exec_plan.accum.astype(np_dtype)),
        )
        assert exec_plan.val_src is not None and exec_plan.diag_src is not None
        return DistributedBoundSolve(
            spec,
            mesh,
            args,
            jnp.asarray(exec_plan.val_src, jnp.int32),
            jnp.asarray(exec_plan.diag_src, jnp.int32),
            np_dtype,
            expected_entry_count(exec_plan),
            exchange_info=exchange_info,
        )

    def _bind_rows(self, exec_plan, np_dtype, mesh, fused, slack):
        import jax.numpy as jnp

        from repro.core.rowshard import partition_plan
        from repro.solver.executor import w_major
        from repro.solver.rowsharded import (
            rowshard_halo_args,
            rowshard_plan_args,
        )

        assert exec_plan.val_src is not None and exec_plan.diag_src is not None
        n_shards = mesh.shape["model"]
        exchange_info = None
        bounds = None
        if fused is not None:
            bounds = fused.fused_bounds
            cert = fused.stats()
            exchange_info = {
                "slack": slack,
                "predicted_rounds": fused.n_fused_supersteps,
                "predicted_fusion": cert["barrier_fusion"],
            }
        rsp = partition_plan(exec_plan, n_shards, exchange_bounds=bounds)
        plan_args = rowshard_plan_args(rsp, dtype=jnp.dtype(np_dtype.name))
        mode = "ring"  # bitwise-safe default; psum is bench/opt-in
        halo_args = rowshard_halo_args(rsp, mode)
        # GLOBAL entry ids per shard, in the executor's step layout: one
        # gather refreshes all shards
        val_src = jnp.asarray(
            w_major(np.stack([s.val_src for s in rsp.shards])), jnp.int32
        )
        diag_src = jnp.asarray(
            np.stack([s.diag_src for s in rsp.shards]), jnp.int32
        )
        return RowShardedBoundSolve(
            rsp,
            mesh,
            mode,
            plan_args,
            halo_args,
            val_src,
            diag_src,
            np_dtype,
            expected_entry_count(exec_plan),
            exchange_info=exchange_info,
        )
