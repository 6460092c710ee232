"""Distributed SpTRSV executor — the BSP model on a device mesh.

Here the paper's abstract machine becomes literal hardware: the k schedule
cores are k devices along the ``model`` mesh axis; a superstep is a local
sequential scan over each device's chain; the synchronization barrier is an
``all_gather`` of the x-fragments produced in the superstep (the paper's
L = barrier cost becomes the ICI all-gather latency — see DESIGN.md §3).

The jitted graph contains exactly ``n_supersteps`` all-gathers: GrowLocal's
barrier reduction is visible directly in the lowered HLO. Multi-RHS
(SpTRSM) batches shard over the ``data`` axis, giving the full production
mesh a workload.

``distributed_input_specs`` / ``lower_distributed_solve`` lower the solve
without running it; the mesh compile tests use them.

This module is the device half of the ``distributed`` entry in
``repro.backends`` — bind through the registry
(``get_backend("distributed").bind(plan, mesh=mesh)``) unless you need
the raw pieces.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.plan import ExecPlan


@dataclasses.dataclass
class DistPlanSpec:
    """Static description of a distributed solve (shapes only)."""

    n: int
    k: int  # devices on the model axis == schedule cores
    W: int
    T: int
    step_bounds: tuple  # len S+1
    batch: int  # number of RHS (SpTRSM); sharded over 'data'
    dtype: np.dtype = np.dtype(np.float32)
    # plan-step indices of the barriers actually executed (len F+1,
    # subset of step_bounds). None -> one barrier per superstep. Set
    # from the elastic fused-run certificate (core.elastic) to fuse
    # greedy superstep runs into single all-gather rounds: a fused run
    # has no cross-core reads of values written inside it, so deferring
    # the exchange to the run boundary is exactly as correct as the
    # per-superstep barrier (tests/test_rowshard_distributed.py).
    exchange_steps: tuple = None


def dist_plan_spec(
    plan: ExecPlan, batch: int = 1, dtype=np.float32, exchange_steps=None
) -> DistPlanSpec:
    return DistPlanSpec(
        n=plan.n,
        k=plan.k,
        W=plan.W,
        T=plan.n_steps,
        step_bounds=tuple(int(t) for t in plan.step_bounds),
        batch=batch,
        dtype=np.dtype(dtype),
        exchange_steps=(
            None
            if exchange_steps is None
            else tuple(int(t) for t in exchange_steps)
        ),
    )


def _local_solve(spec: DistPlanSpec, rows_full, col_idx, vals, diag,
                 accum_full, b_pad):
    """Per-device body (inside shard_map). Shapes (local):
    rows_full int32[T, k] (REPLICATED — static plan metadata);
    col_idx int32[T, 1, W]; vals f[T, 1, W]; diag f[T, 1];
    accum_full f[T, k] (replicated); b_pad f[B_local, n+1].
    Returns x f[B_local, n+1]."""
    Bl = b_pad.shape[0]
    x = jnp.zeros((Bl, spec.n + 1), dtype=b_pad.dtype)
    core = jax.lax.axis_index("model")
    row_ids = jax.lax.dynamic_slice_in_dim(rows_full, core, 1, axis=1)
    accum = jax.lax.dynamic_slice_in_dim(accum_full, core, 1, axis=1)

    def superstep(x, lo, hi):
        def step(carry, inp):
            x, acc = carry
            rows, cols, v, d, a = inp  # (1,), (1,W), (1,W), (1,), (1,)
            gathered = x[:, cols[0]]  # [Bl, W]
            acc = acc + gathered @ v[0]  # [Bl]
            xv = (b_pad[:, rows[0]] - acc) / d[0]
            keep = a[0] > 0.5
            old = x[:, rows[0]]
            write = jnp.where(keep, old, xv)
            x = x.at[:, rows[0]].set(write)
            acc = jnp.where(keep, acc, jnp.zeros_like(acc))
            return (x, acc), xv

        acc0 = jnp.zeros((Bl,), dtype=b_pad.dtype)
        (x, _), xv_steps = jax.lax.scan(
            step,
            (x, acc0),
            (
                row_ids[lo:hi],
                col_idx[lo:hi],
                vals[lo:hi],
                diag[lo:hi],
                accum[lo:hi],
            ),
        )
        return x, xv_steps  # xv_steps: [hi-lo, Bl]

    # Perf note (EXPERIMENTS.md §Perf, sptrsv cell): row ids and accum
    # flags are STATIC plan data — every device already holds the full
    # [T, k] arrays (replicated in_specs) — so the barrier exchanges ONLY
    # the solved values: one all-gather per superstep instead of three.
    # With exchange_steps set, runs of supersteps certified by the
    # elastic fusion bound share a single barrier.
    bounds = (
        spec.exchange_steps
        if spec.exchange_steps is not None
        else spec.step_bounds
    )
    for s in range(len(bounds) - 1):
        lo, hi = bounds[s], bounds[s + 1]
        if hi == lo:
            continue
        x, xv_steps = superstep(x, lo, hi)
        # --- BARRIER: exchange the fragment produced in this superstep ----
        xv_all = jax.lax.all_gather(xv_steps, "model")  # [k, hi-lo, Bl]
        flat_vals = xv_all.reshape(-1, Bl).T  # [Bl, k*(hi-lo)]
        # static metadata: all cores' rows/accum flags, transposed to the
        # same (core, step) order as the gathered values
        rows_all = rows_full[lo:hi].T.reshape(-1)  # [k*(hi-lo)]
        acc_all = accum_full[lo:hi].T.reshape(-1)
        safe_rows = jnp.where(acc_all > 0.5, spec.n, rows_all)
        x = x.at[:, safe_rows].set(
            jnp.where(acc_all > 0.5, x[:, safe_rows], flat_vals)
        )
    return x


def build_distributed_solver(spec: DistPlanSpec, mesh: Mesh):
    """Returns a jittable ``solve(plan_tensors..., b_pad) -> x`` shard-mapped
    over (data: RHS batch, model: schedule cores)."""
    plan_spec_in = (
        P(None, None),  # rows_full [T, k] — replicated plan metadata
        P(None, "model", None),  # col_idx
        P(None, "model", None),  # vals
        P(None, "model"),  # diag
        P(None, None),  # accum_full [T, k] — replicated
        P("data", None),  # b_pad [B, n+1]
    )

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=plan_spec_in,
        out_specs=P("data", None),
        check_rep=False,
    )
    def solve(row_ids, col_idx, vals, diag, accum, b_pad):
        return _local_solve(spec, row_ids, col_idx, vals, diag, accum, b_pad)

    return solve


def distributed_input_specs(spec: DistPlanSpec, mesh: Mesh):
    """ShapeDtypeStructs (+ shardings) for lowering without allocation."""
    f = spec.dtype
    shapes = [
        ((spec.T, spec.k), np.int32, P(None, None)),
        ((spec.T, spec.k, spec.W), np.int32, P(None, "model", None)),
        ((spec.T, spec.k, spec.W), f, P(None, "model", None)),
        ((spec.T, spec.k), f, P(None, "model")),
        ((spec.T, spec.k), f, P(None, None)),
        ((spec.batch, spec.n + 1), f, P("data", None)),
    ]
    return [
        jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(mesh, p))
        for (s, d, p) in shapes
    ]


def lower_distributed_solve(spec: DistPlanSpec, mesh: Mesh):
    """.lower() the distributed solve on the given mesh (compile only)."""
    solve = build_distributed_solver(spec, mesh)
    args = distributed_input_specs(spec, mesh)
    return jax.jit(solve).lower(*args)


def run_distributed_solve(plan: ExecPlan, b: np.ndarray, mesh: Mesh, dtype=jnp.float32):
    """Execute on a real (or host-count-forced) mesh; b: [B, n]."""
    spec = dist_plan_spec(plan, batch=b.shape[0], dtype=np.dtype(dtype))
    solve = build_distributed_solver(spec, mesh)
    b_pad = np.concatenate(
        [np.asarray(b, dtype=dtype), np.zeros((b.shape[0], 1), dtype=dtype)], axis=1
    )
    args = (
        jnp.asarray(plan.row_ids, jnp.int32),
        jnp.asarray(plan.col_idx, jnp.int32),
        jnp.asarray(plan.vals, dtype),
        jnp.asarray(plan.diag, dtype),
        jnp.asarray(plan.accum.astype(np.dtype(dtype))),
        jnp.asarray(b_pad),
    )
    with mesh:
        x = jax.jit(solve)(*args)
    return np.asarray(x)[:, : plan.n]
