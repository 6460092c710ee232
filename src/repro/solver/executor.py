"""Single-chip JAX executor: a `lax.scan` over the ExecPlan.

This module is the device half of the ``scan`` entry in
``repro.backends`` — bind through the registry
(``get_backend("scan").bind(plan)``) unless you need the raw pieces.

Each scan step processes one lock-step row per core (k rows in parallel on
the VPU): gather x at the row's column indices, fused multiply-accumulate,
divide by the diagonal, scatter into x. Same-core sequential chains flow
through the scan carry; superstep barriers are free on one chip (DESIGN.md
§3), so the scan ignores `step_bounds` — they matter for the distributed
executor and the Pallas kernel grid.

Padding protocol (see core.plan): row id n = scratch row, gather index n =
scratch slot, so padded lanes are harmless. `accum` rows carry partial sums
for rows wider than W.

The elastic section at the bottom (``ElasticArrays`` /
``solve_with_elastic``) is the ``mode="elastic"`` variant: the same step
bodies, but scanned over ``ceil(T/slack)`` fused macro-steps with the
slack window unrolled inside each one (certificate in ``core.elastic``;
bound via ``get_backend("scan").bind(plan, slack=s)``). Results are
bitwise-identical to the bulk scan — the unrolled bodies replay the
exact same op sequence.
"""
from __future__ import annotations

import time
from functools import partial
from typing import List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.plan import ExecPlan


class PlanArrays(NamedTuple):
    """Device-resident plan tensors (see ExecPlan for shapes)."""

    row_ids: jax.Array  # int32[T, k]
    col_idx: jax.Array  # int32[T, k, W]
    vals: jax.Array  # f[T, k, W]
    diag: jax.Array  # f[T, k]
    accum: jax.Array  # bool[T, k]
    n: int
    step_bounds: np.ndarray  # host-side; used by distributed executor


def plan_arrays(plan: ExecPlan, dtype=jnp.float32) -> PlanArrays:
    return PlanArrays(
        row_ids=jnp.asarray(plan.row_ids, dtype=jnp.int32),
        col_idx=jnp.asarray(plan.col_idx, dtype=jnp.int32),
        vals=jnp.asarray(plan.vals, dtype=dtype),
        diag=jnp.asarray(plan.diag, dtype=dtype),
        accum=jnp.asarray(plan.accum),
        n=plan.n,
        step_bounds=np.asarray(plan.step_bounds),
    )


def _step_single(x, acc, rows, cols, v, d, a, b_pad):
    """One plan step: gather, fused multiply-accumulate, divide, scatter.

    Shared verbatim by the bulk-synchronous scan, the elastic macro-step
    executor AND the row-sharded distributed executor
    (``solver.rowsharded``) so every path emits the exact same op
    sequence per step — the foundation of the bitwise elastic == bulk
    and sharded == single-chip guarantees (tests/test_elastic.py,
    tests/test_rowshard_distributed.py).

    The W-reduction is an explicit fixed-order loop of ELEMENTWISE
    multiply/adds rather than an einsum dot: elementwise IEEE ops are
    exact per element, so a lane's bits are independent of the step's
    tensor SHAPES. An einsum's reduction order is XLA's choice and was
    observed to differ between k and k_local < k operands (1-ulp FMA
    drift), which would break bitwise parity between a shard's local
    scan and the full-width scan.
    """
    # named_scope tags the emitted HLO (zero runtime cost), so a
    # jax.profiler device trace carries plan-step names
    with jax.named_scope("sptrsv_step"):
        for w in range(v.shape[1]):
            acc = acc + v[:, w] * x[cols[:, w]]
        xv = (b_pad[rows] - acc) / d
        # finishing lanes write x and reset their accumulator
        write = jnp.where(a, x[rows], xv)
        # NOTE: padded lanes share the scratch row id n -> indices are not
        # unique; plain scatter keeps them well-defined (they all write
        # junk to the scratch slot).
        x = x.at[rows].set(write)
        acc = jnp.where(a, acc, 0.0)
    return x, acc


def _scan_single(row_ids, col_idx, vals, diag, accum, b_pad, n):
    obs.counter_add("jit.trace.scan")  # at trace time only
    x0 = jnp.zeros(n + 1, dtype=b_pad.dtype)
    acc0 = jnp.zeros(row_ids.shape[1], dtype=b_pad.dtype)

    def step(carry, inp):
        return _step_single(*carry, *inp, b_pad), None

    (x, _), _ = jax.lax.scan(
        step, (x0, acc0), (row_ids, col_idx, vals, diag, accum)
    )
    return x[:n]


_solve_scan = partial(jax.jit, static_argnames=("n",))(_scan_single)


def _scan_lanes(row_ids, col_idx, vals, diag, accum, lane_idx, b_pad, n):
    """Lane j runs the single-RHS scan on plan ``lane_idx[j]`` of the
    stacked plan tensors (leading plan axis P) with rhs ``b_pad[j]``.

    Each scan step slices step t out of every plan and gathers the
    lanes' rows of that slice, so lanes that share a plan never copy its
    tensors: a [lanes, T, k, W] copy of a 64^3 IC(0) plan at 32 lanes
    asks a TPU v5e compile for 26 GB of HBM once the minor dimensions
    are padded to the (8, 128) tile. Lanes are
    data-independent: the vmapped step runs the same op sequence per
    lane, so a lane's bits never depend on what its neighbors hold
    (property-tested in tests/test_serve_scaleout.py)."""
    obs.counter_add("jit.trace.scan_lanes")  # at trace time only
    k = row_ids.shape[2]
    w = lane_idx.shape[0]
    step = jax.vmap(_step_single)

    def body(carry, t):
        def lanes(a):
            return jax.lax.dynamic_index_in_dim(a, t, 1, False)[lane_idx]

        return step(
            *carry, lanes(row_ids), lanes(col_idx), lanes(vals),
            lanes(diag), lanes(accum), b_pad,
        ), None

    x0 = jnp.zeros((w, n + 1), b_pad.dtype)
    acc0 = jnp.zeros((w, k), b_pad.dtype)
    (x, _), _ = jax.lax.scan(
        body, (x0, acc0), jnp.arange(row_ids.shape[1])
    )
    return x[:, :n]


_solve_scan_lanes = partial(jax.jit, static_argnames=("n",))(_scan_lanes)


def solve_with_plan_group(pas, b_cols: jax.Array) -> jax.Array:
    """Solve lane j of ``b_cols`` f[g, n] (already in plan row order)
    against ``pas[j]`` — one traversal over the whole group. All plans
    must share the same tensor shapes (one width class); returns
    x f[g, n].

    Stacks each distinct plan once per call — fine for
    replay/verification; the serving hot path amortizes the stacking
    through a ``BankTensors`` bank + ``_solve_scan_banked`` instead
    (bitwise-identical output, asserted in
    tests/test_serve_scaleout.py)."""
    dtype = pas[0].vals.dtype
    b = jnp.asarray(b_cols, dtype)
    b_pad = jnp.concatenate([b, jnp.zeros((b.shape[0], 1), dtype)], axis=1)
    first = {}
    lane_idx = np.array(
        [first.setdefault(id(pa), len(first)) for pa in pas], np.int32
    )
    uniq = list({id(pa): pa for pa in pas}.values())
    stacked = [
        jnp.stack([getattr(pa, f) for pa in uniq])
        for f in ("row_ids", "col_idx", "vals", "diag", "accum")
    ]
    return _solve_scan_lanes(
        *stacked, jnp.asarray(lane_idx), b_pad, pas[0].n
    )


class BankTensors(NamedTuple):
    """A width class's plan tensors stacked ONCE on device (lane axis P
    first) plus per-lane row permutations — the serving fast path for
    cross-pattern grouped batches. Dispatches index lanes inside the jit
    (``_solve_scan_banked``), so a microbatch costs one compiled call
    with no per-dispatch stacking; the bank is only restacked when the
    class membership changes (new pattern or plan version)."""

    row_ids: jax.Array  # int32[P, T, k]
    col_idx: jax.Array  # int32[P, T, k, W]
    vals: jax.Array  # f[P, T, k, W]
    diag: jax.Array  # f[P, T, k]
    accum: jax.Array  # bool[P, T, k]
    perm: jax.Array  # int32[P, n]  caller order -> plan row order
    inv: jax.Array  # int32[P, n]  plan row order -> caller order


def stack_plan_bank(pas, perms, invs) -> BankTensors:
    """Stack one width class's plans into a ``BankTensors``. The lane
    axis is padded UP to a power of two (repeating lane 0) so the jitted
    banked solve compiles at most log2 bank-size variants as classes
    grow and shrink with plan-version churn."""
    P = len(pas)
    pad = (1 << max(P - 1, 0).bit_length()) - P if P > 1 else 0
    idx = list(range(P)) + [0] * pad
    return BankTensors(
        *(
            jnp.stack([getattr(pas[i], f) for i in idx])
            for f in ("row_ids", "col_idx", "vals", "diag", "accum")
        ),
        perm=jnp.stack([perms[i] for i in idx]),
        inv=jnp.stack([invs[i] for i in idx]),
    )


@partial(jax.jit, static_argnames=("n",))
def _solve_scan_banked(
    row_ids, col_idx, vals, diag, accum, perm, inv, lane_idx, B, n
):
    """The banked grouped solve: request j reads bank lane
    ``lane_idx[j]`` — plan tensors AND its row permutation — solves, and
    un-permutes, all inside one compiled call. ``B`` is f[n, m] in
    caller row order; returns x f[n, m]. Bitwise-identical to
    ``solve_with_plan_group`` on the same lanes: the permutations move
    bits unchanged, and both run ``_scan_lanes``."""
    obs.counter_add("jit.trace.scan_banked")  # at trace time only
    b = jnp.take_along_axis(
        B.T.astype(vals.dtype), perm[lane_idx], axis=1
    )
    b_pad = jnp.concatenate(
        [b, jnp.zeros((b.shape[0], 1), b.dtype)], axis=1
    )
    x = _scan_lanes(row_ids, col_idx, vals, diag, accum, lane_idx, b_pad, n)
    return jnp.take_along_axis(x, inv[lane_idx], axis=1).T


def solve_with_bank(bank: BankTensors, lane_idx, B) -> jax.Array:
    """Solve column j of ``B`` f[n, m] (caller order) against bank lane
    ``lane_idx[j]``; returns x f[n, m] (caller order)."""
    n = int(bank.perm.shape[1])
    return _solve_scan_banked(
        *bank, jnp.asarray(lane_idx, jnp.int32), jnp.asarray(B), n
    )


# ------------------------------------------------- resident RHS slots
# The continuous-batching serve engine (repro.serve.slots) keeps one
# device-resident rhs bank B f[n, S] per width class: admission INSERTS a
# request's b into a free slot (dynamic_update_slice — no host restack of
# the whole batch), every dispatch-loop pass solves a pow2 lane prefix
# of the bank through the same jitted banked kernel, and completion
# EXTRACTS the finished slot's column. The slot index is a traced scalar,
# so insert/extract compile exactly once per (n, S) shape and the pass
# at most log2(S) times (one per pow2 prefix width).

@jax.jit
def _insert_lane(B, lane, b):
    return jax.lax.dynamic_update_slice(B, b[:, None], (0, lane))


@jax.jit
def _extract_lane(X, lane):
    return jax.lax.dynamic_slice_in_dim(X, lane, 1, axis=1)[:, 0]


def blank_rhs(n: int, slots: int, dtype) -> jax.Array:
    """A zeroed device-resident rhs bank f[n, slots]."""
    return jnp.zeros((n, slots), dtype)


def insert_lane(B_res: jax.Array, lane: int, b) -> jax.Array:
    """New resident bank with column ``lane`` replaced by ``b`` f[n] —
    bits of every other column are untouched (``dynamic_update_slice``
    moves bits unchanged; slot-neighbor independence is property-tested
    in tests/test_serve_slots.py). Pure: the input bank is not mutated,
    so a dispatch pass holding the old reference keeps solving the
    snapshot it captured."""
    return _insert_lane(
        B_res, jnp.int32(lane), jnp.asarray(b, B_res.dtype)
    )


def extract_lane(X: jax.Array, lane: int) -> jax.Array:
    """Column ``lane`` of ``X`` f[n, S] as f[n] (bits unchanged)."""
    return _extract_lane(X, jnp.int32(lane))


def solve_resident(bank: BankTensors, lane_idx, B_res) -> jax.Array:
    """The continuous-mode solve pass: identical to ``solve_with_bank``
    (same jitted kernel, bitwise-identical bits per (width, column)),
    except ``B_res`` is already device-resident — nothing re-uploads.
    The pass width is ``len(lane_idx)``: the engine allocates lanes
    lowest-first and dispatches the smallest pow2 lane prefix covering
    the occupied slots, so a lightly-loaded bank never pays the full-S
    solve (``lax.slice_in_dim`` moves bits unchanged, so the result is
    still bitwise-identical to solving a freshly-stacked width-w batch
    of the same columns). Free slots inside the prefix carry stale
    columns whose results are simply never extracted (lane independence
    makes them harmless to live neighbors)."""
    w = len(lane_idx)
    if w != B_res.shape[1]:
        B_res = jax.lax.slice_in_dim(B_res, 0, w, axis=1)
    return solve_with_bank(bank, lane_idx, B_res)


def _step_mrhs(x, acc, rows, cols, v, d, a, b_pad):
    """Multi-RHS twin of ``_step_single`` (value lanes widen to m);
    shared by the bulk scan, the elastic macro-step body and the
    row-sharded executor. Same fixed-order elementwise W-reduction as
    ``_step_single`` — a column's bits are independent of both the lane
    count k and the batch width m."""
    with jax.named_scope("sptrsv_step_mrhs"):
        for w in range(v.shape[1]):
            acc = acc + v[:, w, None] * x[cols[:, w]]
        xv = (b_pad[rows] - acc) / d[:, None]
        write = jnp.where(a[:, None], x[rows], xv)
        x = x.at[rows].set(write)
        acc = jnp.where(a[:, None], acc, 0.0)
    return x, acc


@partial(jax.jit, static_argnames=("n",))
def _solve_scan_mrhs(row_ids, col_idx, vals, diag, accum, b_pad, n):
    """Batched SpTRSM: ``b_pad`` f[n+1, m], carry ``x`` f[n+1, m]. One plan
    traversal solves all m right-hand sides (the gather/scatter indices are
    shared; only the value lanes widen)."""
    obs.counter_add("jit.trace.scan_mrhs")  # at trace time only
    m = b_pad.shape[1]
    x0 = jnp.zeros((n + 1, m), dtype=b_pad.dtype)
    acc0 = jnp.zeros((row_ids.shape[1], m), dtype=b_pad.dtype)

    def step(carry, inp):
        return _step_mrhs(*carry, *inp, b_pad), None

    (x, _), _ = jax.lax.scan(
        step, (x0, acc0), (row_ids, col_idx, vals, diag, accum)
    )
    return x[:n]


def solve_with_plan(pa: PlanArrays, b: jax.Array) -> jax.Array:
    """Solve L x = b using the compiled plan. ``b``: f[n] or f[n, m]
    (multi-RHS — solved in one batched traversal)."""
    b = b.astype(pa.vals.dtype)
    pad = jnp.zeros((1, *b.shape[1:]), pa.vals.dtype)
    b_pad = jnp.concatenate([b, pad])
    solver = _solve_scan if b.ndim == 1 else _solve_scan_mrhs
    return solver(pa.row_ids, pa.col_idx, pa.vals, pa.diag, pa.accum, b_pad, pa.n)


# --------------------------------------------------------------- elastic
class ElasticArrays(NamedTuple):
    """Device-resident plan tensors in macro-step layout: the T plan
    steps, padded up to ``M * slack`` with scratch steps, reshaped to a
    leading [M, slack] grid. ``lax.scan`` runs over the M macro-steps;
    the slack axis is unrolled inside the step body (see
    ``_elastic_single``)."""

    row_ids: jax.Array  # int32[M, S, k]
    col_idx: jax.Array  # int32[M, S, k, W]
    vals: jax.Array  # f[M, S, k, W]
    diag: jax.Array  # f[M, S, k]
    accum: jax.Array  # bool[M, S, k]
    n: int
    slack: int
    n_steps: int  # original (pre-padding) plan step count T


def _pad_to_window(a: np.ndarray, pad: int, fill) -> np.ndarray:
    if pad == 0:
        return a
    tail = np.full((pad, *a.shape[1:]), fill, dtype=a.dtype)
    return np.concatenate([a, tail], axis=0)


def elastic_plan_arrays(
    plan: ExecPlan, *, slack: int, dtype=jnp.float32
) -> ElasticArrays:
    """Lay the plan out for the elastic executor. Padding steps are the
    usual scratch protocol (row n, gather n, val 0, diag 1, no accum):
    they cost a few junk scratch writes inside the last macro-step and
    cannot perturb x[:n]. The accumulator provably enters the padding
    region as zero — a plan's last real step never carries ``accum``
    (every virtual-row chain ends with its finishing row)."""
    T = plan.n_steps
    M = max(1, -(-T // slack))
    pad = M * slack - T
    n, k, W = plan.n, plan.k, plan.W
    return ElasticArrays(
        row_ids=jnp.asarray(
            _pad_to_window(plan.row_ids, pad, n).reshape(M, slack, k),
            dtype=jnp.int32,
        ),
        col_idx=jnp.asarray(
            _pad_to_window(plan.col_idx, pad, n).reshape(M, slack, k, W),
            dtype=jnp.int32,
        ),
        vals=jnp.asarray(
            _pad_to_window(plan.vals, pad, 0).reshape(M, slack, k, W),
            dtype=dtype,
        ),
        diag=jnp.asarray(
            _pad_to_window(plan.diag, pad, 1).reshape(M, slack, k),
            dtype=dtype,
        ),
        accum=jnp.asarray(
            _pad_to_window(plan.accum, pad, False).reshape(M, slack, k)
        ),
        n=n,
        slack=int(slack),
        n_steps=T,
    )


def _elastic_single(row_ids, col_idx, vals, diag, accum, b_pad, n):
    """Elastic scan: ``ceil(T / slack)`` fused macro-steps. Each scan
    step replays its window's ``slack`` plan steps in order through the
    statically-unrolled ``_step_single`` body — intra-window
    dependencies resolve by local substitution on the live x carry, so
    every row still accumulates in exactly the plan order and the result
    is bitwise-identical to ``_scan_single``; only the scan trip count
    (and with it per-step dispatch overhead) shrinks."""
    obs.counter_add("jit.trace.elastic")  # at trace time only
    S = row_ids.shape[1]
    x0 = jnp.zeros(n + 1, dtype=b_pad.dtype)
    acc0 = jnp.zeros(row_ids.shape[2], dtype=b_pad.dtype)

    def macro(carry, inp):
        x, acc = carry
        rows, cols, v, d, a = inp
        for j in range(S):
            x, acc = _step_single(x, acc, rows[j], cols[j], v[j], d[j], a[j], b_pad)
        return (x, acc), None

    (x, _), _ = jax.lax.scan(
        macro, (x0, acc0), (row_ids, col_idx, vals, diag, accum)
    )
    return x[:n]


_solve_elastic = partial(jax.jit, static_argnames=("n",))(_elastic_single)


@partial(jax.jit, static_argnames=("n",))
def _solve_elastic_mrhs(row_ids, col_idx, vals, diag, accum, b_pad, n):
    """Multi-RHS elastic scan (macro-step twin of ``_solve_scan_mrhs``)."""
    obs.counter_add("jit.trace.elastic_mrhs")  # at trace time only
    S = row_ids.shape[1]
    m = b_pad.shape[1]
    x0 = jnp.zeros((n + 1, m), dtype=b_pad.dtype)
    acc0 = jnp.zeros((row_ids.shape[2], m), dtype=b_pad.dtype)

    def macro(carry, inp):
        x, acc = carry
        rows, cols, v, d, a = inp
        for j in range(S):
            x, acc = _step_mrhs(x, acc, rows[j], cols[j], v[j], d[j], a[j], b_pad)
        return (x, acc), None

    (x, _), _ = jax.lax.scan(
        macro, (x0, acc0), (row_ids, col_idx, vals, diag, accum)
    )
    return x[:n]


def solve_with_elastic(ea: ElasticArrays, b: jax.Array) -> jax.Array:
    """Solve L x = b through the elastic macro-step scan. ``b``: f[n] or
    f[n, m]; bitwise-identical to ``solve_with_plan`` on the same plan."""
    b = b.astype(ea.vals.dtype)
    pad = jnp.zeros((1, *b.shape[1:]), ea.vals.dtype)
    b_pad = jnp.concatenate([b, pad])
    solver = _solve_elastic if b.ndim == 1 else _solve_elastic_mrhs
    return solver(ea.row_ids, ea.col_idx, ea.vals, ea.diag, ea.accum, b_pad, ea.n)


# ---------------------------------------------------------- timed solves
# Opt-in per-step device timing (``TriangularSolver.plan(..., timed=True)``
# / ``BoundSolve.solve_timed``): the plan traversal is broken at its
# natural boundaries — superstep bounds for the bulk scan, macro-step
# windows for elastic — and each segment runs as its own jitted call,
# host-timed around ``block_until_ready``. Results stay numerically
# identical to the fused scans (the segment carry replays the same step
# bodies in the same order); only dispatch granularity changes, which is
# exactly what makes the per-segment wall-clock observable. Compiled
# variants are bounded: one per distinct superstep length (bulk) and ONE
# total for elastic (every window is [slack, ...]-shaped).

@jax.jit
def _solve_segment(rows, cols, v, d, a, b_pad, x, acc):
    """Run one contiguous run of plan steps on an existing (x, acc)
    carry. Serves both timed paths: a bulk superstep slice (rows
    int32[t, k]) and one elastic macro window (rows int32[slack, k]).
    Single- vs multi-RHS is resolved statically from the carry rank."""
    obs.counter_add("jit.trace.segment")  # at trace time only
    body = _step_single if x.ndim == 1 else _step_mrhs

    def step(carry, inp):
        return body(*carry, *inp, b_pad), None

    (x, acc), _ = jax.lax.scan(step, (x, acc), (rows, cols, v, d, a))
    return x, acc


def _timed_carry(b, vals_dtype, n, k):
    """Shared setup for the timed paths: padded rhs + zero carry."""
    b = jnp.asarray(b).astype(vals_dtype)
    pad = jnp.zeros((1, *b.shape[1:]), vals_dtype)
    b_pad = jnp.concatenate([b, pad])
    if b.ndim == 1:
        x = jnp.zeros(n + 1, b_pad.dtype)
        acc = jnp.zeros(k, b_pad.dtype)
    else:
        m = b.shape[1]
        x = jnp.zeros((n + 1, m), b_pad.dtype)
        acc = jnp.zeros((k, m), b_pad.dtype)
    return b_pad, x, acc


def solve_with_plan_timed(
    pa: PlanArrays, b: jax.Array
) -> Tuple[jax.Array, List[dict]]:
    """``solve_with_plan`` with per-superstep device timing: one jitted
    segment per superstep, synchronized and host-timed. Returns
    ``(x, steps)`` where each entry is
    ``{"superstep", "n_steps", "us"}``; an ``executor.superstep`` span
    lands in the active trace buffer per segment when tracing is on."""
    k = int(pa.row_ids.shape[1])
    b_pad, x, acc = _timed_carry(b, pa.vals.dtype, pa.n, k)
    bounds = pa.step_bounds
    steps: List[dict] = []
    for s in range(len(bounds) - 1):
        lo, hi = int(bounds[s]), int(bounds[s + 1])
        if hi == lo:
            continue
        with obs.span(
            "executor.superstep", cat="executor", superstep=s, steps=hi - lo
        ):
            t0 = time.perf_counter_ns()
            x, acc = _solve_segment(
                pa.row_ids[lo:hi],
                pa.col_idx[lo:hi],
                pa.vals[lo:hi],
                pa.diag[lo:hi],
                pa.accum[lo:hi],
                b_pad,
                x,
                acc,
            )
            x.block_until_ready()
            dur = time.perf_counter_ns() - t0
        steps.append(
            {"superstep": s, "n_steps": hi - lo, "us": round(dur / 1e3, 2)}
        )
    return x[:pa.n], steps


def solve_with_elastic_timed(
    ea: ElasticArrays, b: jax.Array
) -> Tuple[jax.Array, List[dict]]:
    """``solve_with_elastic`` with per-macro-step device timing. Every
    window shares the [slack, ...] shape, so the whole loop compiles ONE
    ``_solve_segment`` variant. Returns ``(x, steps)`` with one
    ``{"macro_step", "n_steps", "us"}`` entry (and one
    ``executor.macro_step`` span when tracing) per executed macro-step —
    the runtime side of the elastic barrier-fusion certificate."""
    k = int(ea.row_ids.shape[2])
    b_pad, x, acc = _timed_carry(b, ea.vals.dtype, ea.n, k)
    M = int(ea.row_ids.shape[0])
    steps: List[dict] = []
    for m in range(M):
        with obs.span(
            "executor.macro_step", cat="executor", macro=m, slack=ea.slack
        ):
            t0 = time.perf_counter_ns()
            x, acc = _solve_segment(
                ea.row_ids[m],
                ea.col_idx[m],
                ea.vals[m],
                ea.diag[m],
                ea.accum[m],
                b_pad,
                x,
                acc,
            )
            x.block_until_ready()
            dur = time.perf_counter_ns() - t0
        steps.append(
            {"macro_step": m, "n_steps": ea.slack, "us": round(dur / 1e3, 2)}
        )
    return x[:ea.n], steps


def make_solver(plan: ExecPlan, dtype=jnp.float32):
    """Bind a plan; returns ``solve(b) -> x`` (jit-compiled on first call).
    ``b`` may be f[n] or f[n, m] for a batched multi-RHS solve."""
    pa = plan_arrays(plan, dtype=dtype)

    def solve(b):
        return solve_with_plan(pa, jnp.asarray(b, dtype=dtype))

    return solve
