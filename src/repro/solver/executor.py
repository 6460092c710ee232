"""Single-chip JAX executor: a `lax.scan` over the ExecPlan.

This module is the device half of the ``scan`` entry in
``repro.backends`` — bind through the registry
(``get_backend("scan").bind(plan)``) unless you need the raw pieces.

Each scan step processes one lock-step row per core (k rows in parallel on
the VPU) with two device ops that touch x: ONE gather of every x value
the step's k·W slots read, then a fixed-order multiply-accumulate and the
divide by the diagonal, then ONE contiguous write of the k results
(``lax.dynamic_update_slice``). Same-core sequential chains flow through
the scan carry; superstep barriers are free on one chip (DESIGN.md §3),
so the scan ignores `step_bounds` — they matter for the distributed
executor and the Pallas kernel grid.

Slot layout. x lives in step-slot order: slot t·k + j holds what lane j
writes at plan step t, so plan step t writes slots [t·k, t·k + k) and
nothing else. A plan row's slot is that of its finishing (non-``accum``)
lane, which every real row has exactly once; ``out_index`` int32[n]
lists it per plan row. Solves hand back x in slot order and the caller
folds ``out_index`` into its own permutation gather
(``TriangularSolver``); ``solve_with_plan`` / ``solve_with_elastic``
gather it back to plan order for anyone else.

Step layout. The executor lays the plan out for itself at bind time
(``ExecPlan`` keeps its [T, k, W] tensors): ``cols`` int32[T, W·k] holds
the gather slots w-major and flat, so one ``x[cols]`` serves every slot
of the step; ``vals`` f[T, W, k] the matching values; and ``write_rows``
int32[T, k] the plan row of each lane, read only by the gather of b in
slot order, ``b[write_rows]``, once per solve before the loop; b rides
the scan as one more per-step tensor.

Padding protocol: x carries one slot past the last step's, the zero
slot T·k, which no step writes (writes only touch [0, T·k)). Padded
gathers read it with value 0. Padded lanes and ``accum`` lanes (partial
sums of rows wider than W, carried in the accumulator) write their own
slots, which no gather reads, so a step never reads x back to leave a
row as it was. b gets one zero row, n, which padded and accum lanes
read.

The elastic section at the bottom (``ElasticArrays`` /
``solve_with_elastic``) is the ``mode="elastic"`` variant: the same step
bodies, but scanned over ``ceil(T/slack)`` fused macro-steps with the
slack window unrolled inside each one (certificate in ``core.elastic``;
bound via ``get_backend("scan").bind(plan, slack=s)``). Its padding
steps extend the slot space, so plan row slots, and ``out_index``, are
those of the bulk scan. Results are bitwise-identical to the bulk scan —
the unrolled bodies replay the exact same op sequence.
"""
from __future__ import annotations

import time
from typing import List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.plan import ExecPlan


class PlanArrays(NamedTuple):
    """Device-resident plan tensors in the step layout (module
    docstring)."""

    write_rows: jax.Array  # int32[T, k]  b's row per lane; padded, accum: n
    cols: jax.Array  # int32[T, W*k]  w-major gather slots; padding: T*k
    vals: jax.Array  # f[T, W, k]
    diag: jax.Array  # f[T, k]
    accum: jax.Array  # bool[T, k]
    n: int
    step_bounds: np.ndarray  # host-side; used by distributed executor
    out_index: jax.Array  # int32[n]  slot of each plan row


def w_major(a: np.ndarray) -> np.ndarray:
    """[..., k, W] -> [..., W, k]: a plan's per-slot tensor (``vals``,
    ``val_src``) in the step layout."""
    return np.swapaxes(a, -1, -2)


def _pad_to_window(a: np.ndarray, pad: int, fill) -> np.ndarray:
    if pad == 0:
        return a
    tail = np.full((pad, *a.shape[1:]), fill, dtype=a.dtype)
    return np.concatenate([a, tail], axis=0)


def slot_index(plan: ExecPlan) -> np.ndarray:
    """int32[n]: the slot t·k + j of each plan row's finishing lane.
    Raises if a real row has no finishing lane or more than one."""
    finish = np.flatnonzero((plan.row_ids < plan.n) & ~plan.accum)
    rows = plan.row_ids.ravel()[finish]
    out = np.full(plan.n, -1, np.int64)
    out[rows] = finish
    if len(rows) != plan.n or (out < 0).any():
        raise ValueError(
            "every plan row needs exactly one finishing (non-accum) lane"
        )
    return out.astype(np.int32)


def laid_out(plan: ExecPlan, pad: int = 0):
    """The plan's step tensors in the step layout, host-side, with
    ``pad`` padding steps appended (b row n, gather the zero slot, val
    0, diag 1, no accum): ``((write_rows, cols, vals, diag, accum),
    out_index)``."""
    n, k, W = plan.n, plan.k, plan.W
    T = plan.n_steps + pad
    out = slot_index(plan)
    accum = _pad_to_window(plan.accum, pad, False)
    # plan row n (the plan's padding gather) reads the zero slot T·k
    slot_of = np.append(out, np.int32(T * k))
    cols = w_major(slot_of[_pad_to_window(plan.col_idx, pad, n)])
    host = (
        np.where(accum, n, _pad_to_window(plan.row_ids, pad, n)),
        cols.reshape(T, W * k),
        w_major(_pad_to_window(plan.vals, pad, 0)),
        _pad_to_window(plan.diag, pad, 1),
        accum,
    )
    return host, out


def to_device(host, dtype):
    """Step tensors (any leading shape), as ``laid_out`` gives them, as
    device arrays."""
    rows, cols, vals, diag, accum = host
    return (
        jnp.asarray(rows, jnp.int32),
        jnp.asarray(cols, jnp.int32),
        jnp.asarray(vals, dtype),
        jnp.asarray(diag, dtype),
        jnp.asarray(accum),
    )


def plan_arrays(plan: ExecPlan, dtype=jnp.float32) -> PlanArrays:
    host, out = laid_out(plan)
    return PlanArrays(
        *to_device(host, dtype),
        n=plan.n,
        step_bounds=np.asarray(plan.step_bounds),
        out_index=jnp.asarray(out),
    )


def pad_rhs(b):
    """``b`` f[n(, m)] with one zero row appended: row n, which padded
    and accum lanes read, reads 0."""
    return jnp.pad(b, [(0, 1)] + [(0, 0)] * (b.ndim - 1))


def _values_single(x, acc, cols, v, d, a, bt):
    """One plan step's k results: one gather, fused multiply-accumulate,
    divide. ``bt``/``d``/``a`` are [k], ``cols`` [W·k], ``v`` [W, k]
    (step layout, module docstring). Returns ``(results [k], acc)``,
    the accumulator cleared on every lane but the ``accum`` ones.

    Shared verbatim by the bulk-synchronous scan, the elastic macro-step
    executor AND the row-sharded distributed executor
    (``solver.rowsharded``), which differ only in where they write the
    results, so every path computes each result with the exact same op
    sequence — the foundation of the bitwise elastic == bulk and
    sharded == single-chip guarantees (tests/test_elastic.py,
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
        k = acc.shape[0]
        xg = x[cols]
        for w in range(v.shape[0]):
            acc = acc + v[w] * xg[w * k:(w + 1) * k]
        val = (bt - acc) / d
        acc = jnp.where(a, acc, 0.0)
    return val, acc


def _values_mrhs(x, acc, cols, v, d, a, bt):
    """Multi-RHS twin of ``_values_single`` (value lanes widen to m);
    shared by the same executors. Same fixed-order elementwise
    W-reduction — a column's bits are independent of both the lane
    count k and the batch width m."""
    with jax.named_scope("sptrsv_step_mrhs"):
        k = acc.shape[0]
        xg = x[cols]
        for w in range(v.shape[0]):
            acc = acc + v[w, :, None] * xg[w * k:(w + 1) * k]
        val = (bt - acc) / d[:, None]
        acc = jnp.where(a[:, None], acc, 0.0)
    return val, acc


def walk(values, carry, steps, b_pad, t0=0, window: int = 0):
    """Run ``values`` (``_values_single`` or ``_values_mrhs``) over
    ``steps`` = (write_rows, cols, vals, diag, accum), leading axis
    first, from ``carry`` = (x, acc) in slot order: the i-th step walked
    is plan step ``t0`` + i and writes its k results to the slots of
    that step with one ``dynamic_update_slice``. b is gathered for every
    step at once, before the loop. With ``window`` the leading axis is
    the elastic macro-step: each scan step replays its ``window`` plan
    steps in order, unrolled."""
    k = steps[0].shape[-1]

    def body(carry, inp):
        x, acc, at = carry  # at: the first slot of the scan step's writes
        for j in range(window or 1):
            step = tuple(a[j] for a in inp) if window else inp
            val, acc = values(x, acc, *step)
            x = _write(x, val, at + j * k, 0)
        return (x, acc, at + (window or 1) * k), None

    (x, acc, _), _ = jax.lax.scan(
        body,
        (*carry, jnp.asarray(t0, jnp.int32) * k),
        (*steps[1:], b_pad[steps[0]]),
    )
    return x, acc


def _write(x, val, at, axis):
    """One step's k results into x at slot ``at`` along ``axis``: one
    contiguous update, in place on the scan carry."""
    with jax.named_scope("sptrsv_write"):
        return jax.lax.dynamic_update_slice_in_dim(
            x, val, at, axis, allow_negative_indices=False
        )


def _slot_carry(write_rows, b):
    """The zero carry of a solve over ``write_rows``' steps: x f[slots +
    1(, m)] (the zero slot last) and the accumulator f[k(, m)]."""
    tail = b.shape[1:]
    return (
        jnp.zeros((write_rows.size + 1, *tail), b.dtype),
        jnp.zeros((write_rows.shape[-1], *tail), b.dtype),
    )


def _solve_steps(steps, b, window: int = 0):
    """x f[slots + 1(, m)] in slot order with L x = b, b f[n(, m)], over
    laid-out plan steps."""
    values = _values_single if b.ndim == 1 else _values_mrhs
    carry = _slot_carry(steps[0], b)
    x, _ = walk(values, carry, steps, pad_rhs(b), window=window)
    return x


@jax.jit
def _solve_scan(write_rows, cols, vals, diag, accum, b):
    obs.counter_add("jit.trace.scan")  # at trace time only
    return _solve_steps((write_rows, cols, vals, diag, accum), b)


@jax.jit
def _solve_scan_mrhs(write_rows, cols, vals, diag, accum, b):
    """Batched SpTRSM: ``b`` f[n, m], carry ``x`` f[T·k + 1, m]. One plan
    traversal solves all m right-hand sides (the gather indices and
    write offsets are shared; only the value lanes widen)."""
    obs.counter_add("jit.trace.scan_mrhs")  # at trace time only
    return _solve_steps((write_rows, cols, vals, diag, accum), b)


def _scan_lanes(write_rows, cols, vals, diag, accum, lane_idx, b):
    """Lane j runs the single-RHS scan on plan ``lane_idx[j]`` of the
    stacked plan tensors (leading plan axis P) with rhs ``b[j]`` f[n];
    returns x f[lanes, T·k + 1] in slot order. Plans of one width class
    share T and k, so every lane writes step t at the same offset t·k:
    one ``dynamic_update_slice`` on [lanes, slots] a step.

    Each scan step slices step t out of every plan and gathers the
    lanes' rows of that slice, so lanes that share a plan never copy its
    tensors: a [lanes, T, k, W] copy of a 64^3 IC(0) plan at 32 lanes
    asks a TPU v5e compile for 26 GB of HBM once the minor dimensions
    are padded to the (8, 128) tile. For the same reason b is gathered
    inside the step here, not for all steps up front: that would be a
    [lanes, T, k] tensor, k padded to the tile's 128. Lanes are
    data-independent: the vmapped step runs the same op sequence per
    lane, so a lane's bits never depend on what its neighbors hold
    (property-tested in tests/test_serve_scaleout.py)."""
    obs.counter_add("jit.trace.scan_lanes")  # at trace time only
    b_pad = jax.vmap(pad_rhs)(b)
    values = jax.vmap(_values_single)
    k = write_rows.shape[2]

    def body(carry, t):
        def lanes(a):
            return jax.lax.dynamic_index_in_dim(a, t, 1, False)[lane_idx]

        x, acc = carry
        rows = lanes(write_rows)
        bt = jnp.take_along_axis(b_pad, rows, axis=1)
        val, acc = values(
            x, acc, lanes(cols), lanes(vals), lanes(diag), lanes(accum), bt,
        )
        return (_write(x, val, t * k, 1), acc), None

    T = write_rows.shape[1]
    carry = (
        jnp.zeros((b.shape[0], T * k + 1), b.dtype),
        jnp.zeros((b.shape[0], k), b.dtype),
    )
    (x, _), _ = jax.lax.scan(body, carry, jnp.arange(T))
    return x


_solve_scan_lanes = jax.jit(_scan_lanes)

# the plan tensors a grouped or banked solve stacks, in PlanArrays order
_STEP_FIELDS = ("write_rows", "cols", "vals", "diag", "accum")


def solve_with_plan_group(pas, b_cols: jax.Array) -> jax.Array:
    """Solve lane j of ``b_cols`` f[g, n] (already in plan row order)
    against ``pas[j]`` — one traversal over the whole group. All plans
    must share the same tensor shapes (one width class); returns
    x f[g, T·k + 1] in slot order (lane j's plan rows at
    ``pas[j].out_index``).

    Stacks each distinct plan once per call — fine for
    replay/verification; the serving hot path amortizes the stacking
    through a ``BankTensors`` bank + ``_solve_scan_banked`` instead
    (bitwise-identical output, asserted in
    tests/test_serve_scaleout.py)."""
    b = jnp.asarray(b_cols, pas[0].vals.dtype)
    first = {}
    lane_idx = np.array(
        [first.setdefault(id(pa), len(first)) for pa in pas], np.int32
    )
    uniq = list({id(pa): pa for pa in pas}.values())
    stacked = [
        jnp.stack([getattr(pa, f) for pa in uniq]) for f in _STEP_FIELDS
    ]
    return _solve_scan_lanes(*stacked, jnp.asarray(lane_idx), b)


class BankTensors(NamedTuple):
    """A width class's plan tensors stacked ONCE on device (lane axis P
    first) plus per-lane row permutations — the serving fast path for
    cross-pattern grouped batches. Dispatches index lanes inside the jit
    (``_solve_scan_banked``), so a microbatch costs one compiled call
    with no per-dispatch stacking; the bank is only restacked when the
    class membership changes (new pattern or plan version)."""

    write_rows: jax.Array  # int32[P, T, k]
    cols: jax.Array  # int32[P, T, W*k]
    vals: jax.Array  # f[P, T, W, k]
    diag: jax.Array  # f[P, T, k]
    accum: jax.Array  # bool[P, T, k]
    perm: jax.Array  # int32[P, n]  caller order -> plan row order
    inv: jax.Array  # int32[P, n]  caller row -> its slot (out_index folded)


def stack_plan_bank(pas, perms, invs) -> BankTensors:
    """Stack one width class's plans into a ``BankTensors``; ``invs[i]``
    gives each caller row's slot in plan i's scan (``out_index``
    composed with the inverse row permutation). The lane axis is padded
    UP to a power of two (repeating lane 0) so the jitted banked solve
    compiles at most log2 bank-size variants as classes grow and shrink
    with plan-version churn."""
    P = len(pas)
    pad = (1 << max(P - 1, 0).bit_length()) - P if P > 1 else 0
    idx = list(range(P)) + [0] * pad
    return BankTensors(
        *(jnp.stack([getattr(pas[i], f) for i in idx]) for f in _STEP_FIELDS),
        perm=jnp.stack([perms[i] for i in idx]),
        inv=jnp.stack([invs[i] for i in idx]),
    )


@jax.jit
def _solve_scan_banked(
    write_rows, cols, vals, diag, accum, perm, inv, lane_idx, B
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
    x = _scan_lanes(write_rows, cols, vals, diag, accum, lane_idx, b)
    return jnp.take_along_axis(x, inv[lane_idx], axis=1).T


def solve_with_bank(bank: BankTensors, lane_idx, B) -> jax.Array:
    """Solve column j of ``B`` f[n, m] (caller order) against bank lane
    ``lane_idx[j]``; returns x f[n, m] (caller order)."""
    return _solve_scan_banked(
        *bank, jnp.asarray(lane_idx, jnp.int32), jnp.asarray(B)
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


def solve_slots(arrays, b: jax.Array) -> jax.Array:
    """x f[slots + 1(, m)] in slot order with L x = b, through the bulk
    (``PlanArrays``) or elastic (``ElasticArrays``) scan. ``b``: f[n] or
    f[n, m] (multi-RHS — solved in one batched traversal)."""
    b = b.astype(arrays.vals.dtype)
    if isinstance(arrays, ElasticArrays):
        solver = _solve_elastic if b.ndim == 1 else _solve_elastic_mrhs
    else:
        solver = _solve_scan if b.ndim == 1 else _solve_scan_mrhs
    return solver(*arrays[:5], b)


def solve_with_plan(pa: PlanArrays, b: jax.Array) -> jax.Array:
    """Solve L x = b using the compiled plan; x in plan row order.
    ``b``: f[n] or f[n, m]."""
    return solve_slots(pa, b)[pa.out_index]


# --------------------------------------------------------------- elastic
class ElasticArrays(NamedTuple):
    """Device-resident plan tensors in macro-step layout: the T plan
    steps, padded up to ``M * slack`` with padding steps, reshaped to a
    leading [M, slack] grid. ``lax.scan`` runs over the M macro-steps;
    the slack axis is unrolled inside the step body (see
    ``_solve_elastic``)."""

    write_rows: jax.Array  # int32[M, S, k]  as PlanArrays'
    cols: jax.Array  # int32[M, S, W*k]  padding gathers slot M·S·k
    vals: jax.Array  # f[M, S, W, k]
    diag: jax.Array  # f[M, S, k]
    accum: jax.Array  # bool[M, S, k]
    n: int
    slack: int
    n_steps: int  # original (pre-padding) plan step count T
    out_index: jax.Array  # int32[n]  slot of each plan row (as bulk)


def elastic_plan_arrays(
    plan: ExecPlan, *, slack: int, dtype=jnp.float32
) -> ElasticArrays:
    """Lay the plan out for the elastic executor. Padding steps follow
    the padding protocol (b row n, gather the zero slot, val 0, diag 1,
    no accum): they write zeros to their own slots, past every plan
    row's, inside the last macro-step. The accumulator provably enters
    the padding region as zero — a plan's last real step never carries
    ``accum`` (every virtual-row chain ends with its finishing row)."""
    T = plan.n_steps
    M = max(1, -(-T // slack))
    host, out = laid_out(plan, M * slack - T)
    return ElasticArrays(
        *to_device([a.reshape(M, slack, *a.shape[1:]) for a in host], dtype),
        n=plan.n,
        slack=int(slack),
        n_steps=T,
        out_index=jnp.asarray(out),
    )


@jax.jit
def _solve_elastic(write_rows, cols, vals, diag, accum, b):
    """Elastic scan: ``ceil(T / slack)`` fused macro-steps. Each scan
    step replays its window's ``slack`` plan steps in order through the
    statically-unrolled ``_values_single`` body — intra-window
    dependencies resolve by local substitution on the live x carry, so
    every row still accumulates in exactly the plan order and the result
    is bitwise-identical to ``_solve_scan``; only the scan trip count
    (and with it per-step dispatch overhead) shrinks."""
    obs.counter_add("jit.trace.elastic")  # at trace time only
    steps = (write_rows, cols, vals, diag, accum)
    return _solve_steps(steps, b, window=write_rows.shape[1])


@jax.jit
def _solve_elastic_mrhs(write_rows, cols, vals, diag, accum, b):
    """Multi-RHS elastic scan (macro-step twin of ``_solve_scan_mrhs``)."""
    obs.counter_add("jit.trace.elastic_mrhs")  # at trace time only
    steps = (write_rows, cols, vals, diag, accum)
    return _solve_steps(steps, b, window=write_rows.shape[1])


def solve_with_elastic(ea: ElasticArrays, b: jax.Array) -> jax.Array:
    """Solve L x = b through the elastic macro-step scan; x in plan row
    order. ``b``: f[n] or f[n, m]; bitwise-identical to
    ``solve_with_plan`` on the same plan."""
    return solve_slots(ea, b)[ea.out_index]


# ---------------------------------------------------------- timed solves
# Opt-in per-step device timing (``TriangularSolver.plan(..., timed=True)``
# / ``BoundSolve.solve_timed``): the plan traversal is broken at its
# natural boundaries — superstep bounds for the bulk scan, macro-step
# windows for elastic — and each segment runs as its own jitted call,
# host-timed around ``block_until_ready``. Results stay bitwise
# identical to the fused scans, in the same slot order (the segment
# carry replays the same step bodies in the same order); only dispatch
# granularity changes, which is exactly what makes the per-segment
# wall-clock observable. Compiled
# variants are bounded: one per distinct superstep length (bulk) and ONE
# total for elastic (every window is [slack, ...]-shaped).

@jax.jit
def _solve_segment(write_rows, cols, v, d, a, b_pad, x, acc, t0):
    """Run one contiguous run of plan steps, from plan step ``t0`` (a
    traced scalar, so segments of one length share a compile), on an
    existing (x, acc) carry. Serves both timed paths: a bulk superstep
    slice (rows int32[t, k]) and one elastic macro window (rows
    int32[slack, k]). Single- vs multi-RHS is resolved statically from
    the carry rank."""
    obs.counter_add("jit.trace.segment")  # at trace time only
    values = _values_single if x.ndim == 1 else _values_mrhs
    return walk(values, (x, acc), (write_rows, cols, v, d, a), b_pad, t0)


def _timed_carry(arrays, b):
    """Shared setup for the timed paths: padded rhs + zero carry."""
    b = jnp.asarray(b).astype(arrays.vals.dtype)
    return (pad_rhs(b), *_slot_carry(arrays.write_rows, b))


def solve_with_plan_timed(
    pa: PlanArrays, b: jax.Array
) -> Tuple[jax.Array, List[dict]]:
    """``solve_with_plan`` with per-superstep device timing: one jitted
    segment per superstep, synchronized and host-timed. Returns
    ``(x, steps)``, x in slot order as ``solve_slots`` gives it, where
    each entry is
    ``{"superstep", "n_steps", "us"}``; an ``executor.superstep`` span
    lands in the active trace buffer per segment when tracing is on."""
    b_pad, x, acc = _timed_carry(pa, b)
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
                *(t[lo:hi] for t in pa[:5]), b_pad, x, acc, jnp.int32(lo)
            )
            x.block_until_ready()
            dur = time.perf_counter_ns() - t0
        steps.append(
            {"superstep": s, "n_steps": hi - lo, "us": round(dur / 1e3, 2)}
        )
    return x, steps


def solve_with_elastic_timed(
    ea: ElasticArrays, b: jax.Array
) -> Tuple[jax.Array, List[dict]]:
    """``solve_with_elastic`` with per-macro-step device timing. Every
    window shares the [slack, ...] shape, so the whole loop compiles ONE
    ``_solve_segment`` variant. Returns ``(x, steps)``, x in slot
    order, with one
    ``{"macro_step", "n_steps", "us"}`` entry (and one
    ``executor.macro_step`` span when tracing) per executed macro-step —
    the runtime side of the elastic barrier-fusion certificate."""
    b_pad, x, acc = _timed_carry(ea, b)
    M = int(ea.write_rows.shape[0])
    steps: List[dict] = []
    for m in range(M):
        with obs.span(
            "executor.macro_step", cat="executor", macro=m, slack=ea.slack
        ):
            t0 = time.perf_counter_ns()
            x, acc = _solve_segment(
                *(t[m] for t in ea[:5]), b_pad, x, acc,
                jnp.int32(m * ea.slack),
            )
            x.block_until_ready()
            dur = time.perf_counter_ns() - t0
        steps.append(
            {"macro_step": m, "n_steps": ea.slack, "us": round(dur / 1e3, 2)}
        )
    return x, steps


def make_solver(plan: ExecPlan, dtype=jnp.float32):
    """Bind a plan; returns ``solve(b) -> x`` (jit-compiled on first call).
    ``b`` may be f[n] or f[n, m] for a batched multi-RHS solve."""
    pa = plan_arrays(plan, dtype=dtype)

    def solve(b):
        return solve_with_plan(pa, jnp.asarray(b, dtype=dtype))

    return solve
