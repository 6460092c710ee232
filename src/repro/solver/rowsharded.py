"""Row-sharded SpTRSV executor — local supersteps + halo exchange.

Device half of the ``distributed`` backend's ``shard="rows"`` binding
mode (partitioning in ``repro.core.rowshard``; bind through
``get_backend("distributed").bind(plan, mesh=mesh, shard="rows")``).

Each ``model``-axis device owns one shard: a contiguous block of
``k_local`` schedule cores and their rows. Its x-buffer is *resident* —
``[owned | halo | scratch]`` local slots — and a solve is the ordinary
scan over the shard's local ``ExecPlan`` (the exact ``_values_single`` /
``_values_mrhs`` arithmetic from ``solver.executor``, so per-row
arithmetic is bitwise-identical to the single-chip scan), punctuated by
one halo exchange per barrier round. Unlike the single-chip scan, which
keeps x in step-slot order and writes each step's results with one
contiguous update, a shard keeps x in its compact local slot space —
the halo slots the exchange fills sit among them — so a step scatters
its k results to their local slots. The scan carries one slot past the
local slots: the sink that the step's ``accum`` lanes write.

Unlike the model-axis executor (``solver.distributed``), which
``all_gather``s every core's xv at every superstep, the exchange moves
ONLY the boundary values some other shard actually reads — static index
tensors computed at partition time.

Two lowerings of the same exchange plan:

  * ``mode="ring"`` (default): one ``ppermute`` per occupied hop
    distance per round. Values move bits unchanged — this is the
    bitwise-safe path the conformance tests pin.
  * ``mode="psum"``: scatter-add into a shared sparse boundary buffer,
    one ``psum`` per round, gather into halo slots. Fewest collectives,
    but ``-0.0 + 0.0 == +0.0`` makes it not bitwise-safe; bench/opt-in.

Because each device simulates its ``k_local`` cores with the full-width
einsum step (not one lane per device), ``shard="rows"`` also lifts the
model-axis mode's ``k <= mesh devices`` restriction — a k=256 schedule
runs on 8 devices as 8 shards of 32 lanes.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.rowshard import RowShardPlan
from repro.solver.executor import (
    _values_mrhs,
    _values_single,
    to_device,
    w_major,
)


@dataclasses.dataclass(frozen=True)
class RowShardSpec:
    """Static (hashable) description of a row-sharded solve: everything
    the traced graph's structure depends on. Per-round exchange-table
    shapes are static too — they ride in ``rounds_static``
    (``rowshard_round_static``) for cache keys; table *contents* travel
    as operands."""

    n: int
    n_shards: int
    k_local: int
    W: int
    T: int
    n_loc: int
    n_halo: int
    step_bounds: Tuple[int, ...]
    exchange_bounds: Tuple[int, ...]
    rounds_static: Tuple  # see rowshard_round_static
    mode: str = "ring"  # "ring" | "psum"
    batch: int = 0  # 0 = single RHS; else padded multi-RHS width

    @property
    def slots(self) -> int:
        return self.n_loc + self.n_halo + 1

    @property
    def n_rounds(self) -> int:
        return len(self.exchange_bounds) - 1


def rowshard_round_static(rsp: RowShardPlan, mode="ring"):
    """The exchange schedule's static shape: ring -> one
    ``(hop, width)`` pair per occupied hop per round; psum ->
    ``(send_w, recv_w, buf_size)`` per round."""
    if mode == "ring":
        return tuple(
            tuple((int(h), int(ss.shape[1])) for h, ss, _ in r.hops)
            for r in rsp.rounds
        )
    return tuple(
        (int(r.send_slot.shape[1]), int(r.recv_pos.shape[1]), int(r.buf_size))
        for r in rsp.rounds
    )


def rowshard_spec(rsp: RowShardPlan, *, mode="ring", batch=0) -> RowShardSpec:
    if mode not in ("ring", "psum"):
        raise ValueError(f"exchange mode must be 'ring' or 'psum': {mode!r}")
    return RowShardSpec(
        n=rsp.n,
        n_shards=rsp.n_shards,
        k_local=rsp.k_local,
        W=rsp.W,
        T=rsp.T,
        n_loc=rsp.n_loc,
        n_halo=rsp.n_halo,
        step_bounds=tuple(rsp.step_bounds),
        exchange_bounds=tuple(rsp.exchange_bounds),
        rounds_static=rowshard_round_static(rsp, mode),
        mode=mode,
        batch=batch,
    )


def laid_out_rows(plan):
    """A shard plan's step tensors, host-side, in the row layout: the
    scan executor's w-major step layout, but ``write_rows`` int32[T, k]
    holds each lane's local slot (padding: the scratch slot n, the
    shard plan's own padding; ``accum`` lanes: the sink n + 1, which no
    gather reads) and ``cols`` local slots (padding: n)."""
    n, k, W = plan.n, plan.k, plan.W
    cols = w_major(plan.col_idx)
    return (
        np.where(plan.accum, n + 1, plan.row_ids),
        cols.reshape(cols.shape[0], W * k),
        w_major(plan.vals),
        plan.diag,
        plan.accum,
    )


def rowshard_plan_args(rsp: RowShardPlan, dtype=jnp.float32):
    """Stack the per-shard plans, in the row layout (``laid_out_rows``),
    into device operands [n_shards, T, ...] (sharded over ``model`` by
    shard_map)."""
    per_shard = zip(*(laid_out_rows(s) for s in rsp.shards))
    return to_device([np.stack(t) for t in per_shard], dtype)


def _scatter_step(values):
    """The row layout's step: ``values``' k results scattered to the
    lanes' local slots. Padded lanes share the scratch slot and accum
    lanes the sink, so indices are not unique; a plain scatter keeps
    them well-defined (they all write junk to those two slots)."""

    def step(x, acc, rows, cols, v, d, a, bt):
        val, acc = values(x, acc, cols, v, d, a, bt)
        return x.at[rows].set(val), acc

    return step


def with_sink(x):
    """``x`` f[slots(, m)] with one zero slot appended: the sink."""
    return jnp.concatenate([x, jnp.zeros((1, *x.shape[1:]), x.dtype)])


def walk_rows(step, carry, steps, b_pad):
    """Run ``step`` over ``steps`` = (write_rows, cols, vals, diag,
    accum), leading axis first, from ``carry`` = (x, acc); b is gathered
    for every step at once, before the loop."""

    def body(carry, inp):
        return step(*carry, *inp), None

    carry, _ = jax.lax.scan(body, carry, (*steps, b_pad[steps[0]]))
    return carry


def rowshard_halo_args(rsp: RowShardPlan, mode="ring"):
    """The exchange plan as a FLAT tuple of int32[n_shards, H] operands
    (shard_map slices each along ``model``). Ring: per round, per hop,
    ``send_slot`` then ``recv_slot`` — order matches
    ``rowshard_round_static``; psum: per round ``send_slot, send_pos,
    recv_pos, recv_slot``."""
    flat = []
    for r in rsp.rounds:
        if mode == "ring":
            for _, ss, rt in r.hops:
                flat.append(jnp.asarray(ss, jnp.int32))
                flat.append(jnp.asarray(rt, jnp.int32))
        else:
            flat.append(jnp.asarray(r.send_slot, jnp.int32))
            flat.append(jnp.asarray(r.send_pos, jnp.int32))
            flat.append(jnp.asarray(r.recv_pos, jnp.int32))
            flat.append(jnp.asarray(r.recv_slot, jnp.int32))
    return tuple(flat)


PLAN_SPECS = (
    P("model", None, None),  # write_rows [n_shards, T, k_local]
    P("model", None, None),  # cols [n_shards, T, W * k_local]
    P("model", None, None, None),  # vals [n_shards, T, W, k_local]
    P("model", None, None),  # diag
    P("model", None, None),  # accum
)


def _exchange_ring(x, tables, hops_static, n_shards):
    """One ring round on the local x ([slots] or [slots, m]): per hop h,
    every shard i sends its boundary values finalized this round to
    shard (i + h) % n_shards in a single ``ppermute``. Sender/receiver
    tables are positionally aligned by construction (sorted by global
    row id within each src->dst pair; dst = src + h is a bijection per
    hop), so the position IS the routing. Padded positions send the
    scratch slot — provably +0.0 (by induction: a padded lane gathers
    the scratch slot with value 0 and writes (0 - 0) / 1 back) — and
    land on the receiver's scratch slot: ragged per-shard halo counts
    stay bitwise harmless."""
    for (h, _), (ss, rt) in zip(hops_static, tables):
        perm = [(i, (i + h) % n_shards) for i in range(n_shards)]
        got = jax.lax.ppermute(x[ss[0]], "model", perm=perm)
        x = x.at[rt[0]].set(got)
    return x


def _exchange_psum(x, tables, buf_size):
    """One sparse-psum round: owners scatter-add fresh boundary values
    into a shared [buf_size + 1] buffer (position buf_size is the
    padding trash slot), one ``psum`` reduces it, consumers gather their
    positions into halo slots. Each position is written by exactly one
    owner, so the reduction is value + zeros — numerically exact but NOT
    bitwise-safe when the value is -0.0 (-0.0 + 0.0 == +0.0)."""
    ss, sp, rp, rt = tables
    tail = x.shape[1:]
    buf = jnp.zeros((buf_size + 1, *tail), x.dtype)
    buf = buf.at[sp[0]].add(x[ss[0]])
    # repro: blessed-reduction — value + zeros per position (exactly one
    # owner writes each); numerically exact, -0.0 hazard documented
    # above, and the executor defaults to the bitwise-safe ring form
    buf = jax.lax.psum(buf, "model")
    return x.at[rt[0]].set(buf[rp[0]])


def _group_tables(spec: RowShardSpec, flat):
    """Regroup the flat halo operands by round (inverse of
    ``rowshard_halo_args``), using the static shape schedule."""
    rounds, i = [], 0
    for rs in spec.rounds_static:
        if spec.mode == "ring":
            tabs = tuple(
                (flat[i + 2 * j], flat[i + 2 * j + 1])
                for j in range(len(rs))
            )
            i += 2 * len(rs)
        else:
            tabs = tuple(flat[i: i + 4])
            i += 4
        rounds.append(tabs)
    return rounds


def _run_round(spec, step, x, acc, plan, b_pad, r):
    """Scan the plan steps of exchange round ``r`` on the carry."""
    sb, eb = spec.step_bounds, spec.exchange_bounds
    lo, hi = sb[eb[r]], sb[eb[r + 1]]
    if hi == lo:
        return x, acc
    return walk_rows(step, (x, acc), tuple(t[lo:hi] for t in plan), b_pad)


def _local_operands(args):
    """The shard's plan tensors and its rhs with the sink row appended,
    from shard_map's operands (whose size-1 shard axis is stripped)."""
    plan = tuple(a[0] for a in args[:5])
    return plan, with_sink(args[-1][0])


def build_rowsharded_solver(spec: RowShardSpec, mesh: Mesh):
    """Returns a jittable
    ``solve(rows, cols, vals, diag, accum, *halo, b_loc) -> x_owned``
    shard-mapped over (model: shards, data: RHS batch).

    ``b_loc`` is the rhs pre-scattered into local slots
    (``RowShardPlan.b_scatter``): f[n_shards, slots] single-RHS or
    f[n_shards, slots, batch] multi-RHS (batch sharded over ``data``).
    Returns the stacked owned regions f[n_shards, n_loc(, batch)] —
    recover global order with ``RowShardPlan.x_gather``."""
    mrhs = spec.batch > 0
    n_halo_args = sum(
        (2 * len(rs) if spec.mode == "ring" else 4)
        for rs in spec.rounds_static
    )
    halo_specs = (P("model", None),) * n_halo_args
    b_spec = P("model", None, "data") if mrhs else P("model", None)
    out_spec = P("model", None, "data") if mrhs else P("model", None)

    def body(*args):
        halo = _group_tables(spec, args[5:-1])
        plan, b_pad = _local_operands(args)
        step = _scatter_step(_values_mrhs if mrhs else _values_single)
        x = jnp.zeros_like(b_pad)
        acc = jnp.zeros((spec.k_local, *b_pad.shape[1:]), b_pad.dtype)
        for r in range(spec.n_rounds):
            x, acc = _run_round(spec, step, x, acc, plan, b_pad, r)
            if r < spec.n_rounds - 1:
                if spec.mode == "ring":
                    x = _exchange_ring(
                        x, halo[r], spec.rounds_static[r], spec.n_shards
                    )
                else:
                    x = _exchange_psum(x, halo[r], spec.rounds_static[r][2])
        return x[: spec.n_loc][None]

    return shard_map(
        body,
        mesh=mesh,
        in_specs=PLAN_SPECS + halo_specs + (b_spec,),
        out_specs=out_spec,
        check_rep=False,
    )


def build_rowsharded_round(spec: RowShardSpec, mesh: Mesh, r: int):
    """One exchange round as its own shard-mapped call, for the timed
    path: ``round(rows, ..., *round_halo, b_loc, x_global) -> x_global``
    where ``x_global`` f[n_shards, slots(, batch)] carries the resident
    shards between calls. The per-round accumulator starts at zero —
    valid because virtual-row chains never span a superstep boundary
    (the plan's accumulator is provably zero at every barrier), so the
    segmented replay emits the same op sequence as the fused graph."""
    mrhs = spec.batch > 0
    rs = spec.rounds_static[r] if r < len(spec.rounds_static) else ()
    do_exchange = r < spec.n_rounds - 1
    n_halo_args = (2 * len(rs) if spec.mode == "ring" else 4) if do_exchange else 0
    halo_specs = (P("model", None),) * n_halo_args
    xb_spec = P("model", None, "data") if mrhs else P("model", None)

    def body(*args):
        halo = args[5:5 + n_halo_args]
        plan, b_pad = _local_operands(args[:-1])
        # the carried x gets the sink row for this round's scan only
        x = with_sink(args[-1][0])
        step = _scatter_step(_values_mrhs if mrhs else _values_single)
        acc = jnp.zeros((spec.k_local, *b_pad.shape[1:]), b_pad.dtype)
        x, acc = _run_round(spec, step, x, acc, plan, b_pad, r)
        x = x[:-1]
        if do_exchange:
            if spec.mode == "ring":
                tabs = tuple(
                    (halo[2 * j], halo[2 * j + 1]) for j in range(len(rs))
                )
                x = _exchange_ring(x, tabs, rs, spec.n_shards)
            else:
                x = _exchange_psum(x, tuple(halo), rs[2])
        return x[None]

    return shard_map(
        body,
        mesh=mesh,
        in_specs=PLAN_SPECS + halo_specs + (xb_spec, xb_spec),
        out_specs=xb_spec,
        check_rep=False,
    )


def halo_args_for_round(rsp: RowShardPlan, r: int, mode="ring"):
    """The flat halo operands for round ``r`` only (timed path)."""
    hr = rsp.rounds[r]
    if mode == "ring":
        out = []
        for _, ss, rt in hr.hops:
            out.append(jnp.asarray(ss, jnp.int32))
            out.append(jnp.asarray(rt, jnp.int32))
        return tuple(out)
    return (
        jnp.asarray(hr.send_slot, jnp.int32),
        jnp.asarray(hr.send_pos, jnp.int32),
        jnp.asarray(hr.recv_pos, jnp.int32),
        jnp.asarray(hr.recv_slot, jnp.int32),
    )


def lower_rowsharded_solve(
    rsp: RowShardPlan, mesh: Mesh, *, batch=0, dtype=np.float32, mode="ring"
):
    """.lower() the sharded solve on the given mesh (compile only): real
    partition tensors, jit + shard_map, no execution."""
    spec = rowshard_spec(rsp, mode=mode, batch=batch)
    solve = build_rowsharded_solver(spec, mesh)
    args = rowshard_plan_args(rsp, dtype=jnp.dtype(np.dtype(dtype).name))
    halo = rowshard_halo_args(rsp, mode)
    shape = (
        (rsp.n_shards, spec.slots)
        if batch == 0
        else (rsp.n_shards, spec.slots, batch)
    )
    b_loc = jnp.zeros(shape, np.dtype(dtype))
    with mesh:
        return jax.jit(solve).lower(*args, *halo, b_loc)
