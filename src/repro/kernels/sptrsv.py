"""Pallas TPU kernel for the scheduled SpTRSV executor.

One kernel serves every binding: single- and multi-RHS (a single RHS is
the m = 1 case) and both execution modes (``mode="elastic"`` runs the
same kernel with the tile size set to the slack window).

Layout. The solution ``x`` and the right-hand side ``b`` live in VMEM for
the whole solve as lane-dense ``(R, 128)`` arrays: slot ``c`` of an
m-wide solve (m padded to a power of two <= 128) occupies lanes
``[(c % spr) * m, (c % spr + 1) * m)`` of row ``c // spr``, with
``spr = 128 // m`` slots per row. The plan tensors (row ids, column
indices, values, diagonals, accumulate flags) stream HBM -> SMEM one tile
of ``steps_per_tile`` lock-step rows at a time. The grid dimension is
sequential ("arbitrary"), which *is* the superstep chain.

Per plan slot the scalar unit reads the column index and value from SMEM,
loads the one x row that holds the slot (dynamic sublane index), rotates
the slot's m lanes down to lane 0 and accumulates; a finishing row writes
its m lanes back with a masked read-modify-write of one x row. Mosaic has
no vector gather from VMEM at arbitrary addresses, so this per-element
form is what lowers; it is not tuned for speed.

Arithmetic order per (row, RHS) is the scan executor's exactly
(``solver.executor._step_single``): ``acc += v[w] * x[col[w]]`` for
w = 0..W-1 in order, then ``(b[row] - acc) / diag``. Lanes of one step
are independent rows of one superstep, so running them one after another
reads the same values the scan's gather-then-scatter step reads. Padding
lanes (row id n) are skipped: in the scan they only write +0.0 into the
scratch slot, which the kernel's zero-initialized scratch slot already
holds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs

LANES = 128
# Mosaic's default scoped-VMEM limit on v5e; the kernel asks for more
# only when its resident x and b need it
_DEFAULT_SCOPED_VMEM = 16 << 20


def _sptrsv_kernel(
    row_ref,  # int32[S, k]     SMEM tile
    col_ref,  # int32[S, k*W]   SMEM tile
    val_ref,  # f[S, k*W]       SMEM tile
    diag_ref,  # f[S, k]        SMEM tile
    accum_ref,  # f[S, k]       SMEM tile (0.0 / 1.0)
    b_ref,  # f[R, 128]         VMEM, resident
    x_ref,  # f[R, 128]         VMEM, resident output
    acc_ref,  # f[k8, 128]      VMEM scratch: per-lane partial sums
    *,
    steps_per_tile: int,
    k: int,
    W: int,
    m: int,
    n: int,
):
    spr = LANES // m
    shift = spr.bit_length() - 1  # spr is a power of two

    @pl.when(pl.program_id(0) == 0)
    def _init():
        x_ref[...] = jnp.zeros_like(x_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def load_slot(ref, c):
        """Slot ``c``'s m values, rotated down to lanes [0, m)."""
        v = ref[pl.ds(c >> shift, 1), :]
        if spr == 1:
            return v
        off = (c & (spr - 1)) * m
        return pltpu.roll(v, (LANES - off) & (LANES - 1), 1)

    def store_slot(ref, c, v):
        """Write lanes [0, m) of ``v`` into slot ``c``; the row's other
        slots keep their bits."""
        r = c >> shift
        if spr == 1:
            ref[pl.ds(r, 1), :] = v
            return
        off = (c & (spr - 1)) * m
        v = pltpu.roll(v, off, 1)
        mine = (lane >= off) & (lane < off + m)
        ref[pl.ds(r, 1), :] = jnp.where(mine, v, ref[pl.ds(r, 1), :])

    def lane_body(t, l):
        row = row_ref[t, l]

        @pl.when(row != n)
        def _():
            acc = acc_ref[pl.ds(l, 1), :]
            for w in range(W):
                acc = acc + val_ref[t, l * W + w] * load_slot(
                    x_ref, col_ref[t, l * W + w]
                )
            keep = accum_ref[t, l] > 0.5  # row continues in the next step

            @pl.when(keep)
            def _carry():
                acc_ref[pl.ds(l, 1), :] = acc

            @pl.when(jnp.logical_not(keep))
            def _finish():
                xv = (load_slot(b_ref, row) - acc) / diag_ref[t, l]
                store_slot(x_ref, row, xv)
                acc_ref[pl.ds(l, 1), :] = jnp.zeros_like(acc)

    def step(t, carry):
        def lane_loop(l, c):
            lane_body(t, l)
            return c

        return jax.lax.fori_loop(0, k, lane_loop, carry)

    jax.lax.fori_loop(0, steps_per_tile, step, ())


def _pow2_at_least(m: int) -> int:
    return 1 << max(m - 1, 0).bit_length()


def lane_rows(n_slots: int, m: int) -> int:
    """Rows of the ``(R, 128)`` VMEM layout holding ``n_slots`` slots of
    an m-wide solve (m a power of two <= 128), padded to a sublane
    multiple."""
    spr = LANES // m
    return -(-(-(-n_slots // spr)) // 8) * 8


def vmem_bytes(n_slots: int, m: int, k: int, itemsize: int = 4) -> int:
    """VMEM the kernel keeps resident: x and b in the lane layout plus
    the per-lane accumulator."""
    return (2 * lane_rows(n_slots, m) + max(8, -(-k // 8) * 8)) * (
        LANES * itemsize
    )


def _solve_block(row_ids, col_idx, vals, diag, accum_mask, b_pad, *,
                 steps_per_tile, interpret):
    """One kernel call for an m <= 128 wide ``b_pad`` f[n+1, m]."""
    T, k, W = col_idx.shape
    n1, m = b_pad.shape
    mp = _pow2_at_least(m)
    R = lane_rows(n1, mp)
    dt = vals.dtype
    b = jnp.pad(b_pad.astype(dt), ((0, 0), (0, mp - m)))
    b = jnp.pad(b.reshape(-1), (0, R * LANES - n1 * mp)).reshape(R, LANES)

    def smem_tile(cols):
        return pl.BlockSpec(
            (steps_per_tile, cols), lambda i: (i, 0),
            memory_space=pltpu.SMEM,
        )

    resident = pl.BlockSpec(memory_space=pltpu.VMEM)
    compiler_params = None
    if not interpret:
        need = vmem_bytes(n1, mp, k, jnp.dtype(dt).itemsize)
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # sequential grid = chain
            vmem_limit_bytes=(
                need + (2 << 20) if need > _DEFAULT_SCOPED_VMEM else None
            ),
        )
    x = pl.pallas_call(
        functools.partial(
            _sptrsv_kernel, steps_per_tile=steps_per_tile, k=k, W=W, m=mp,
            n=n1 - 1,
        ),
        grid=(T // steps_per_tile,),
        in_specs=[
            smem_tile(k),  # row_ids
            smem_tile(k * W),  # col_idx
            smem_tile(k * W),  # vals
            smem_tile(k),  # diag
            smem_tile(k),  # accum mask
            resident,  # b
        ],
        out_specs=resident,  # x
        out_shape=jax.ShapeDtypeStruct((R, LANES), dt),
        scratch_shapes=[pltpu.VMEM((max(8, -(-k // 8) * 8), LANES), dt)],
        interpret=interpret,
        compiler_params=compiler_params,
        name="sptrsv_tile",
    )(
        row_ids,
        col_idx.reshape(T, k * W),
        vals.reshape(T, k * W),
        diag,
        accum_mask,
        b,
    )
    return x.reshape(-1)[: n1 * mp].reshape(n1, mp)[:, :m]


@functools.partial(
    jax.jit,
    static_argnames=("steps_per_tile", "interpret"),
)
def sptrsv_pallas(
    row_ids,  # int32[T, k]
    col_idx,  # int32[T, k, W]
    vals,  # f[T, k, W]
    diag,  # f[T, k]
    accum_mask,  # f[T, k] (0/1)
    b_pad,  # f[n+1] or f[n+1, m] (multi-RHS)
    *,
    steps_per_tile: int = 8,
    interpret: bool = False,
):
    """Run the full scheduled solve; returns x shaped like ``b_pad`` (last
    row is scratch). A 2-D ``b_pad`` solves all m RHS in one pass per
    block of 128 columns."""
    obs.counter_add("jit.trace.pallas")  # at trace time only
    T = row_ids.shape[0]
    assert T % steps_per_tile == 0, "pad T to a multiple of steps_per_tile"
    single = b_pad.ndim == 1
    b2 = b_pad[:, None] if single else b_pad
    blocks = [
        _solve_block(
            row_ids, col_idx, vals, diag, accum_mask, b2[:, j: j + LANES],
            steps_per_tile=steps_per_tile, interpret=interpret,
        )
        for j in range(0, b2.shape[1], LANES)
    ]
    x = blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=1)
    return x[:, 0] if single else x
