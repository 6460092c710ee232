"""Compile the main path's device programs for a described TPU v5e.

No chip is attached: the TPU compiler builds each program for a
``v5e:2x2`` topology that is only described, at the shapes
``chip_smoke.py`` runs, and raises what the chip's compiler would raise
(an unsupported Pallas construct, too much VMEM, a program that does
not fit). Nothing executes, so these tests say nothing about results or
times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may hold the TPU library, and the
test worker that is handed this file is the one that loads it.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, SingleDeviceSharding

# chip_smoke.py shapes: the 64^3 Poisson IC(0) factor at k=8, W=4,
# elastic slack 8, with T = n, one step per row (the serial plan GrowLocal
# gave it before its frontier-widening cut: the largest T at this n), and
# the n = 1e6 narrow-band matrix planned by growlocal at k=4
SOLVE_N, SOLVE_T, SOLVE_K, SOLVE_W, SLACK = 262_144, 262_144, 8, 4, 8
BAND_N, BAND_T, BAND_K, BAND_W = 1_000_000, 251_094, 4, 4
N_RHS = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip cannot be read back from the
    # persistent cache; keep it out of the cache entirely
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _plan_shapes(T, k, W, sharding, lead=()):
    """The ExecPlan's tensors, as the Pallas kernel takes them."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        (*lead, *shape), dt, sharding=sharding
    )
    return (
        s((T, k), jnp.int32),
        s((T, k, W), jnp.int32),
        s((T, k, W), jnp.float32),
        s((T, k), jnp.float32),
        s((T, k), jnp.bool_),
    )


def _step_shapes(T, k, W, sharding, lead=()):
    """The scan executor's step layout (``solver.executor``): b's row
    per lane, w-major flat gather slots, [W, k] values, diag, accum."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        (*lead, *shape), dt, sharding=sharding
    )
    return (
        s((T, k), jnp.int32),
        s((T, W * k), jnp.int32),
        s((T, W, k), jnp.float32),
        s((T, k), jnp.float32),
        s((T, k), jnp.bool_),
    )


def _rhs(n, m, sharding):
    shape = (n,) if m == 1 else (n, m)
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("m", [1, N_RHS])
def test_scan_executor_compiles(one_chip, m):
    from repro.solver.executor import _solve_scan, _solve_scan_mrhs

    fn = _solve_scan if m == 1 else _solve_scan_mrhs
    compiled = fn.lower(
        *_step_shapes(BAND_T, BAND_K, BAND_W, one_chip),
        _rhs(BAND_N, m, one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


@pytest.mark.parametrize("m", [1, N_RHS])
def test_elastic_scan_executor_compiles(one_chip, m):
    from repro.solver.executor import _solve_elastic, _solve_elastic_mrhs

    fn = _solve_elastic if m == 1 else _solve_elastic_mrhs
    M = SOLVE_T // SLACK
    compiled = fn.lower(
        *_step_shapes(SLACK, SOLVE_K, SOLVE_W, one_chip, lead=(M,)),
        _rhs(SOLVE_N, m, one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def _computations(hlo: str) -> dict:
    """Compiled HLO text -> {computation name: its instruction lines}."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) ", line)
        if head and line.rstrip().endswith("{"):
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _while_body_ops(hlo: str, op: str) -> int:
    """The instructions of the one while loop's body that are an ``op``
    (``gather``, ``scatter``, ``dynamic-update-slice``), themselves or
    inside the fusion they call (fusions nest)."""
    comps = _computations(hlo)
    bodies = [
        re.search(r"body=%([\w.\-]+)", line).group(1)
        for lines in comps.values() for line in lines if " while(" in line
    ]
    assert len(bodies) == 1, bodies

    def moves(line):
        if re.search(rf" {op}\(", line):
            return True
        called = re.search(r"calls=%([\w.\-]+)", line)
        return bool(called) and any(map(moves, comps[called.group(1)]))

    return sum(map(moves, comps[bodies[0]]))


# the plan steps one iteration of the compiled loop walks: the elastic
# scan unrolls a slack window of 8, the bulk scan walks one; k 128, W 13
# is the hpcg_104.symgs cell's plan (hdagg on HPCG's 27-point block)
@pytest.mark.parametrize(
    "name, k, W, m, window",
    [("_solve_elastic", 8, 4, 1, SLACK), ("_solve_scan_mrhs", 8, 12, N_RHS, 1),
     ("_solve_elastic", 128, 13, 1, SLACK)],
    ids=["elastic", "mrhs", "elastic-k128"],
)
def test_step_body_is_one_gather_and_one_scatter(one_chip, name, k, W, m,
                                                 window):
    """The compiled loop body moves x with two device ops per plan step:
    the gather of every slot's x and one contiguous write of the k
    results (a dynamic-update-slice; x is kept in step-slot order, so
    the step scatters nothing). The rhs is gathered before the loop,
    and an accum lane writes its own slot instead of reading its row
    back. Small T, so the compile stays fast."""
    from repro.solver import executor

    T = 1024
    lead = (T // window, window) if window > 1 else (T,)
    hlo = getattr(executor, name).lower(
        *_step_shapes(lead[-1], k, W, one_chip, lead=lead[:-1]),
        _rhs(T, m, one_chip),
    ).compile().as_text()
    assert _while_body_ops(hlo, "scatter") == 0
    assert _while_body_ops(hlo, "gather") == window
    assert _while_body_ops(hlo, "dynamic-update-slice") == window


@pytest.mark.parametrize("m", [1, N_RHS])
def test_pallas_kernel_compiles(one_chip, m):
    """The bulk kernel (the elastic binding runs the same kernel with
    the tile set to the slack window) lowers through Mosaic, and its
    resident x and b fit the VMEM it asks for."""
    from repro.kernels.sptrsv import sptrsv_pallas, vmem_bytes

    row, col, val, diag, accum = _plan_shapes(
        SOLVE_T, SOLVE_K, SOLVE_W, one_chip
    )
    accum = jax.ShapeDtypeStruct(accum.shape, jnp.float32, sharding=one_chip)
    compiled = sptrsv_pallas.lower(
        row, col, val, diag, accum, _rhs(SOLVE_N + 1, m, one_chip),
        steps_per_tile=8, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # v5e has 128 MiB of VMEM per core
    assert vmem_bytes(SOLVE_N + 1, m, SOLVE_K) < 100 << 20


@pytest.mark.parametrize("lanes", [1, 32])
def test_banked_grouped_scan_compiles(one_chip, lanes):
    """The continuous serving pass: ``lanes`` resident RHS slots solved
    against a one-plan bank (the 64^3 factor's width class) within one
    chip's 16 GB."""
    from repro.solver.executor import _solve_scan_banked

    bank = _step_shapes(SOLVE_T, SOLVE_K, SOLVE_W, one_chip, lead=(1,))
    perm = jax.ShapeDtypeStruct((1, SOLVE_N), jnp.int32, sharding=one_chip)
    compiled = _solve_scan_banked.lower(
        *bank, perm, perm,
        jax.ShapeDtypeStruct((lanes,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((SOLVE_N, lanes), jnp.float32,
                             sharding=one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8e9


# HPCG's 104^3 block (the V-cycle cell's finest level): A's rows at the
# 52^3 coarse points for the restriction, and D^-1 U on the whole level
HPCG_N, HPCG_NC = 104 ** 3, 52 ** 3


@pytest.mark.parametrize("rows, width", [(HPCG_NC, 27), (HPCG_N, 13)],
                         ids=["restriction", "upper"])
def test_multigrid_spmv_compiles(one_chip, rows, width):
    """The V-cycle's ELL SpMV: one gather of x at every [width, rows]
    slot and a sum over the width, no scatter."""
    from repro.solver.ell import _spmv_ell

    compiled = _spmv_ell.lower(
        jax.ShapeDtypeStruct((width, rows), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((width, rows), jnp.float32, sharding=one_chip),
        _rhs(HPCG_N, 1, one_chip),
    ).compile()
    hlo = compiled.as_text()
    assert " gather(" in hlo and " scatter(" not in hlo
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2e9


@pytest.fixture(scope="module")
def band_plan():
    """The ``--chips 4`` phase's plan: growlocal at k=4 on the n = 1e6
    narrow-band matrix (host-side inspector only)."""
    from repro.pipeline import TriangularSolver
    from repro.sparse.generators import narrow_band_lower

    L = narrow_band_lower(BAND_N, 0.12, 8, seed=3)
    return TriangularSolver.plan(L, strategy="growlocal", k=BAND_K).exec_plan


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))


def test_rowsharded_solve_compiles_on_four_chips(mesh4, band_plan):
    from repro.core.rowshard import partition_plan
    from repro.solver.rowsharded import lower_rowsharded_solve

    assert band_plan.n_steps == BAND_T
    rsp = partition_plan(band_plan, 4)
    compiled = lower_rowsharded_solve(rsp, mesh4).compile()
    txt = compiled.as_text()
    assert "collective-permute" in txt  # the halo ring


def test_model_sharded_solve_compiles_on_four_chips(mesh4, band_plan):
    from repro.solver.distributed import (
        dist_plan_spec,
        lower_distributed_solve,
    )

    spec = dist_plan_spec(band_plan, batch=1)
    txt = lower_distributed_solve(spec, mesh4).compile().as_text()
    # the per-superstep all_gather (the TPU compiler may lower a small
    # one as an all-reduce)
    assert "all-gather" in txt or "all-reduce" in txt
