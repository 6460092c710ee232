"""Closed loop: one caller applies the configuration's operators to a
right-hand side, waits for the answer, and calls again.

Traffic keys:

- ``rhs``: right-hand sides per call (1: a vector; m > 1: an [n, m] block);
- ``pool``: how many distinct right-hand sides are drawn from the seed and
  placed on the device before the window; call i takes pool[i mod pool];
- ``sample``: how many of the window's answers, drawn from the seed, are
  compared with the reference once the window has closed;
- ``trace_seconds``: with ``--trace 1``, how much of the window is traced;
- ``plan``: options that override the configuration's ``plan``.

Each call runs ``TriangularSolver.solve`` once per operator, the device
output of one going straight into the next, and ends in
``block_until_ready``.
"""
from __future__ import annotations

import time

import numpy as np

from bench import harness, reference, roofline


def run(ctx) -> dict:
    import jax

    args, traffic = ctx.args, ctx.traffic
    ops = ctx.generator.operators(ctx.config["matrix"], [int(args.seed), 7])
    n = ops[0][0].shape[0]
    m = int(traffic["rhs"])
    solvers, inspector_s = harness.plan_solvers(
        ops, harness.plan_options(ctx.config, traffic))

    pool_host = harness.rhs_pool(args.seed, int(traffic["pool"]), n, m)
    pool = [jax.device_put(b) for b in pool_host]

    def call(b):
        with harness.annotate("bench.solve"):
            for s in solvers:
                b = s.solve(b)
            return b.block_until_ready()

    # warm-up: the first call compiles (or loads from the cache), the
    # second is a steady call
    t = time.perf_counter()
    call(pool[0])
    first = time.perf_counter() - t
    t = time.perf_counter()
    call(pool[0])
    steady = time.perf_counter() - t
    compile_s = max(first - steady, 0.0)

    sample = harness.Sample(int(traffic["sample"]), harness.rng(args.seed, 2))
    tracer = harness.Tracer()
    trace_seconds = float(traffic.get("trace_seconds", args.seconds))
    traced_calls = 0
    setup_s = time.perf_counter() - ctx.t_start
    ctx.compiles.active = True
    if args.trace:
        tracer.start()
    span = harness.annotate("bench.window") if args.trace else None
    if span is not None:
        span.__enter__()
    calls = 0
    ends = []  # when each call returned: host stalls show as long calls
    t0 = time.perf_counter()
    while True:
        j = calls % len(pool)
        out = call(pool[j])
        calls += 1
        ends.append(time.perf_counter())
        sample.offer((j, out))
        elapsed = ends[-1] - t0
        if span is not None and elapsed >= trace_seconds:
            span.__exit__(None, None, None)
            span = None
            traced_calls = calls
            tracer.stop()
        if elapsed >= args.seconds:
            break
    wall = time.perf_counter() - t0
    if span is not None:
        span.__exit__(None, None, None)
        traced_calls = calls
        tracer.stop()
    ctx.compiles.active = False
    peak = harness.memory_peak_bytes(ctx.devices)

    # correctness: every sampled answer against the plain reference
    answers = [(j, np.asarray(out)) for j, out in sample.items]
    del sample, pool
    refs = harness.references(ops)
    err = max(
        reference.rel_err(x, reference.chain_solve(refs, pool_host[j]))
        for j, x in answers)

    call_s = np.diff([t0] + ends)
    work = harness.call_work(ops, m)
    steps = sum(s.exec_plan.n_steps for s in solvers)
    rec = {
        "driver": "closed_loop",
        "setup_s": setup_s,
        "inspector_s": inspector_s,
        "compile_s": compile_s,
        "backends": sorted({s.backend for s in solvers}),
        "attempted": calls,
        "failed": 0,
        "memory_peak_bytes": peak,
        "window": {"wall_s": wall, "calls": calls,
                   "solves": calls * len(solvers),
                   "call_median_s": float(np.median(call_s)),
                   "call_max_s": float(call_s.max())},
        "checks": {"rel_err": err, "answers_compared": len(answers)},
        "plans": [s.exec_plan.stats() | {"strategy": s.strategy,
                                         "mode": s.info()["mode"]}
                  for s in solvers],
    }
    if args.trace:
        tr = tracer.reduce()
        least, bound = roofline.least_seconds(work, ctx.peak)
        # the operators of one call (L and L^T) have the same size
        tr.update({
            "solve_only": True,
            "calls": traced_calls,
            "solve_steps": steps / len(solvers),
            "solve_least_s": least / len(solvers),
            "bound": bound,
        })
        rec["trace"] = tr
    return rec
