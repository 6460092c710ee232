"""Closed loop around HPCG's V-cycle: one caller applies z = M^-1 r, the
program's ``VCycle`` over the configuration's hierarchy, waits for the
answer, and calls again.

Traffic keys, as ``closed_loop.py``'s: ``rhs`` (1), ``pool`` (right-hand
sides drawn from the seed and placed on the device before the window;
call i takes pool[i mod pool]), ``sample`` (answers drawn from the seed
and compared with ``mg_reference.py`` once the window has closed, the
reference computed once for the whole pool, as one [n, pool] block at
every level) and ``trace_seconds``. Beside the answers, the comparison
(``mg_reference.compare``) holds every coarse level to the reference:
for each distinct sampled entry, the program's V-cycle from that level
down (``VCycle.from_level``, the same programs) on the reference's
restricted residual there, since a coarse level's share of z is too small
for z alone to show it wrong. The configuration gives ``matrix`` (the generator's
block and ``levels``) and ``plans``, one ``TriangularSolver.plan``
option set per level, finest first.

Its record is a closed loop's (``"driver": "closed_loop"``): a call is one
V-cycle, 4 (levels - 1) + 2 triangular solves (``window.solves``
counts them), so ``solve_ms``, ``idle_pct.solve`` and the program-span
readers read it as they read the other closed-loop cells. It is not
``solve_only`` (SpMVs and vector updates run between the solves), so the
single-solve readers report nothing. ``inspector_s`` is the host clock
around ``VCycle.build``: the plans and the hierarchy's layout. Traced, it
reads the chip trace itself (``trace.read_xplane``), for the SpMV
program's runs, the programs of one V-cycle and the chip's idle time
inside a V-cycle, beside the usual reduction.
"""
from __future__ import annotations

# the program's V-cycle first: a program without it fails the cell here,
# before any set-up
from repro.pipeline import VCycle

from repro import obs

import json
import re
import shutil
import sys
import time

import numpy as np

from bench import harness, mg_reference, mg_roofline, trace

SPMV_PROGRAM = re.compile(r"^jit__spmv_ell(\(|$)")


def solves_per_call(levels: int) -> int:
    """Triangular solves of one V-cycle: two a sweep, two sweeps at every
    level but the coarsest, one there."""
    return 4 * (levels - 1) + 2


def spmv_runs(device: dict, host: list, window: dict) -> dict:
    """The SpMV program's whole runs in the traced window on the first
    chip: how many, and their device seconds (from ``XLA Modules``)."""
    spans = [(s, e) for name, s, e in host if name == trace.WINDOW]
    if not device or not spans:
        return {"runs": 0, "seconds": 0.0}
    lo = min(s for s, _ in spans)
    hi = max(e for _, e in spans) - window["cut_s"] * 1e9
    lines = device[sorted(device)[0]]
    ev = lines.get(trace.MODULES)
    if ev is None:
        return {"runs": 0, "seconds": 0.0}
    ids = [i for name, i in ev.names.items() if SPMV_PROGRAM.match(name)]
    s, e = np.asarray(ev.start), np.asarray(ev.end)
    inside = np.isin(np.asarray(ev.name_id), ids) & (s >= lo) & (e <= hi)
    return {"runs": int(inside.sum()),
            "seconds": float((e[inside] - s[inside]).sum()) * 1e-9}


def cycle_programs(device: dict, host: list, window: dict) -> dict:
    """The programs one V-cycle runs on the first chip, in order: for each
    ``bench.solve`` span inside the traced window, the ``XLA Modules``
    events inside it; over the calls whose sequence of programs is the
    most common one, each position's name and mean device seconds. The
    positions follow the V-cycle (level 0's solves, its restriction, level
    1's, ...), so this is the traced breakdown by level. ``idle_s``: over
    every such call, the mean of its span's seconds in which the chip ran
    no program (``XLA Modules`` or, where the plane has none, ``XLA Ops``):
    from the call's start to its first program, between programs, and from
    the last to the call's return. That is the time the chip waited on the
    host inside a V-cycle: launch, dispatch that fell behind, the sync."""
    none = {"calls": 0, "names": [], "seconds": [], "idle_s": None}
    spans = [(s, e) for name, s, e in host if name == trace.WINDOW]
    if not device or not spans:
        return none
    lo = min(s for s, _ in spans)
    hi = max(e for _, e in spans) - window["cut_s"] * 1e9
    lines = device[sorted(device)[0]]
    whole = lines.get(trace.MODULES) or lines.get(trace.OPS)
    if whole is None:
        return none
    ws, we = trace.merge(whole.start, whole.end)
    ev = lines.get(trace.MODULES)
    s = np.asarray(ev.start) if ev is not None else np.empty(0)
    e = np.asarray(ev.end) if ev is not None else np.empty(0)
    ids = np.asarray(ev.name_id) if ev is not None else np.empty(0, int)
    seqs, idle = {}, []
    for name, a, b in host:
        if name == "bench.solve" and lo <= a and b <= hi:
            busy = (np.minimum(we, b) - np.maximum(ws, a)).clip(min=0).sum()
            idle.append((b - a - busy) * 1e-9)
            inside = np.flatnonzero((s >= a) & (e <= b))
            inside = inside[np.argsort(s[inside], kind="stable")]
            seqs.setdefault(tuple(ids[inside]), []).append(
                e[inside] - s[inside])
    out = dict(none, idle_s=float(np.mean(idle)) if idle else None)
    if not seqs or ev is None:
        return out
    key = max(seqs, key=lambda k: len(seqs[k]))
    names = {i: name for name, i in ev.names.items()}
    return out | {"calls": len(seqs[key]), "names": [names[i] for i in key],
                  "seconds": (np.mean(seqs[key], axis=0) * 1e-9).tolist()}


def reduce_trace(tracer) -> dict:
    """``Tracer.reduce``'s numbers, with the SpMV program's runs and the
    programs of one V-cycle."""
    t = time.perf_counter()
    try:
        device, host = trace.read_xplane(trace.find_xplane(tracer.dir))
        out = trace.reduce(device, host)
        out["spmv"] = spmv_runs(device, host, out)
        out["cycle"] = cycle_programs(device, host, out)
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)
    return out | {"stop_s": tracer.stop_s,
                  "read_s": time.perf_counter() - t}


def run(ctx) -> dict:
    import jax

    args, traffic, config = ctx.args, ctx.traffic, ctx.config
    spec = config["matrix"]
    levels = ctx.generator.levels(spec)
    n = levels[0][0].shape[0]
    if int(traffic["rhs"]) != 1:
        raise ValueError("the V-cycle cell takes one right-hand side a call")
    if (config["pre_smoother_steps"], config["post_smoother_steps"]) != (1, 1):
        raise ValueError("the program's V-cycle sweeps once before and once "
                         "after the coarse correction, as hpcg.dat's")
    plans = [harness.plan_options({"plan": p}, traffic)
             for p in config["plans"]]
    t = time.perf_counter()
    with harness.annotate("bench.plan"):
        vc = VCycle.build((int(spec["nx"]), int(spec["ny"]), int(spec["nz"])),
                          a=harness.program_csr(levels[0][0]),
                          levels=len(levels), plans=plans)
    inspector_s = time.perf_counter() - t
    per_call = solves_per_call(len(levels))

    pool_host = harness.rhs_pool(args.seed, int(traffic["pool"]), n)
    pool = [jax.device_put(b) for b in pool_host]

    def call(b):
        with harness.annotate("bench.solve"):
            return vc(b).block_until_ready()

    # warm-up: the first call compiles (or loads from the cache) every
    # program of the V-cycle, the second is a steady call
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
    ends = []
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

    # correctness: the reference once for the whole pool, one [n, pool]
    # block, level by level; each sampled answer against its column, and
    # for each distinct sampled entry the program's V-cycle from each
    # coarse level on the reference's restricted residual there (the same
    # programs; their spans go to a buffer of their own, so that the
    # window's stay the window's)
    answers = [(j, np.asarray(out)) for j, out in sample.items]
    del sample, pool
    rhs, want = mg_reference.MGReference(levels).levels_of(pool_host.T)
    with obs.tracing(obs.TraceBuffer("bench.check")):
        got_levels = {
            j: [np.asarray(vc.from_level(i, rhs[i][:, j]))
                for i in range(1, len(levels))]
            for j in sorted({j for j, _ in answers})}
    cmp = mg_reference.compare(answers, want[0], got_levels, want[1:])
    print(json.dumps({"vcycle_checks": {
        "rel_err": cmp["rel_err"], "answer_rel_err": cmp["answer"],
        "level_rel_err": cmp["levels"]}}), file=sys.stderr)

    call_s = np.diff([t0] + ends)
    solvers = vc.solvers()
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
                   "solves": calls * per_call,
                   "call_median_s": float(np.median(call_s)),
                   "call_max_s": float(call_s.max()),
                   "call_max_at": int(call_s.argmax()),
                   "call_max_at_s": float(ends[call_s.argmax()] - t0)},
        "checks": {"rel_err": cmp["rel_err"],
                   "answers_compared": len(answers)},
        "plans": [s.exec_plan.stats() | {"strategy": s.strategy,
                                         "mode": s.info()["mode"],
                                         "level": i // 2}
                  for i, s in enumerate(solvers)],
    }
    if args.trace:
        tr = reduce_trace(tracer)
        least = mg_roofline.least_seconds(levels, ctx.peak)
        recorded = tr["window_s"] / (tr["window_s"] + tr["cut_s"])
        tr["spmv"]["per_cycle"] = least["spmv_count"]
        tr.update({
            "solve_only": False,
            "calls": traced_calls,
            "cycles": traced_calls * recorded,
            "vcycle_least_s": least["vcycle"],
            "spmv_least_s": least["spmv"],
            "bound": least["bounds"],
        })
        rec["trace"] = tr
    return rec
