"""What every driver shares: the system under test, spans, sampling, checks.

The program is reached only here and in the drivers: ``TriangularSolver``
and ``SolveService`` are the system under test, and its inputs are the
benchmark's own matrices handed over as the program's CSR type.
"""
from __future__ import annotations

import os
import shutil
import tempfile
import time

import numpy as np

from bench import reference, roofline, trace


def annotate(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), salt])


def rhs_pool(seed: int, count: int, n: int, m: int = 1) -> np.ndarray:
    """The right-hand sides a run draws before its window: ``count`` of
    them, standard normal in float32, each ``[n]`` (m = 1) or ``[n, m]``."""
    shape = (count, n) if m == 1 else (count, n, m)
    return rng(seed, 1).standard_normal(shape).astype(np.float32)


def program_csr(a):
    """The benchmark's scipy CSR matrix as the program's ``CSRMatrix``."""
    from repro.sparse.csr import CSRMatrix

    n = a.shape[0]
    return CSRMatrix(n, n, a.indptr.astype(np.int64),
                     a.indices.astype(np.int64), a.data.astype(np.float64))


def plan_options(config: dict, traffic: dict) -> dict:
    """The configuration's plan options, with the traffic's overrides."""
    return {**config.get("plan", {}), **traffic.get("plan", {})}


def plan_solvers(ops, options: dict):
    """Plan one ``TriangularSolver`` per operator; returns the solvers and
    the inspector's host seconds, taken around the program's ``plan``."""
    from repro.pipeline import TriangularSolver

    solvers, seconds = [], 0.0
    for a, lower in ops:
        t = time.perf_counter()
        with annotate("bench.plan"):
            solvers.append(
                TriangularSolver.plan(program_csr(a), lower=lower, **options))
        seconds += time.perf_counter() - t
    return solvers, seconds


def references(ops):
    return [reference.TriangularReference(a, lower) for a, lower in ops]


def call_work(ops, m: int) -> dict:
    """The work of one call, counted from its matrices."""
    out = {"bytes": 0, "flops": 0}
    for a, _ in ops:
        w = roofline.solve_work(a.shape[0], a.nnz, m)
        out["bytes"] += w["bytes"]
        out["flops"] += w["flops"]
    return out


class Sample:
    """A fixed-size sample of a stream drawn from the seed (reservoir
    sampling): every item of the stream is kept with the same chance."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size = size
        self.rng = rng
        self.items = []
        self.seen = 0

    def offer(self, item) -> None:
        if len(self.items) < self.size:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.size:
                self.items[j] = item
        self.seen += 1


class CompileCounter:
    """Counts the backend compiles JAX reports while it is active."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **kw):
        if self.active and event == self.EVENT:
            self.count += 1


class Tracer:
    """One profiler session into a private directory under ``TMPDIR``;
    ``reduce`` reads it back into the trace's numbers and deletes it. The
    host seconds that stopping and reading the trace took are reported
    beside them (``stop_s``, ``read_s``)."""

    def __init__(self):
        self.dir = None
        self.on = False
        self.stop_s = 0.0

    def start(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self.dir)
        self.on = True

    def stop(self):
        import jax

        if self.on:
            t = time.perf_counter()
            jax.profiler.stop_trace()
            self.stop_s = time.perf_counter() - t
            self.on = False

    def reduce(self) -> dict:
        t = time.perf_counter()
        try:
            out = trace.reduce_file(trace.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return out | {"stop_s": self.stop_s,
                      "read_s": time.perf_counter() - t}


def memory_peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def checkout_cache_dir(root) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else a fixed directory inside the checkout."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        str(root), ".jax_cache")
