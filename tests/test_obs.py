"""``repro.obs`` — the tracing core's contracts.

What this file pins down:

  * **disabled-path purity** — with tracing off, ``span()`` returns the
    process-wide ``NULL_SPAN`` singleton (identity, not equality: the
    zero-allocation guarantee) and neither spans nor counters reach any
    buffer;
  * **span nesting and threading** — records carry the emitting thread,
    per-thread streams bracket properly, and the Chrome exporter's B/E
    event stream survives a stack-simulation validation after a
    round-trip through JSON on disk;
  * **counter wrap/reset** — counters are exact ints that wrap modulo
    ``COUNTER_WRAP`` and survive ``clear()`` (only ``reset_counters``
    zeroes them);
  * **end-to-end instrumentation** — one ``plan()`` + solve under
    ``obs.tracing()`` produces spans from the inspector, autotune,
    cache, backend, and executor layers; ``timed=True`` solves return
    per-superstep timings and (elastic) a runtime macro-step certificate
    in ``describe()``;
  * **LatencyReservoir thread-safety** (satellite regression): hammering
    ``add`` and ``percentiles_us`` concurrently must not raise — the
    unlocked deque iteration crashed with "deque mutated during
    iteration" under serving load.
"""
import gc
import json
import threading

import numpy as np
import pytest

from repro import obs
from repro.pipeline import PlanCache, TriangularSolver
from repro.serve.metrics import LatencyReservoir
from repro.sparse.generators import erdos_renyi_lower


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with tracing globally off, and runs
    with automatic garbage collection paused, so that no ``host.gc``
    span joins the spans a test counts (``gc.collect()`` still runs and
    is still recorded)."""
    obs.disable()
    gc.disable()
    yield
    gc.enable()
    obs.disable()


def _matrix(n=150, seed=7):
    return erdos_renyi_lower(n, 0.03, seed=seed)


# --------------------------------------------------------- disabled path
def test_disabled_span_is_null_singleton():
    assert not obs.is_enabled()
    s1 = obs.span("a", cat="x", k=1)
    s2 = obs.span("b")
    assert s1 is obs.NULL_SPAN and s2 is obs.NULL_SPAN
    with s1 as inner:
        assert inner is obs.NULL_SPAN
        inner.set(anything=True)  # no-op, returns the singleton
    assert obs.active_buffer() is None


def test_disabled_records_nothing():
    buf = obs.get_buffer("default")
    n0, c0 = len(buf), dict(buf.counters())
    with obs.span("ghost", cat="x"):
        obs.counter_add("ghost.counter", 5)
    assert len(buf) == n0
    assert buf.counters() == c0


def test_disabled_survives_exception():
    with pytest.raises(ValueError):
        with obs.span("ghost"):
            raise ValueError("boom")


# ---------------------------------------------------------- enabled path
def test_span_records_and_nests():
    buf = obs.TraceBuffer("t1")
    with obs.tracing(buf):
        with obs.span("outer", cat="c", a=1) as sp:
            with obs.span("inner", cat="c"):
                pass
            sp.set(b=2)
    assert not obs.is_enabled()  # tracing() restored the off state
    recs = buf.spans()
    assert [r.name for r in recs] == ["inner", "outer"]  # completion order
    outer = recs[1]
    assert outer.args == {"a": 1, "b": 2}
    assert outer.t1_ns >= outer.t0_ns
    inner = recs[0]
    assert outer.t0_ns <= inner.t0_ns and inner.t1_ns <= outer.t1_ns


def test_span_records_exception_and_reraises():
    buf = obs.TraceBuffer("t2")
    with obs.tracing(buf):
        with pytest.raises(RuntimeError):
            with obs.span("fails"):
                raise RuntimeError("boom")
    (rec,) = buf.spans()
    assert rec.args["error"] == "RuntimeError"


def test_default_cat_is_name_prefix():
    buf = obs.TraceBuffer("t3")
    with obs.tracing(buf):
        with obs.span("executor.solve"):
            pass
    assert buf.spans()[0].cat == "executor"


def test_buffer_cap_counts_drops():
    buf = obs.TraceBuffer("t4", cap=2)
    with obs.tracing(buf):
        for i in range(5):
            with obs.span(f"s{i}"):
                pass
    assert len(buf) == 2 and buf.dropped == 3
    buf.clear()
    assert len(buf) == 0 and buf.dropped == 0


def test_threaded_spans_tag_their_thread():
    buf = obs.TraceBuffer("t5")
    n_threads, per = 8, 50
    barrier = threading.Barrier(n_threads)

    def work(i):
        barrier.wait()
        for j in range(per):
            with obs.span("worker", cat="x", i=i, j=j):
                obs.counter_add("work.done")

    with obs.tracing(buf):
        ts = [threading.Thread(target=work, args=(i,)) for i in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    # a garbage collection during the run adds its own host.gc span
    spans = [r for r in buf.spans() if r.name == "worker"]
    assert len(spans) == n_threads * per
    assert buf.counters()["work.done"] == n_threads * per
    assert len({r.tid for r in spans}) == n_threads


# --------------------------------------------------------------- counters
def test_counter_wrap_and_reset():
    buf = obs.TraceBuffer("t6")
    with obs.tracing(buf):
        obs.counter_add("c", obs.COUNTER_WRAP - 1)
        assert buf.counters()["c"] == obs.COUNTER_WRAP - 1
        obs.counter_add("c", 3)  # wraps
        assert buf.counters()["c"] == 2
        obs.counter_add("neg", -5)
        assert buf.counters()["neg"] == obs.COUNTER_WRAP - 5
    buf.clear()  # spans gone, counters survive
    assert buf.counters()["c"] == 2
    buf.reset_counters()
    assert buf.counters() == {}


# --------------------------------------------------------------- exporter
def test_chrome_trace_roundtrip(tmp_path):
    buf = obs.TraceBuffer("t7")
    with obs.tracing(buf):
        with obs.span("outer", cat="a", n=3):
            with obs.span("inner", cat="b"):
                pass
        with obs.span("sibling", cat="a"):
            pass
        obs.counter_add("hits", 2)
    path = tmp_path / "trace.json"
    payload = obs.export_chrome_trace(str(path), buf)
    assert payload["schema"] == obs.TRACE_SCHEMA
    loaded = obs.load_chrome_trace(str(path))
    assert loaded == json.loads(json.dumps(payload))  # exact round-trip
    report = obs.validate_chrome_trace(loaded)
    assert report["n_pairs"] == 3
    assert set(report["cats"]) == {"a", "b"}
    assert loaded["counters"] == {"hits": 2}
    # ts monotonic + B/E bracketing are what validate_chrome_trace
    # enforces; check the args survived too
    begins = {
        ev["name"]: ev
        for ev in loaded["traceEvents"]
        if ev.get("ph") == "B"
    }
    assert begins["outer"]["args"] == {"n": 3}


def test_validate_rejects_broken_traces():
    ok = {
        "traceEvents": [
            {"ph": "B", "name": "s", "tid": 1, "ts": 1.0},
            {"ph": "E", "name": "s", "tid": 1, "ts": 2.0},
        ]
    }
    assert obs.validate_chrome_trace(ok)["n_pairs"] == 1
    for bad in (
        [{"ph": "E", "name": "s", "tid": 1, "ts": 1.0}],  # E without B
        [{"ph": "B", "name": "s", "tid": 1, "ts": 1.0}],  # unclosed
        [  # not monotonic
            {"ph": "B", "name": "s", "tid": 1, "ts": 2.0},
            {"ph": "E", "name": "s", "tid": 1, "ts": 1.0},
        ],
        [  # mismatched names
            {"ph": "B", "name": "s", "tid": 1, "ts": 1.0},
            {"ph": "E", "name": "t", "tid": 1, "ts": 2.0},
        ],
    ):
        with pytest.raises(ValueError):
            obs.validate_chrome_trace({"traceEvents": bad})


def test_metrics_rows_shape():
    buf = obs.TraceBuffer("t8")
    with obs.tracing(buf):
        with obs.span("executor.solve", cat="executor"):
            pass
        obs.counter_add("cache.hit", 4)
    rows = obs.metrics_rows(buf)
    by_name = {name: (val, derived) for name, val, derived in rows}
    assert "obs.executor.solve" in by_name
    assert by_name["obs.counter.cache.hit"] == (4.0, "counter")


# ----------------------------------------------------------- end to end
def test_plan_solve_spans_all_layers():
    L = _matrix()
    rng = np.random.default_rng(0)
    b = rng.standard_normal(L.n_rows).astype(np.float32)
    buf = obs.TraceBuffer("e2e")
    with obs.tracing(buf):
        solver = TriangularSolver.plan(
            L, strategy="auto", cache=PlanCache(), timed=True
        )
        x, steps = solver.solve_timed(b)
    cats = {r.cat for r in buf.spans()}
    assert {"inspector", "autotune", "cache", "backend", "executor"} <= cats
    assert buf.counters().get("cache.miss") == 1
    assert steps and all(s["us"] >= 0 for s in steps)
    assert solver.last_step_timings == steps
    # timed path returns the same solution as the untimed one
    solver.timed = False
    np.testing.assert_allclose(
        np.asarray(x), np.asarray(solver.solve(b)), rtol=1e-5, atol=1e-5
    )


def test_cache_hit_counter_and_timed_toggle():
    L = _matrix()
    cache = PlanCache()
    buf = obs.TraceBuffer("hits")
    with obs.tracing(buf):
        s1 = TriangularSolver.plan(L, strategy="growlocal", cache=cache)
        s2 = TriangularSolver.plan(
            L, strategy="growlocal", cache=cache, timed=True
        )
    assert buf.counters()["cache.miss"] == 1
    assert buf.counters()["cache.hit"] == 1
    # timed is a mutable observability toggle, not part of plan identity:
    # the hit returns the SAME cached solver with the toggle flipped
    assert s2 is s1 and s2.timed
    assert s2.info()["timed"]


def test_elastic_runtime_certificate():
    L = _matrix(n=200, seed=9)
    rng = np.random.default_rng(1)
    b = rng.standard_normal(L.n_rows).astype(np.float32)
    solver = TriangularSolver.plan(
        L, strategy="growlocal", mode="elastic", slack=4, timed=True
    )
    before = solver.info()["binding"]["runtime"]
    assert before["timed_solves"] == 0
    x, steps = solver.solve_timed(b)
    np.testing.assert_allclose(
        np.asarray(x),
        np.asarray(
            TriangularSolver.plan(L, strategy="growlocal").solve(b)
        ),
        rtol=1e-4, atol=1e-4,
    )
    rt = solver.info()["binding"]["runtime"]
    assert rt["timed_solves"] == 1
    assert rt["macro_steps_executed"] == len(steps)
    assert rt["macro_steps_per_solve"] == rt["predicted_macro_steps"]
    assert rt["predicted_barrier_fusion"] >= 1.0
    assert all(s["n_steps"] >= 1 and s["us"] >= 0 for s in steps)


def test_obs_summary_merges_into_service_stats():
    from repro.serve import SolveService

    L = _matrix(n=120, seed=3)
    buf = obs.TraceBuffer("svc")
    with obs.tracing(buf):
        with SolveService(max_batch=4, strategy="growlocal") as svc:
            h = svc.register(L)
            rng = np.random.default_rng(0)
            t = svc.submit(h, rng.standard_normal(L.n_rows).astype(np.float32))
            t.result()
            stats = svc.stats()
    assert stats["obs"]["enabled"]
    assert "serve.microbatch" in stats["obs"]["spans"]
    # disabled: the section degrades to a single flag, never raises
    assert obs.summary() == {"enabled": False}


# ----------------------------------------- satellite: reservoir threading
def test_latency_reservoir_threaded():
    """Regression: unlocked deque iteration during concurrent append
    past maxlen raised RuntimeError('deque mutated during iteration')."""
    res = LatencyReservoir(cap=256)
    stop = threading.Event()
    errors = []

    def writer():
        i = 0
        while not stop.is_set():
            res.add(i * 1e-6)
            i += 1

    def reader():
        while not stop.is_set():
            try:
                p = res.percentiles_us()
                assert set(p) == {"p50", "p95", "p99", "p99.9"}
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return

    threads = [threading.Thread(target=writer) for _ in range(4)] + [
        threading.Thread(target=reader) for _ in range(4)
    ]
    for t in threads:
        t.start()
    import time

    time.sleep(0.5)
    stop.set()
    for t in threads:
        t.join()
    assert not errors, f"reservoir raced: {errors[0]!r}"
    assert res.count > 0 and len(res.samples()) <= 256


# ------------------------------------------- the profiler's clock (bridge)
class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs enter/exit."""

    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


def test_bridge_opens_annotation_only_while_enabled(monkeypatch):
    from repro.obs import trace

    monkeypatch.setattr(trace, "_ANNOTATION", _FakeAnnotation)
    _FakeAnnotation.log = []
    assert obs.span("off.probe", k=1) is obs.NULL_SPAN
    with obs.span("off.probe"):
        pass
    assert _FakeAnnotation.log == []
    buf = obs.TraceBuffer("bridge")
    with obs.tracing(buf):
        with obs.span("outer.probe", cat="x", n=3):
            with obs.span("inner.probe"):
                pass
    # args stay off the annotation's name; nesting is kept
    assert _FakeAnnotation.log == [
        ("enter", "outer.probe"), ("enter", "inner.probe"),
        ("exit", "inner.probe"), ("exit", "outer.probe"),
    ]
    assert [r.name for r in buf.spans()] == ["inner.probe", "outer.probe"]
    _FakeAnnotation.log = []
    assert obs.span("off.again") is obs.NULL_SPAN
    assert _FakeAnnotation.log == []


def test_spans_land_in_the_profiler_record(tmp_path):
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.tracing(obs.TraceBuffer("profiler")):
            with obs.span("obs.bridge_probe", n=1):
                jax.numpy.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    names = {
        e.name
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
    }
    assert "obs.bridge_probe" in names


# -------------------------------------------------------- GC pauses as spans
def test_gc_collection_recorded_as_span():
    from repro.obs import trace

    buf = obs.TraceBuffer("gc")
    with obs.tracing(buf):
        assert trace._on_gc in gc.callbacks
        gc.collect()
    assert trace._on_gc not in gc.callbacks  # unregistered with tracing
    pauses = [r for r in buf.spans() if r.name == "host.gc"]
    assert pauses, "a forced collection left no host.gc span"
    rec = pauses[-1]
    assert rec.cat == "host" and rec.t1_ns >= rec.t0_ns
    assert rec.args["generation"] == 2
    assert isinstance(rec.args["collected"], int)
    n = len(buf)
    gc.collect()  # tracing off: nothing more recorded
    assert len(buf) == n


def test_add_record_off_and_on():
    buf = obs.TraceBuffer("records")
    obs.add_record("serve.request", 1.0, 2.0, id=1)  # off: dropped
    with obs.tracing(buf):
        obs.add_record("serve.request", 1.0, 2.5, id=7, queue_s=0.5)
    (rec,) = buf.spans()
    assert (rec.name, rec.cat, rec.tid) == ("serve.request", "serve", 0)
    assert (rec.t0_ns, rec.t1_ns) == (1_000_000_000, 2_500_000_000)
    assert rec.args == {"id": 7, "queue_s": 0.5}


# ------------------------------------------------------- inspector phases
@pytest.mark.parametrize("strategy", ["growlocal", "auto"])
def test_inspector_schedule_span_pinned_and_auto(strategy):
    L = _matrix(n=140, seed=11)
    buf = obs.TraceBuffer(f"sched.{strategy}")
    with obs.tracing(buf):
        solver = TriangularSolver.plan(L, strategy=strategy)
    sched = [r for r in buf.spans() if r.name == "inspector.schedule"]
    assert len(sched) == 1
    assert sched[0].args["strategy"] == strategy
    if strategy == "auto":
        assert sched[0].args["picked"] == solver.strategy
    assert not [r for r in buf.spans()
                if r.name.startswith("inspector.schedule.")]
    (bind,) = [r for r in buf.spans() if r.name == "backend.bind"]
    assert bind.args["backend"] == "scan"


def test_bind_span_covers_the_elastic_certificate():
    L = _matrix(n=160, seed=13)
    buf = obs.TraceBuffer("bind")
    with obs.tracing(buf):
        solver = TriangularSolver.plan(L, strategy="growlocal", slack=4)
    (bind,) = [r for r in buf.spans() if r.name == "backend.bind"]
    assert bind.args["slack"] == 4
    assert solver.exec_plan.elastic is not None
    assert solver.exec_plan.elastic.slack == 4


def test_solve_span_is_executor_dispatch():
    L = _matrix(n=130, seed=17)
    solver = TriangularSolver.plan(L, strategy="growlocal")
    b = np.ones(L.n_rows, np.float32)
    buf = obs.TraceBuffer("dispatch")
    with obs.tracing(buf):
        solver.solve(b)
    assert [r.name for r in buf.spans()] == ["executor.dispatch"]


# ------------------------------------------------ compile counter (traces)
def test_jit_trace_counter_counts_only_retraces():
    L = _matrix(n=151, seed=19)  # a size no other test solves
    solver = TriangularSolver.plan(L, strategy="growlocal")
    B = np.ones((L.n_rows, 7), np.float32)
    buf = obs.TraceBuffer("jit")
    with obs.tracing(buf):
        solver.solve(B).block_until_ready()
        first = buf.counters().get("jit.trace.scan_mrhs", 0)
        solver.solve(B).block_until_ready()
        again = buf.counters().get("jit.trace.scan_mrhs", 0) - first
    assert (first, again) == (1, 0)
