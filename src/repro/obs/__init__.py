"""``repro.obs`` — cross-cutting observability: tracing, counters,
exportable telemetry.

The paper's headline numbers are *accounting* claims (12.07x fewer
barriers, balanced per-step work); ``ExecPlan.stats()`` reports them
statically. This package measures where wall-clock actually goes at
runtime, across every layer of the stack:

    inspector   compile_plan phases, DAG build, schedule, reorder
    autotune    feature extraction, candidate scoring, measured trials
    cache       PlanCache hit/miss/evict/pin counters + lookup spans
    backend     bind (elastic transform and device puts included) /
                update_values per backend
    executor    per-solve dispatch (``executor.dispatch``: the enqueue,
                not the device time); per-superstep (bulk) and
                per-macro-step (elastic) device timings on a
                ``timed=True`` plan; ``jit.trace.<program>`` counters,
                bumped each time JAX traces a solve body
    serve       one span per batch (microbatch, grouped batch, slot
                pass) with its phases ``serve.batch.stack`` /
                ``dispatch`` / ``wait`` / ``fulfil``, and one
                ``serve.request`` record per request (submit -> done)
    host        ``host.gc``: each garbage collection while enabled

Usage::

    from repro import obs

    obs.enable()                      # or: with obs.tracing(): ...
    solver = TriangularSolver.plan(L, strategy="auto", cache=cache)
    x = solver.solve(b)
    obs.export_chrome_trace("trace.json")   # chrome://tracing / Perfetto
    print(obs.summary())                    # per-span aggregate + counters

Tracing is OFF by default and costs one flag check per instrumentation
site when off (no allocation — ``span()`` returns a process-wide
singleton; bounded ~0.5% on the corpus hot path, enforced by
``benchmarks/obs_overhead.py``). Enabled tracing stays on the host side
of the JAX async dispatch boundary, bounded <= 3% median solve latency
on the same bench. Every enabled span is also a
``jax.profiler.TraceAnnotation`` of the same name, so a running JAX
profiler records it on the clock of the device events; and
``jax.named_scope`` annotations inside the executors tag the XLA HLO, so
a ``jax.profiler`` trace carries plan-step names at zero runtime cost.
"""
from repro.obs.export import (
    TRACE_SCHEMA,
    chrome_trace_events,
    chrome_trace_payload,
    export_chrome_trace,
    load_chrome_trace,
    metrics_rows,
    validate_chrome_trace,
)
from repro.obs.trace import (
    COUNTER_WRAP,
    DEFAULT_CAP,
    NULL_SPAN,
    Span,
    SpanRecord,
    TraceBuffer,
    active_buffer,
    add_record,
    counter_add,
    disable,
    enable,
    get_buffer,
    is_enabled,
    span,
    tracing,
)


def summary(buffer=None) -> dict:
    """JSON-ready aggregate of the active (or given) buffer — the dict
    ``SolveService.stats()["obs"]`` embeds."""
    buf = buffer if buffer is not None else active_buffer()
    if buf is None:
        return {"enabled": False}
    return {"enabled": is_enabled(), **buf.summary()}


__all__ = [
    "COUNTER_WRAP",
    "DEFAULT_CAP",
    "NULL_SPAN",
    "Span",
    "SpanRecord",
    "TRACE_SCHEMA",
    "TraceBuffer",
    "active_buffer",
    "add_record",
    "chrome_trace_events",
    "chrome_trace_payload",
    "counter_add",
    "disable",
    "enable",
    "export_chrome_trace",
    "get_buffer",
    "is_enabled",
    "load_chrome_trace",
    "metrics_rows",
    "span",
    "summary",
    "tracing",
    "validate_chrome_trace",
]
