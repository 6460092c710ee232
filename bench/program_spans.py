"""The program's own spans (``repro.obs``), read by the ``program_span``
metrics.

A per-layer reader that reads them calls ``switch_on()`` as it is loaded.
The benchmark loads per-layer readers only for a ``--trace 1`` run, before
set-up, so the program's tracing covers the set-up and the whole window of
a traced run and stays off in an untraced one. The program records into a
buffer of its own here, on the host's perf-counter clock; each span also
lands in the profiler's record of the traced slice, where the program puts
it there.

Once the run is over, the first reader's ``section(rec)`` turns the buffer
into the record's ``obs`` section, prints its totals on standard error and
switches tracing off; later readers read the same section:

- ``window_start``: where the window's own work begins (perf-counter
  seconds): in the open loop, the submit of the window's first request
  (its requests are the last ``attempted`` request ids); in the closed
  loop, the start of the window's first ``executor.dispatch`` (its calls
  make the last ``window.solves`` of them);
- ``setup`` and ``window``: seconds by span name before and from the
  window start: the union of a name's intervals on each thread, so that a
  span nested in one of the same name counts once;
- ``batches``: for each batch the window's requests rode, the host
  seconds of its ``serve.batch.stack``, ``dispatch`` and ``fulfil``;
- ``requests``: for each of the window's requests, ``[queue_s,
  service_s]``: submit to dispatch, and dispatch to done;
- ``counters``: the program's counters over the run (``jit.trace.*``
  among them: how often JAX traced each solve body, set-up included).

Where the program records none of these spans or records, the section
holds nothing for them, and the readers report nothing.
"""
from __future__ import annotations

import json
import sys

import numpy as np

HOST_PHASES = ("serve.batch.stack", "serve.batch.dispatch",
               "serve.batch.fulfil")

_buffer = None  # the program's TraceBuffer from switch_on to section


def switch_on() -> None:
    """Turn the program's tracing on, into a buffer of its own (once)."""
    global _buffer
    if _buffer is not None:
        return
    try:
        from repro import obs
    except ImportError:
        return
    _buffer = obs.TraceBuffer("bench")
    obs.enable(_buffer)


def section(rec: dict):
    """The record's ``obs`` section: ``rec["obs"]`` where it has one; for
    a run's record (one with a ``driver``) made while tracing was on, the
    section built from what the program recorded; else None."""
    if "obs" in rec:
        return rec["obs"]
    global _buffer
    if _buffer is None or "driver" not in rec:
        return None
    from repro import obs

    obs.disable()
    buf, _buffer = _buffer, None  # the next run's readers start afresh
    rec["obs"] = build(rec, buf.spans(), buf.counters())
    print(json.dumps({"program_obs": summary(rec["obs"], buf)}),
          file=sys.stderr)
    return rec["obs"]


def total(rec: dict, part: str, name: str):
    """Seconds of span ``name`` in ``part`` (``setup`` or ``window``)."""
    sec = section(rec)
    if sec is None:
        return None
    return sec[part].get(name)


def build(rec: dict, spans, counters) -> dict:
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    requests = _window_requests(rec, by_name.get("serve.request", []))
    start = window_start(rec, requests, by_name.get("executor.dispatch", []))
    setup, window = {}, {}
    for name, group in by_name.items():
        before, after = _union_split(group, start)
        if before > 0:
            setup[name] = before
        if after > 0:
            window[name] = after
    ridden = {r.args["batch"] for r in requests}
    host = {}
    for name in HOST_PHASES:
        for s in by_name.get(name, []):
            b = s.args.get("batch")
            if b in ridden:
                host[b] = host.get(b, 0.0) + (s.t1_ns - s.t0_ns) * 1e-9
    return {
        "window_start": start,
        "setup": setup,
        "window": window,
        "batches": [host[b] for b in sorted(host)],
        "requests": [[r.args["queue_s"],
                      (r.t1_ns - r.t0_ns) * 1e-9 - r.args["queue_s"]]
                     for r in requests],
        "counters": dict(counters),
    }


def window_start(rec: dict, requests, dispatches):
    """Perf-counter seconds at which the window's own work begins, or
    None where the program recorded none of it."""
    if rec.get("driver") == "open_loop":
        if not requests:
            return None
        return min(r.t0_ns for r in requests) * 1e-9
    solves = int(rec.get("window", {}).get("solves", 0))
    if not solves or len(dispatches) < solves:
        return None
    return sorted(s.t0_ns for s in dispatches)[-solves] * 1e-9


def _window_requests(rec: dict, records) -> list:
    """The ``serve.request`` records of the window's requests: the last
    ``attempted`` request ids (a request that failed has no record)."""
    if rec.get("driver") != "open_loop" or not records:
        return []
    last = max(r.args["id"] for r in records)
    first = last - int(rec["attempted"]) + 1
    return sorted((r for r in records if r.args["id"] >= first),
                  key=lambda r: r.args["id"])


def _union_split(group, start):
    """Seconds of the union of ``group``'s intervals on each thread,
    before and from ``start`` (all before where ``start`` is None)."""
    cut = np.inf if start is None else start * 1e9
    before = after = 0.0
    by_tid = {}
    for s in group:
        by_tid.setdefault(s.tid, []).append((s.t0_ns, s.t1_ns))
    for iv in by_tid.values():
        iv = np.asarray(sorted(iv), np.float64)
        lo, hi = iv[:, 0], np.maximum.accumulate(iv[:, 1])
        new = np.ones(len(iv), bool)
        new[1:] = lo[1:] > hi[:-1]
        first = np.flatnonzero(new)
        last = np.append(first[1:] - 1, len(iv) - 1)
        s, e = lo[first], hi[last]
        before += float((np.minimum(e, cut) - np.minimum(s, cut)).sum())
        if start is not None:
            after += float((np.maximum(e, cut) - np.maximum(s, cut)).sum())
    return before * 1e-9, after * 1e-9


def summary(sec: dict, buf) -> dict:
    """The section's totals, for standard error: seconds by span name,
    the ``jit.trace.*`` counts, the collections in the window and the
    spans the buffer dropped."""
    gc_window = [s for s in buf.spans() if s.name == "host.gc"
                 and sec["window_start"] is not None
                 and s.t0_ns * 1e-9 >= sec["window_start"]]
    pauses = [(s.t1_ns - s.t0_ns) * 1e-9 for s in gc_window]
    return {
        "setup_s": sec["setup"],
        "window_s": sec["window"],
        "jit_traces_in_run": {k: v for k, v in sec["counters"].items()
                              if k.startswith("jit.trace.")},
        "gc_in_window": {"count": len(pauses),
                         "max_s": max(pauses, default=0.0),
                         "total_s": sum(pauses)},
        "batches": len(sec["batches"]),
        "requests": len(sec["requests"]),
        "dropped": buf.dropped,
    }
