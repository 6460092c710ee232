"""Open loop: independent clients send one-RHS requests to ``SolveService``
on a fixed schedule, whether or not earlier ones are done.

Traffic keys:

- ``rate_hz``: the offered rate, fixed in the cell (arrivals are
  Poisson-like, see ``bench.loadgen``);
- ``pool``: how many distinct right-hand sides are drawn from the seed
  before the window; each request takes one, in an order the seed draws;
- ``warm_requests``: a burst served during set-up, after ``prewarm``, so
  that the service's host path is warm when the window opens;
- ``sample``: how many of the window's requests, drawn from the seed, are
  compared with the reference once every answer is in;
- ``service``: keyword arguments of ``SolveService`` (empty: its defaults);
- ``plan``: options that override the configuration's ``plan``;
- ``trace_after_s``: with ``--trace 1``, how far into the window the
  profiler starts (default 0, at most half the window). It runs until every
  answer is in and keeps what its buffer holds from its start, so a start
  in the middle of the window reads steady load, not the window's first
  second.

A request's latency runs from when it was due to when its ticket was
fulfilled. A request that is not answered within a minute after the last
one was due, or that fails, counts as failed. The submit-to-dispatch wait
of every request the window serves is taken from the service's metrics as
it records each batch.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from bench import harness, loadgen, reference

GRACE_S = 60.0


class QueueWaits:
    """The submit-to-dispatch waits that ``SolveService`` hands its
    ``ServeMetrics`` with every batch it records, kept while ``active``:
    one per request served, however many there are."""

    RECORDERS = ("record_batch", "record_grouped_batch", "record_slot_pass")

    def __init__(self, metrics):
        self.waits = []
        self.active = False
        for name in self.RECORDERS:
            setattr(metrics, name, self._keep(getattr(metrics, name)))

    def _keep(self, record):
        def recorder(*args, **kwargs):
            if self.active:
                self.waits.extend(kwargs["queue_waits"])
            return record(*args, **kwargs)

        return recorder


class TracedSlice:
    """With ``--trace 1``, the profiler from ``after_s`` into the window
    until ``close``, with a ``bench.window`` span over it, all on a thread
    of its own so that the load's schedule does not wait for it."""

    def __init__(self, tracer, after_s: float):
        self.tracer = tracer
        self.closing = threading.Event()
        self.thread = threading.Thread(target=self._run, args=(after_s,),
                                       daemon=True)
        self.thread.start()

    def _run(self, after_s):
        self.closing.wait(after_s)
        self.tracer.start()
        with harness.annotate("bench.window"):
            self.closing.wait()

    def close(self):
        self.closing.set()
        self.thread.join()
        self.tracer.stop()


class Server:
    """``SolveService`` with the configuration's one pattern registered,
    compiled and warm."""

    def __init__(self, ctx):
        from repro.serve import SolveService

        self.ctx = ctx
        args, traffic = ctx.args, ctx.traffic
        self.ops = ctx.generator.operators(ctx.config["matrix"],
                                           [int(args.seed), 7])
        if len(self.ops) != 1:
            raise ValueError("the open loop serves one triangular operator; "
                             f"the configuration has {len(self.ops)}")
        (a, lower), = self.ops
        self.svc = SolveService(**traffic.get("service", {}))
        self.queue_waits = QueueWaits(self.svc.metrics)
        t = time.perf_counter()
        with harness.annotate("bench.plan"):
            self.fp = self.svc.register(
                harness.program_csr(a), lower=lower,
                **harness.plan_options(ctx.config, traffic))
        self.inspector_s = time.perf_counter() - t
        # every batch width the service can dispatch; the second prewarm
        # runs the same programs again, warm
        t = time.perf_counter()
        self.svc.prewarm()
        first = time.perf_counter() - t
        t = time.perf_counter()
        self.svc.prewarm()
        self.compile_s = max(first - (time.perf_counter() - t), 0.0)
        self.pool = harness.rhs_pool(args.seed, int(traffic["pool"]),
                                     a.shape[0])
        warm = [self.svc.submit(self.fp, self.pool[i % len(self.pool)])
                for i in range(int(traffic["warm_requests"]))]
        for ticket in warm:
            ticket.result(GRACE_S)

    def close(self):
        return self.svc.close(timeout=GRACE_S)

    def window(self, rate_hz: float, seconds: float, seed: int,
               traced=None) -> dict:
        """Offer ``rate_hz`` for ``seconds``; wait for every answer.
        ``traced``: a ``TracedSlice`` factory, called as the window opens."""
        due = loadgen.arrival_offsets(rate_hz, seconds, harness.rng(seed, 3))
        which = harness.rng(seed, 4).integers(0, len(self.pool),
                                              size=len(due))
        payloads = [self.pool[k] for k in which]
        self.queue_waits.waits.clear()
        self.queue_waits.active = True
        traced = traced() if traced is not None else None
        t0, tickets, late = loadgen.run_open_loop(
            lambda b: self.svc.submit(self.fp, b), due, payloads,
            annotate=harness.annotate)
        deadline = t0 + due[-1] + GRACE_S
        answered = []
        for i, ticket in enumerate(tickets):
            with harness.annotate("bench.result"):
                try:
                    ticket.result(max(deadline - time.perf_counter(), 0))
                except Exception:  # failed, refused or never answered
                    continue
            answered.append(i)
        if traced is not None:
            traced.close()
        self.queue_waits.active = False
        done = [tickets[i] for i in answered]
        columns = sum(tk.batch_width for tk in done if tk.batch_position == 0)
        return {
            "wall_s": float(due[-1]),
            "requests": len(tickets),
            "latencies_s": [tickets[i].t_done - (t0 + due[i])
                            for i in answered],
            "late_mean_s": float(np.mean(late)),
            "late_max_s": float(np.max(late)),
            "failed": len(tickets) - len(answered),
            "batch_fill": len(done) / columns if columns else None,
            "queue_wait_s": list(self.queue_waits.waits),
            "queue_waits": len(self.queue_waits.waits),
            "answered": answered,
            "tickets": tickets,
            "payloads": payloads,
        }

    def check(self, w: dict, sample: int, seed: int) -> tuple:
        """The widest relative error over a sample of answered requests,
        drawn from the seed; and how many were compared."""
        answered = w["answered"]
        pick = harness.rng(seed, 2).permutation(len(answered))[:sample]
        pick = sorted(answered[k] for k in pick)
        if not pick:
            return float("inf"), 0
        ref = harness.references(self.ops)[0]
        got = np.stack([w["tickets"][i].result(0) for i in pick], axis=1)
        want = ref.solve(np.stack([w["payloads"][i] for i in pick], axis=1))
        return reference.rel_err(got, want), len(pick)


def run(ctx) -> dict:
    args, traffic = ctx.args, ctx.traffic
    server = Server(ctx)
    try:
        setup_s = time.perf_counter() - ctx.t_start
        tracer = harness.Tracer() if args.trace else None
        after_s = min(float(traffic.get("trace_after_s", 0.0)),
                      args.seconds / 2)
        traced = (lambda: TracedSlice(tracer, after_s)) if args.trace else None
        ctx.compiles.active = True
        w = server.window(float(traffic["rate_hz"]), args.seconds, args.seed,
                          traced)
        ctx.compiles.active = False
        peak = harness.memory_peak_bytes(ctx.devices)
        err, compared = server.check(w, int(traffic["sample"]), args.seed)
        solver = server.svc.pattern(server.fp).current_solver()
        rec = {
            "driver": "open_loop",
            "setup_s": setup_s,
            "inspector_s": server.inspector_s,
            "compile_s": server.compile_s,
            "backends": [solver.backend],
            "attempted": w["requests"],
            "failed": w["failed"],
            "memory_peak_bytes": peak,
            "window": {k: w[k] for k in ("wall_s", "requests", "latencies_s",
                                         "late_mean_s", "late_max_s",
                                         "queue_waits")},
            "serve": {"batch_fill": w["batch_fill"],
                      "queue_wait_s": w["queue_wait_s"]},
            "checks": {"rel_err": err, "answers_compared": compared},
            "plans": [solver.exec_plan.stats() | {
                "strategy": solver.strategy, "mode": solver.info()["mode"]}],
        }
        if tracer is not None:
            rec["trace"] = tracer.reduce() | {"solve_only": False}
        return rec
    finally:
        server.close()
