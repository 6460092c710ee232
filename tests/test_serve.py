"""``repro.serve`` — correctness under concurrency.

The acceptance bars this file enforces:

  * with >= 8 client threads over mixed patterns, every served result is
    bitwise-identical to a direct ``TriangularSolver.solve`` call at the
    dispatched batch width (``direct_reference``);
  * interleaved ``numeric_update``s never corrupt or drop queued
    requests — each request is served by the plan version it was
    admitted under (version pinning);
  * the neighbor-independence property the bitwise contract rests on: at
    a fixed (batch width, column position), a column's bits depend only
    on its own right-hand side, never on what the other columns hold.

Matrices here are deliberately small (n ~ 100–200) so plan+compile stays
in tier-1 budget; the corpus-scale serving run is CI's serve smoke
(``benchmarks/serve_load.py --smoke``).
"""
import threading
import time

import numpy as np
import pytest

from repro.pipeline import PlanCache, TriangularSolver
from repro.serve import (
    MicroBatcher,
    QueueFullError,
    SolveService,
    VersionedPlans,
    direct_reference,
    make_sampler,
    mix_weights,
    pad_width,
    run_closed_loop,
    run_open_loop,
)
from repro.sparse.generators import erdos_renyi_lower, narrow_band_lower

STRATEGY = "growlocal"  # fixed: keeps plan() cheap and deterministic


@pytest.fixture(scope="module")
def mats():
    return [
        erdos_renyi_lower(120, 0.03, seed=21),
        narrow_band_lower(160, 0.1, 6, seed=22),
        erdos_renyi_lower(200, 0.02, seed=23),
    ]


@pytest.fixture()
def service():
    svc = SolveService(
        max_batch=8, max_wait_us=3000, strategy=STRATEGY
    )
    yield svc
    svc.close()


# ----------------------------------------------------------- unit: batcher
def test_pad_width_policy():
    assert [pad_width(m, 8) for m in (1, 2, 3, 4, 5, 8)] == [2, 2, 4, 4, 8, 8]
    # a non-pow2 cap quantizes DOWN: dispatching width 12 would break the
    # documented log2(max_batch) compiled-variant bound
    assert pad_width(9, 12) == 8
    assert pad_width(1, 1) == 1  # baseline escape hatch
    assert pad_width(5, 1) == 1


def test_batcher_coalesces_and_splits():
    b = MicroBatcher(max_batch=3, max_wait_us=10_000_000)
    assert b.max_batch == 2  # non-pow2 caps quantize down (pad_width bound)
    for i in range(7):
        b.put("r", i)
    assert b.depth() == 7
    assert b.next_batch() == ("r", [0, 1])  # full group, no wait
    assert b.next_batch() == ("r", [2, 3])
    assert b.next_batch() == ("r", [4, 5])
    b.close()  # flush: the remainder comes out without its deadline
    assert b.next_batch() == ("r", [6])
    assert b.next_batch() is None
    with pytest.raises(RuntimeError):
        b.put("r", 8)


def test_batcher_deadline_dispatches_partial_group():
    b = MicroBatcher(max_batch=64, max_wait_us=20_000)
    t0 = time.perf_counter()
    b.put("r", "x")
    route, items = b.next_batch()
    waited = time.perf_counter() - t0
    assert (route, items) == ("r", ["x"])
    assert waited >= 0.015  # held for ~max_wait, not dispatched eagerly
    b.close()
    assert b.next_batch() is None


def test_batcher_routes_are_isolated():
    b = MicroBatcher(max_batch=2, max_wait_us=10_000_000)
    b.put(("fp1", 0), "a")
    b.put(("fp2", 0), "b")
    b.put(("fp1", 0), "c")
    assert b.next_batch() == (("fp1", 0), ["a", "c"])  # full first
    b.close()
    assert b.next_batch() == (("fp2", 0), ["b"])


# ------------------------------------------- the bitwise contract's bedrock
def test_neighbor_independence_at_fixed_width_and_position(mats):
    """At a fixed (batch width, column position), a column's bits depend
    only on its own b — neighbor contents never matter. This is the
    property that makes coalescing bit-transparent. (Across widths or
    positions XLA may vectorize the batched einsum differently, so the
    contract deliberately fixes both.)"""
    rng = np.random.default_rng(0)
    for L in mats:
        solver = TriangularSolver.plan(L, strategy=STRATEGY)
        n = L.n_rows
        b = rng.standard_normal(n).astype(np.float32)
        for w in (2, 4, 8):
            for pos in (0, w // 2, w - 1):
                ref = direct_reference(solver, b, w, pos)
                for _ in range(2):
                    B = rng.standard_normal((n, w)).astype(np.float32)
                    B[:, pos] = b
                    got = np.asarray(solver.solve(B))[:, pos]
                    assert np.array_equal(got, ref), (n, w, pos)


# --------------------------------------------------------- service basics
def test_submit_by_matrix_then_fingerprint(service, mats):
    L = mats[0]
    rng = np.random.default_rng(1)
    b = rng.standard_normal(L.n_rows)
    t1 = service.submit(L, b)  # auto-registers
    x1 = t1.result(60)
    fp = t1.fingerprint
    x2 = service.solve(fp, b, timeout=60)  # cheap-handle fast path
    solver = service.pattern(fp).solver_for(t1.version)
    assert t1.served_by is solver  # the serving version rides the ticket
    assert np.array_equal(
        x1,
        direct_reference(solver, b, t1.batch_width, t1.batch_position),
    )
    assert np.array_equal(x1, x2)  # lone requests land at (width 2, col 0)


def test_submit_rejects_bad_shapes_and_unknown_fp(service, mats):
    fp = service.register(mats[0])
    n = mats[0].n_rows
    with pytest.raises(ValueError, match="one right-hand side"):
        service.submit(fp, np.ones((n, 2)))
    with pytest.raises(ValueError, match="one right-hand side"):
        service.submit(fp, np.ones(n + 1))
    with pytest.raises(KeyError, match="unknown pattern"):
        service.submit("deadbeef", np.ones(n))


def test_matrix_resubmission_with_new_values_is_implicit_update(
    service, mats
):
    L = mats[0]
    fp = service.register(L)
    assert service.pattern(fp).current == 0
    import dataclasses

    L2 = dataclasses.replace(L, data=L.data * 2.0)
    t = service.submit(L2, np.ones(L.n_rows))
    assert t.version == 1  # pinned to the freshly installed version
    x = t.result(60)
    solver = service.pattern(fp).solver_for(1)
    assert np.array_equal(
        x,
        direct_reference(
            solver, np.ones(L.n_rows), t.batch_width, t.batch_position
        ),
    )
    # resubmitting the same values is NOT another update
    service.solve(L2, np.ones(L.n_rows), timeout=60)
    assert service.pattern(fp).current == 1


def test_register_orientation_mismatch_rejected(service):
    """A diagonal-only matrix passes both orientation checks, so only the
    service's own guard prevents silently re-using a lower=True plan for
    an upper solve."""
    import repro.autotune as at

    d = at.independent_lower(40, seed=9)
    fp = service.register(d, lower=True)
    with pytest.raises(ValueError, match="registered with lower=True"):
        service.register(d, lower=False)
    with pytest.raises(ValueError, match="registered with lower=True"):
        service.submit(d, np.ones(40), lower=False)
    # the fingerprint fast path cross-checks an explicit orientation too
    with pytest.raises(ValueError, match="registered with lower=True"):
        service.submit(fp, np.ones(40), lower=False)
    service.solve(fp, np.ones(40), timeout=60)  # omitted lower: fine
    assert service.pattern(fp).lower is True


def test_close_releases_cache_pins(mats):
    cache = PlanCache(maxsize=2)
    with SolveService(strategy=STRATEGY, cache=cache) as svc:
        for L in mats:
            svc.register(L)
        assert len(cache.pinned) == len(mats)
    assert len(cache.pinned) == 0  # close() released every pin
    assert len(cache) <= 2  # ... and the LRU bound re-applies


def test_closed_service_rejects_submissions(mats):
    svc = SolveService(strategy=STRATEGY)
    fp = svc.register(mats[0])
    svc.close()
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit(fp, np.ones(mats[0].n_rows))
    with pytest.raises(RuntimeError, match="closed"):
        svc.register(mats[1])  # would pin a key close() can't release


# ----------------------------------------- acceptance: concurrent clients
def test_concurrent_clients_bitwise_identical(service, mats):
    """>= 8 client threads over mixed patterns: every served result is
    bitwise-identical to the direct solve on its pinned version."""
    fps = [service.register(L) for L in mats]
    ns = {fp: L.n_rows for fp, L in zip(fps, mats)}
    n_clients, per_client = 8, 6
    out = [[] for _ in range(n_clients)]
    seed_rngs = [np.random.default_rng(100 + i) for i in range(n_clients)]

    def client(ci):
        rng = seed_rngs[ci]
        for j in range(per_client):
            fp = fps[(ci + j) % len(fps)]
            b = rng.standard_normal(ns[fp]).astype(np.float32)
            t = service.submit(fp, b)
            out[ci].append((t, b, t.result(60)))

    threads = [
        threading.Thread(target=client, args=(i,)) for i in range(n_clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    served = [s for c in out for s in c]
    assert len(served) == n_clients * per_client
    for ticket, b, x in served:
        solver = service.pattern(ticket.fingerprint).solver_for(
            ticket.version
        )
        assert np.array_equal(
            x,
            direct_reference(
                solver, b, ticket.batch_width, ticket.batch_position
            ),
        ), (ticket.fingerprint, ticket.batch_width, ticket.batch_position)
    snap = service.stats()
    assert snap["completed"] == len(served) and snap["failed"] == 0
    assert snap["queue_depth"] == 0


def test_microbatching_actually_coalesces(mats):
    """A burst of same-pattern submissions rides few multi-RHS solves,
    not one solve per request (long max_wait so the test is not timing
    sensitive)."""
    with SolveService(
        max_batch=8, max_wait_us=300_000, strategy=STRATEGY
    ) as svc:
        fp = svc.register(mats[0])
        rng = np.random.default_rng(2)
        n = mats[0].n_rows
        tickets = [
            svc.submit(fp, rng.standard_normal(n)) for _ in range(8)
        ]
        for t in tickets:
            t.result(60)
        snap = svc.stats()
    assert snap["batches"] < len(tickets)
    assert max(int(k) for k in snap["batch_size_hist"]) >= 2
    assert snap["mean_batch_size"] > 1


# ------------------------------------- acceptance: live numeric updates
def test_version_pinning_across_interleaved_updates(mats):
    """Requests admitted before a numeric_update are served with the old
    values; requests admitted after see the new ones — bitwise, and with
    nothing dropped. A long max_wait guarantees the v0 requests are
    still queued when the update lands (real interleaving)."""
    L = mats[1]
    n = L.n_rows
    rng = np.random.default_rng(3)
    with SolveService(
        max_batch=64, max_wait_us=150_000, strategy=STRATEGY
    ) as svc:
        fp = svc.register(L)
        direct = {0: svc.pattern(fp).solver_for(0)}
        admitted = []  # (ticket, b)
        for gen in range(1, 4):  # three value swaps, interleaved
            for _ in range(5):
                b = rng.standard_normal(n).astype(np.float32)
                admitted.append((svc.submit(fp, b), b))
            v = svc.numeric_update(fp, L.data * (1.0 + 0.5 * gen))
            assert v == gen
            direct[v] = svc.pattern(fp).solver_for(v)
        for _ in range(5):  # tail batch on the final version
            b = rng.standard_normal(n).astype(np.float32)
            admitted.append((svc.submit(fp, b), b))
        results = [(t, b, t.result(60)) for t, b in admitted]
    versions = [t.version for t, _, _ in results]
    assert versions == [0] * 5 + [1] * 5 + [2] * 5 + [3] * 5  # pinned
    for t, b, x in results:
        assert np.array_equal(
            x,
            direct_reference(
                direct[t.version], b, t.batch_width, t.batch_position
            ),
        ), f"version {t.version} served with wrong values"


def test_update_unknown_fingerprint_and_missing_data(service, mats):
    fp = service.register(mats[0])
    with pytest.raises(KeyError, match="unknown pattern"):
        service.numeric_update("deadbeef", mats[0].data)
    with pytest.raises(ValueError, match="needs the new values"):
        service.numeric_update(fp)


def test_versions_retire_once_drained(mats):
    with SolveService(
        max_batch=4, max_wait_us=1000, strategy=STRATEGY
    ) as svc:
        fp = svc.register(mats[0])
        n = mats[0].n_rows
        t0 = svc.submit(fp, np.ones(n))
        t0.result(60)
        svc.numeric_update(fp, mats[0].data * 3.0)
        t1 = svc.submit(fp, np.ones(n))
        t1.result(60)
        # v0 has no pins left and was superseded -> retired
        assert svc.pattern(fp).wait_retired(0, timeout=10)
        assert svc.pattern(fp).live_versions() == (1,)
        with pytest.raises(KeyError):
            svc.pattern(fp).solver_for(0)


def test_versioned_plans_unit(mats):
    solver = TriangularSolver.plan(mats[0], strategy=STRATEGY)
    vp = VersionedPlans(solver)
    v, s0 = vp.admit()
    assert (v, s0) == (0, solver)
    v1 = vp.update(mats[0].data * 2.0)
    assert v1 == 1 and vp.live_versions() == (0, 1)  # v0 still pinned
    va, s1 = vp.admit()
    assert va == 1 and s1 is not s0
    assert s0.source_values is not None
    assert np.array_equal(s1.source_values, mats[0].data * 2.0)
    vp.complete(0)
    assert vp.live_versions() == (1,)  # drained + superseded -> gone
    vp.complete(1)


# ------------------------------------------------- cache pins + loadgen
def test_plan_cache_pins_are_eviction_safe(mats):
    cache = PlanCache(maxsize=1)
    s0 = TriangularSolver.plan(mats[0], strategy=STRATEGY, cache=cache)
    cache.pin(s0.plan_key)
    TriangularSolver.plan(mats[1], strategy=STRATEGY, cache=cache)
    TriangularSolver.plan(mats[2], strategy=STRATEGY, cache=cache)
    # the pinned entry survived both insertions; unpinned ones churned
    hits0 = cache.stats.hits
    again = TriangularSolver.plan(mats[0], strategy=STRATEGY, cache=cache)
    assert cache.stats.hits == hits0 + 1 and again is s0
    cache.unpin(s0.plan_key)
    assert len(cache) <= 1  # unpin re-applies the LRU bound


def test_service_pins_registered_plans(mats):
    cache = PlanCache(maxsize=1)
    with SolveService(strategy=STRATEGY, cache=cache) as svc:
        fps = [svc.register(L) for L in mats]
        assert len(set(fps)) == len(mats)
        assert len(cache.pinned) == len(mats)
        misses = cache.stats.misses
        for L in mats:  # all three plans still live despite maxsize=1
            svc.register(L)
        assert cache.stats.misses == misses


def test_loadgen_mixes_and_closed_loop(mats):
    w = mix_weights("hot", 4)
    assert w[0] > w[-1] and abs(w.sum() - 1) < 1e-12
    assert np.allclose(mix_weights("uniform", 4), 0.25)
    with pytest.raises(ValueError, match="unknown mix"):
        mix_weights("nope", 3)
    with SolveService(
        max_batch=8, max_wait_us=2000, strategy=STRATEGY
    ) as svc:
        patterns = [(svc.register(L), L.n_rows) for L in mats]
        sampler = make_sampler(patterns, "hot", seed=5)
        report = run_closed_loop(
            svc, sampler, n_clients=4, requests_per_client=4, validate=True
        )
    assert report["requests"] == 16
    assert report["errors"] == 0
    assert report["bitwise_mismatches"] == 0
    assert report["solves_per_sec"] > 0
    assert set(report["latency_us"]) == {"p50", "p95", "p99", "p99.9"}


def test_loadgen_open_loop(mats):
    with SolveService(
        max_batch=8, max_wait_us=2000, strategy=STRATEGY
    ) as svc:
        patterns = [(svc.register(mats[0]), mats[0].n_rows)]
        sampler = make_sampler(patterns, "uniform", seed=6)
        report = run_open_loop(
            svc, sampler, rate_hz=2000.0, n_requests=12, validate=True
        )
    assert report["requests"] == 12 and report["errors"] == 0
    assert report["bitwise_mismatches"] == 0


# ------------------------------------------------ back-pressure (max_queue)
def test_backpressure_rejects_overflow_keeps_queue_bounded(mats):
    """With a bounded admission queue and a stalled worker (long batch
    deadline, big max_batch), overflow submissions come back rejected
    instead of growing the backlog; the accepted ones still get served
    (close() flushes), bitwise-correct."""
    L = mats[0]
    n = L.n_rows
    rng = np.random.default_rng(11)
    with SolveService(
        max_batch=64, max_wait_us=60_000_000, max_queue=4, strategy=STRATEGY
    ) as svc:
        fp = svc.register(L)
        accepted, rejected = [], []
        for _ in range(10):
            b = rng.standard_normal(n).astype(np.float32)
            t = svc.submit(fp, b)
            (rejected if t.rejected else accepted).append((t, b))
            assert svc._batcher.depth() <= 4  # the bound actually holds
        assert len(accepted) == 4 and len(rejected) == 6
        for t, _ in rejected:
            assert t.done() and t.version == -1
            with pytest.raises(QueueFullError, match="max_queue=4"):
                t.result(1)
        snap = svc.stats()
        assert snap["rejected"] == 6
        assert snap["per_pattern"][fp]["rejected"] == 6
    # close() drained the accepted requests; nothing was dropped
    for t, b in accepted:
        x = t.result(60)
        assert np.array_equal(
            x, direct_reference(t.served_by, b, t.batch_width,
                                t.batch_position)
        )


def test_backpressure_unbounded_by_default_and_validates_bound(mats):
    with pytest.raises(ValueError, match="max_queue"):
        SolveService(max_queue=0)
    with SolveService(
        max_batch=4, max_wait_us=1000, strategy=STRATEGY
    ) as svc:  # no max_queue: nothing rejects
        fp = svc.register(mats[0])
        tickets = [
            svc.submit(fp, np.ones(mats[0].n_rows)) for _ in range(12)
        ]
        for t in tickets:
            assert not t.rejected
            t.result(60)
        assert svc.stats()["rejected"] == 0


def test_open_loop_reports_rejections(mats):
    """Loadgen separates back-pressure rejections from errors: an
    open-loop burst against a tiny bound rejects the overflow and the
    served remainder still validates bitwise."""
    with SolveService(
        max_batch=64, max_wait_us=300_000, max_queue=2, strategy=STRATEGY
    ) as svc:
        patterns = [(svc.register(mats[0]), mats[0].n_rows)]
        sampler = make_sampler(patterns, "uniform", seed=13)
        # rate far above the 0.3s batch deadline: all 10 submissions land
        # while the first batch is still held, so everything past the
        # bound must bounce; the held batch then dispatches and validates.
        report = run_open_loop(
            svc, sampler, rate_hz=100_000.0, n_requests=10, validate=True
        )
    assert report["rejected"] == 8  # 2 admitted, 8 bounced
    assert report["errors"] == 0
    assert report["bitwise_mismatches"] == 0
    assert report["completed"] == 2


def test_worker_failure_propagates_to_tickets(mats):
    """A solve blowing up must fail only that batch's tickets, with the
    original exception, and leave the service serving."""
    with SolveService(
        max_batch=4, max_wait_us=1000, strategy=STRATEGY
    ) as svc:
        fp = svc.register(mats[0])
        vp = svc.pattern(fp)
        n = mats[0].n_rows
        boom = RuntimeError("synthetic backend failure")

        class _Exploding:
            def solve(self, B):  # stand-in for the version's solver
                raise boom

        real = vp._versions[vp.current]
        vp._versions[vp.current] = _Exploding()
        try:
            t = svc.submit(fp, np.ones(n))
            with pytest.raises(RuntimeError, match="synthetic backend"):
                t.result(60)
        finally:
            vp._versions[vp.current] = real
        # service still serves after the failure
        x = svc.solve(fp, np.ones(n), timeout=60)
        assert x.shape == (n,)
        snap = svc.stats()
        assert snap["failed"] == 1 and snap["completed"] >= 1


# ------------------------------------- tracing: batch phases and requests
PHASES = ("serve.batch.stack", "serve.batch.dispatch", "serve.batch.wait",
          "serve.batch.fulfil")
BATCH_SPANS = {"microbatch": "serve.microbatch",
               "continuous": "serve.slot_pass"}


def _traced_burst(mats, mode, n_requests=12):
    from repro import obs

    buf = obs.TraceBuffer(f"serve.{mode}")
    rng = np.random.default_rng(5)
    with obs.tracing(buf):
        with SolveService(
            max_batch=4, max_wait_us=20_000, mode=mode, strategy=STRATEGY
        ) as svc:
            fp = svc.register(mats[0])
            tickets = [
                svc.submit(fp, rng.standard_normal(mats[0].n_rows))
                for _ in range(n_requests)
            ]
            for t in tickets:
                t.result(60)
    return buf.spans(), tickets


@pytest.mark.parametrize("mode", ["microbatch", "continuous"])
def test_traced_batches_nest_their_phases(mats, mode):
    spans, tickets = _traced_burst(mats, mode)
    batches = [r for r in spans if r.name == BATCH_SPANS[mode]]
    assert batches
    ridden = {t.batch for t in tickets}
    assert ridden <= {b.args["batch"] for b in batches}
    for b in batches:
        if b.args["batch"] not in ridden:
            continue  # a continuous pass that admitted nothing
        inside = [r for r in spans if r.args.get("batch") == b.args["batch"]
                  and r.name in PHASES]
        assert sorted(r.name for r in inside) == sorted(PHASES)
        for r in inside:
            assert r.tid == b.tid
            assert b.t0_ns <= r.t0_ns <= r.t1_ns <= b.t1_ns
        order = sorted(inside, key=lambda r: r.t0_ns)
        assert [r.name for r in order] == list(PHASES)


def test_traced_requests_one_record_per_ticket(mats):
    spans, tickets = _traced_burst(mats, "microbatch")
    records = [r for r in spans if r.name == "serve.request"]
    assert len(records) == len(tickets)
    by_id = {r.args["id"]: r for r in records}
    assert len(by_id) == len(tickets)  # distinct ids
    assert sorted(by_id) == sorted(t.request_id for t in tickets)
    for t in tickets:
        r = by_id[t.request_id]
        assert r.args["batch"] == t.batch is not None
        assert t.t_submit <= t.t_dispatch <= t.t_done
        assert r.args["queue_s"] == t.t_dispatch - t.t_submit
        assert r.t0_ns == int(t.t_submit * 1e9)
        assert r.t1_ns == int(t.t_done * 1e9)


def test_answers_bitwise_equal_traced_and_untraced(mats):
    """Tracing changes nothing that runs: the same requests, served one
    at a time (so each rides a batch of the same width and column), give
    the same bits with tracing on and off."""
    from repro import obs

    rng = np.random.default_rng(9)
    n = mats[1].n_rows
    rhs = [rng.standard_normal(n) for _ in range(4)]

    def serve():
        with SolveService(max_batch=8, strategy=STRATEGY) as svc:
            fp = svc.register(mats[1])
            return [svc.submit(fp, b).result(60) for b in rhs]

    off = serve()
    with obs.tracing(obs.TraceBuffer("bitwise")):
        on = serve()
    for x_off, x_on in zip(off, on):
        assert np.array_equal(x_off, x_on)
