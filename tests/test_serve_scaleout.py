"""Serve scale-out: width-class cross-pattern batching, multi-worker
serving, and the lifecycle/metrics hardening that rides with them.

Acceptance bars:

  * structurally-identical patterns (one ``width_class``) coalesce into
    single grouped dispatches, and every grouped result stays bitwise-
    reproducible via its ticket's ``served_by`` replay at the recorded
    (width, position) — including across interleaved ``numeric_update``s
    (versions differ per column inside one batch);
  * the grouped kernel's lane independence: a column's bits depend only
    on its own (plan, rhs), never on neighbor columns' plans or values;
  * ``n_workers > 1`` serves concurrent multi-route traffic bitwise-
    correctly with interleaved updates;
  * ``close(timeout)`` never releases plan-cache pins while a worker is
    still alive (the LRU-eviction-vs-in-flight-batch race);
  * the throughput window survives a batch draining after ``reset()``.
"""
import threading
import time

import numpy as np
import pytest

from repro.pipeline import PlanCache, TriangularSolver, grouped_solve
from repro.serve import (
    GroupReplay,
    ServeMetrics,
    SolveService,
    direct_reference,
    make_sampler,
    normalize_max_batch,
    pad_width,
    run_closed_loop,
    width_class_patterns,
)
from repro.sparse import shifted_coupling_lower
from repro.sparse.generators import erdos_renyi_lower

from _oracle import solver_oracle

STRATEGY = "wavefront"  # level scheduler: shift-invariant plan shapes
N = 96


@pytest.fixture(scope="module")
def family():
    return [shifted_coupling_lower(N, j, seed=40 + j) for j in range(4)]


@pytest.fixture(scope="module")
def family_solvers(family):
    return [TriangularSolver.plan(m, strategy=STRATEGY) for m in family]


# ------------------------------------------------------ width-class identity
def test_family_is_distinct_patterns_one_width_class(family, family_solvers):
    from repro.sparse.csr import pattern_fingerprint

    fps = {pattern_fingerprint(m) for m in family}
    assert len(fps) == len(family)  # structurally distinct...
    assert len({s.width_class for s in family_solvers}) == 1  # ...one class
    assert all(s.supports_grouping for s in family_solvers)


def test_width_class_separates_real_structural_differences(family_solvers):
    other = TriangularSolver.plan(
        erdos_renyi_lower(N, 0.05, seed=77), strategy=STRATEGY
    )
    assert other.width_class != family_solvers[0].width_class
    # a different backend binding is a different class even on equal shapes
    s0 = family_solvers[0]
    interp = TriangularSolver.plan(
        shifted_coupling_lower(N, 0, seed=40),
        strategy=STRATEGY,
        backend="pallas",
        interpret=True,
    )
    assert interp.width_class != s0.width_class


def test_plan_cache_width_class_index(family):
    cache = PlanCache()
    solvers = [
        TriangularSolver.plan(m, strategy=STRATEGY, cache=cache)
        for m in family
    ]
    for s in solvers:
        cache.note_width_class(s.width_class, s.plan_key)
    wc = solvers[0].width_class
    assert cache.width_class_members(wc) == frozenset(
        s.plan_key for s in solvers
    )
    assert cache.width_class_sizes()[wc] == len(family)
    cache.clear()
    assert cache.width_class_sizes() == {}


def test_plan_cache_width_class_index_bounded_by_eviction(family):
    """Index entries leave with their evicted plan — a bounded LRU under
    pattern churn must not accumulate width-class keys forever."""
    cache = PlanCache(maxsize=1)
    for m in family:
        s = TriangularSolver.plan(m, strategy=STRATEGY, cache=cache)
        cache.note_width_class(s.width_class, s.plan_key)
    # one live entry -> at most its one index key survives
    assert sum(cache.width_class_sizes().values()) == 1


# ------------------------------------------------- grouped-kernel contracts
def test_grouped_solve_matches_per_solver_solves(family, family_solvers):
    """Each grouped column solves ITS OWN system: checked against the
    scipy-free dense reference of that column's matrix."""
    from repro.sparse.csr import csr_to_dense

    rng = np.random.default_rng(0)
    B = rng.standard_normal((N, len(family_solvers))).astype(np.float32)
    X = np.asarray(grouped_solve(family_solvers, B))
    for j, (m, s) in enumerate(zip(family, family_solvers)):
        dense = csr_to_dense(m).astype(np.float64)
        ref = np.linalg.solve(dense, B[:, j].astype(np.float64))
        np.testing.assert_allclose(X[:, j], ref, rtol=2e-4, atol=2e-5)


def test_grouped_lane_independence_and_replay(family_solvers):
    """The bedrock of the grouped bitwise contract: at a fixed (width,
    position), a lane's bits depend only on its own (plan, b) — vary the
    neighbor lanes' plans AND values, the lane never moves; replaying
    with the lane's own solver replicated everywhere reproduces it."""
    rng = np.random.default_rng(1)
    b = rng.standard_normal(N).astype(np.float32)
    w = len(family_solvers)
    for pos in (0, w - 1):
        fixed = None
        for trial in range(3):
            order = list(rng.permutation(w))
            solvers = [family_solvers[i] for i in order]
            solvers[pos] = family_solvers[0]
            B = rng.standard_normal((N, w)).astype(np.float32)
            B[:, pos] = b
            col = np.asarray(grouped_solve(solvers, B))[:, pos]
            if fixed is None:
                fixed = col
            assert np.array_equal(col, fixed), (pos, trial)
        replay = direct_reference(GroupReplay(family_solvers[0]), b, w, pos)
        assert np.array_equal(replay, fixed)


@pytest.mark.parametrize("width", [None, 2], ids=["plain", "split"])
def test_group_bank_bitwise_matches_grouped_solve(family, family_solvers,
                                                  width):
    """The serving fast path (device bank, lanes indexed inside the jit)
    must be bitwise-identical to the stack-per-call ``grouped_solve`` —
    that identity is what lets ``GroupReplay`` verify bank-served
    results. Checked across compositions and bank sizes (pow2 lane
    padding means P=4 and P=6-padded-to-8 compile different variants),
    and every column against ``kernels/ref.py``'s oracle of its own
    plan. The family's rows hold one entry off the diagonal, so the
    ``split`` case banks four value sets of one Erdos-Renyi pattern at
    ``width=2`` instead, which splits its wider rows into accum chains."""
    import dataclasses

    from repro.pipeline import GroupBank

    if width is not None:
        a = erdos_renyi_lower(N, 0.08, seed=3)
        vrng = np.random.default_rng(5)
        family_solvers = [
            TriangularSolver.plan(
                dataclasses.replace(a, data=a.data * vrng.uniform(0.5, 2, a.nnz)),
                strategy=STRATEGY, width=width,
            )
            for _ in range(4)
        ]
        assert all(s.exec_plan.accum.any() for s in family_solvers)
        assert len({s.width_class for s in family_solvers}) == 1
    rng = np.random.default_rng(4)
    bank = GroupBank()
    for i, s in enumerate(family_solvers):
        bank.add(i, s)
    assert len(bank) == len(family_solvers)
    for comp in ([0, 1, 2, 3], [3, 3, 0, 2], [1, 0, 1, 0]):
        B = rng.standard_normal((N, len(comp))).astype(np.float32)
        got = np.asarray(bank.solve(comp, B))
        ref = np.asarray(
            grouped_solve([family_solvers[i] for i in comp], B)
        )
        assert np.array_equal(got, ref), comp
        for j, i in enumerate(comp):
            assert np.array_equal(
                got[:, j], solver_oracle(family_solvers[i], B[:, j])
            ), (comp, j)
    # membership churn: drop + prune invalidate and rebuild lazily
    rebuilds = bank.rebuilds
    bank.drop(3)
    bank.prune(lambda k: k != 2)
    assert len(bank) == 2
    B = rng.standard_normal((N, 2)).astype(np.float32)
    got = np.asarray(bank.solve([0, 1], B))
    ref = np.asarray(grouped_solve(family_solvers[:2], B))
    assert np.array_equal(got, ref)
    assert bank.rebuilds == rebuilds + 1
    assert bank.describe() == {"n_lanes": 2, "rebuilds": bank.rebuilds}


def test_group_bank_rejects_wrong_members(family_solvers):
    from repro.pipeline import GroupBank

    bank = GroupBank()
    bank.add("a", family_solvers[0])
    other = TriangularSolver.plan(
        erdos_renyi_lower(N, 0.05, seed=79), strategy=STRATEGY
    )
    with pytest.raises(ValueError, match="one width class"):
        bank.add("b", other)
    dist = TriangularSolver.plan(
        shifted_coupling_lower(N, 0, seed=40),
        strategy=STRATEGY,
        backend="pallas",
        interpret=True,
    )
    with pytest.raises(NotImplementedError, match="grouped"):
        bank.add("c", dist)


def test_grouped_solve_rejects_mixed_classes_and_bad_shapes(family_solvers):
    other = TriangularSolver.plan(
        erdos_renyi_lower(N, 0.05, seed=78), strategy=STRATEGY
    )
    with pytest.raises(ValueError, match="one width class"):
        grouped_solve([family_solvers[0], other], np.zeros((N, 2)))
    with pytest.raises(ValueError, match="one column per solver"):
        grouped_solve(family_solvers[:2], np.zeros((N, 3)))
    with pytest.raises(ValueError, match="at least one"):
        grouped_solve([], np.zeros((N, 0)))


# ------------------------------------------------ service: width-class mode
def test_service_coalesces_across_patterns_bitwise(family):
    with SolveService(
        max_batch=8,
        max_wait_us=300_000,
        width_class_batching=True,
        strategy=STRATEGY,
    ) as svc:
        pats = width_class_patterns(svc, 4, n=N, seed=50)
        rng = np.random.default_rng(2)
        tickets = []
        for i in range(8):
            fp, n = pats[i % len(pats)]
            b = rng.standard_normal(n).astype(np.float32)
            tickets.append((svc.submit(fp, b), b))
        for t, b in tickets:
            x = t.result(60)
            assert isinstance(t.served_by, GroupReplay)
            assert np.array_equal(
                x,
                direct_reference(
                    t.served_by, b, t.batch_width, t.batch_position
                ),
            )
        snap = svc.stats()
    # 8 requests over 4 patterns coalesced into FEW cross-pattern batches
    # (per-fingerprint routing would have needed >= 4 dispatches)
    assert snap["grouped_batches"] >= 1
    assert snap["batches"] < len(tickets)
    assert snap["completed"] == len(tickets) and snap["failed"] == 0
    wcs = snap["width_classes"]
    assert len(wcs) == 1 and next(iter(wcs.values()))["n_patterns"] == 4
    for fp, _ in pats:
        assert snap["patterns"][fp]["width_class"] in wcs


def test_width_class_batching_with_interleaved_updates(family):
    """Versions differ per column inside one grouped batch: requests
    pinned to v0 and v1 of one pattern plus another pattern ride one
    dispatch, each served with exactly its pinned values."""
    m0 = shifted_coupling_lower(N, 0, seed=60)
    m1 = shifted_coupling_lower(N, 1, seed=61)
    rng = np.random.default_rng(3)
    with SolveService(
        max_batch=8,
        max_wait_us=400_000,
        width_class_batching=True,
        strategy=STRATEGY,
    ) as svc:
        fp0, fp1 = svc.register(m0), svc.register(m1)
        admitted = []
        b = rng.standard_normal(N).astype(np.float32)
        admitted.append((svc.submit(fp0, b), b))
        svc.numeric_update(fp0, m0.data * 2.0)  # queued request stays v0
        b2 = rng.standard_normal(N).astype(np.float32)
        admitted.append((svc.submit(fp0, b2), b2))  # pinned v1
        b3 = rng.standard_normal(N).astype(np.float32)
        admitted.append((svc.submit(fp1, b3), b3))
        results = [(t, b, t.result(60)) for t, b in admitted]
    assert [t.version for t, _, _ in results] == [0, 1, 0]
    for t, b, x in results:
        assert np.array_equal(
            x,
            direct_reference(t.served_by, b, t.batch_width, t.batch_position),
        ), f"version {t.version} served with wrong values"
    # all three rode one grouped dispatch (same width class, one flush)
    widths = {t.batch_width for t, _, _ in results}
    positions = [t.batch_position for t, _, _ in results]
    assert widths == {4} and sorted(positions) == [0, 1, 2]


def test_homogeneous_groups_keep_the_plain_path(family):
    """A width-class batch whose columns all share (pattern, version)
    must serve through the classic multi-RHS path — same bits and
    ``served_by`` identity as width_class_batching=False."""
    m = shifted_coupling_lower(N, 2, seed=62)
    with SolveService(
        max_batch=8,
        max_wait_us=200_000,
        width_class_batching=True,
        strategy=STRATEGY,
    ) as svc:
        fp = svc.register(m)
        tickets = [
            svc.submit(fp, np.ones(N, np.float32)) for _ in range(3)
        ]
        for t in tickets:
            t.result(60)
        solver = svc.pattern(fp).solver_for(0)
        for t in tickets:
            assert t.served_by is solver  # plain path, not a GroupReplay
        assert svc.stats()["grouped_batches"] == 0


# --------------------------------------------------- multi-worker serving
def test_multi_worker_multi_route_bitwise_with_updates():
    """n_workers=3 over 3 routes: concurrent clients, interleaved
    numeric updates, every result bitwise vs its pinned version."""
    mats = [
        erdos_renyi_lower(120, 0.03, seed=81),
        erdos_renyi_lower(160, 0.02, seed=82),
        erdos_renyi_lower(200, 0.02, seed=83),
    ]
    with SolveService(
        max_batch=4, max_wait_us=2000, n_workers=3, strategy="growlocal"
    ) as svc:
        assert svc.n_workers == 3
        fps = [svc.register(m) for m in mats]
        ns = {fp: m.n_rows for fp, m in zip(fps, mats)}
        data = {fp: m.data for fp, m in zip(fps, mats)}
        n_clients, per_client = 6, 8
        out = [[] for _ in range(n_clients)]
        stop = threading.Event()

        def client(ci):
            rng = np.random.default_rng(300 + ci)
            for j in range(per_client):
                fp = fps[(ci + j) % len(fps)]
                b = rng.standard_normal(ns[fp]).astype(np.float32)
                t = svc.submit(fp, b)
                out[ci].append((t, b, t.result(60)))

        def updater():
            k = 0
            while not stop.is_set():
                fp = fps[k % len(fps)]
                svc.numeric_update(fp, data[fp] * (1.0 + 0.1 * (k + 1)))
                k += 1
                stop.wait(0.002)  # responsive shutdown, no sleep tail

        threads = [
            threading.Thread(target=client, args=(i,))
            for i in range(n_clients)
        ]
        up = threading.Thread(target=updater, daemon=True)
        for t in threads:
            t.start()
        up.start()
        for t in threads:
            t.join()
        stop.set()
        up.join(5)
        served = [s for c in out for s in c]
        assert len(served) == n_clients * per_client
        for ticket, b, x in served:
            assert np.array_equal(
                x,
                direct_reference(
                    ticket.served_by, b, ticket.batch_width,
                    ticket.batch_position,
                ),
            ), (ticket.fingerprint[:8], ticket.version)
        snap = svc.stats()
    assert snap["serving"]["n_workers"] == 3
    assert snap["completed"] == len(served) and snap["failed"] == 0


def test_multi_worker_width_class_loadgen():
    """Workers + width-class batching + loadgen driver compose: a
    validated closed loop over one width class with 2 workers."""
    with SolveService(
        max_batch=8,
        max_wait_us=2000,
        n_workers=2,
        width_class_batching=True,
        strategy=STRATEGY,
    ) as svc:
        pats = width_class_patterns(svc, 4, n=N, seed=70)
        sampler = make_sampler(pats, "uniform", seed=7)
        report = run_closed_loop(
            svc, sampler, n_clients=6, requests_per_client=5, validate=True
        )
    assert report["errors"] == 0
    assert report["bitwise_mismatches"] == 0
    assert report["requests"] == 30


# ------------------------------------------------------- lifecycle hardening
def test_close_timeout_retains_pins_until_workers_exit():
    """A worker stuck inside a batch past close(timeout) must NOT lose
    its plan's eviction pin — unpinning would let LRU eviction race the
    in-flight solve. The pins release on a later close() once the
    worker has actually exited."""
    m = erdos_renyi_lower(100, 0.03, seed=90)
    cache = PlanCache(maxsize=1)
    svc = SolveService(
        max_batch=2, max_wait_us=1000, cache=cache, strategy="growlocal"
    )
    fp = svc.register(m)
    vp = svc.pattern(fp)
    release = threading.Event()
    picked = threading.Event()
    real = vp.solver_for(0)

    class _Stall:
        def solve(self, B):
            picked.set()
            release.wait(30)
            return real.solve(B)

    vp._versions[0] = _Stall()
    t = svc.submit(fp, np.ones(100, np.float32))
    assert picked.wait(10)  # the worker holds the batch and is stalled
    report = svc.close(timeout=0.2)
    assert report["workers_alive"], "worker should still be stalled"
    assert report["pins_released"] == 0 and report["pins_retained"] == 1
    assert len(cache.pinned) == 1  # the pin survived the timed-out close
    release.set()
    t.result(60)
    report2 = svc.close(timeout=30)
    assert report2["workers_alive"] == []
    assert report2["pins_released"] == 1 and report2["pins_retained"] == 0
    assert len(cache.pinned) == 0


def test_close_clean_reports_released_pins():
    m = erdos_renyi_lower(80, 0.03, seed=91)
    svc = SolveService(strategy="growlocal")
    svc.register(m)
    report = svc.close(timeout=30)
    assert report == {
        "workers_alive": [],
        "pins_released": 1,
        "pins_retained": 0,
    }
    assert svc.close()["pins_released"] == 0  # idempotent


# ----------------------------------------------------- metrics window fix
def test_throughput_window_anchors_on_first_completion():
    """A batch completing after reset() (warm-up drain) used to leave
    ``_t_first`` None while setting ``_t_last`` — every later snapshot
    then divided by a zero-width window and reported 0.0 solves/s."""
    ms = ServeMetrics()
    ms.record_submit("fp")
    ms.record_batch("fp", 2, queue_waits=[0.0], e2e=[0.0], solve_seconds=0.0)
    ms.reset()
    # the warm-up drain: completions with NO post-reset submit
    ms.record_batch("fp", 4, queue_waits=[0.0], e2e=[0.0], solve_seconds=0.0)
    time.sleep(0.01)
    ms.record_batch("fp", 4, queue_waits=[0.0], e2e=[0.0], solve_seconds=0.0)
    snap = ms.snapshot()
    assert snap["completed"] == 8
    assert snap["elapsed_seconds"] > 0
    assert snap["solves_per_sec"] > 0


def test_failures_also_anchor_the_window():
    ms = ServeMetrics()
    ms.record_failure("fp", 1)
    time.sleep(0.01)
    ms.record_batch("fp", 2, queue_waits=[0.0], e2e=[0.0], solve_seconds=0.0)
    snap = ms.snapshot()
    assert snap["elapsed_seconds"] > 0 and snap["solves_per_sec"] > 0


def test_grouped_batch_metrics_attribution():
    ms = ServeMetrics()
    for fp in ("a", "a", "b"):
        ms.record_submit(fp)
    ms.record_grouped_batch(
        ["a", "a", "b"],
        queue_waits=[0.001] * 3,
        e2e=[0.002] * 3,
        solve_seconds=0.001,
    )
    snap = ms.snapshot()
    assert snap["grouped_batches"] == 1 and snap["batches"] == 1
    assert snap["completed"] == 3 and snap["mean_batch_size"] == 3.0
    assert snap["per_pattern"]["a"]["completed"] == 2
    assert snap["per_pattern"]["b"]["completed"] == 1
    # the batch is counted once globally, not once per pattern
    assert snap["per_pattern"]["a"]["batches"] == 0
    assert snap["grouped_batch_size_hist"] == {3: 1}


# ------------------------------------------------- pow2 width quantization
def test_normalize_max_batch():
    assert [normalize_max_batch(x) for x in (1, 2, 3, 15, 16, 24, 33)] == [
        1, 2, 2, 8, 16, 16, 32,
    ]
    with pytest.raises(ValueError, match="max_batch"):
        normalize_max_batch(0)


def test_pad_width_never_dispatches_non_pow2():
    for mb in (1, 2, 3, 8, 12, 24, 64):
        for m in range(1, mb + 1):
            w = pad_width(m, mb)
            assert w & (w - 1) == 0, (m, mb, w)
            assert w <= normalize_max_batch(mb)


def test_service_normalizes_max_batch():
    with SolveService(max_batch=24, strategy="growlocal") as svc:
        assert svc.max_batch == 16
        assert svc._batcher.max_batch == 16
        assert svc.stats()["serving"]["max_batch"] == 16
