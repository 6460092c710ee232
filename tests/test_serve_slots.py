"""``repro.serve.slots`` — the continuous-batching engine's gauntlet.

Acceptance bars:

  * ``SlotState`` (the pure lane-allocation state machine) holds its
    invariants under arbitrary admit/release/evict sequences — unit
    cases, a deterministic fuzz walk, and a Hypothesis property drive —
    and every admitted token terminates exactly once;
  * the resident device ops move bits unchanged: ``insert_lane`` /
    ``extract_lane`` round-trip exactly, ``solve_resident`` is bitwise-
    identical to ``solve_bank`` on the same lanes, and writing a
    neighbor lane never perturbs an occupied lane's bits (the
    lane-independence replay the served-equals-direct contract rests
    on);
  * ``mode="continuous"`` serves bitwise-correctly end to end —
    including across interleaved ``numeric_update``s (version pinning),
    slot overflow (backlog > lanes resolves by extra passes, never
    errors), shutdown (``close`` drains; no ticket is ever stranded),
    and back-pressure (``QueueFullError`` beyond ``max_queue``);
  * non-groupable (elastic-bound) patterns fall back to the microbatch
    path gracefully, in continuous mode and under width-class batching.

Matrices stay small (n <= 160) to keep plan+compile in tier-1 budget.
"""
import threading
import time

import numpy as np
import pytest

from _hyp import HAVE_HYPOTHESIS, given, settings, strategies as st
from _oracle import solver_oracle
from repro.pipeline import GroupBank, TriangularSolver
from repro.serve import (
    AdmissionQueue,
    QueueFullError,
    SlotDispatcher,
    SlotEngine,
    SlotState,
    SlotsFull,
    SolveService,
    direct_reference,
)
from repro.serve.service import SolveTicket
from repro.sparse import shifted_coupling_lower
from repro.sparse.generators import erdos_renyi_lower

STRATEGY = "wavefront"  # level scheduler: shift-invariant plan shapes
N = 96


@pytest.fixture(scope="module")
def family():
    return [shifted_coupling_lower(N, j, seed=70 + j) for j in range(3)]


@pytest.fixture(scope="module")
def family_solvers(family):
    return [TriangularSolver.plan(m, strategy=STRATEGY) for m in family]


def rhs(n, seed):
    return np.random.default_rng(seed).standard_normal(n).astype(np.float32)


# ------------------------------------------------------- SlotState: units
def test_slotstate_allocates_lowest_lane_first():
    s = SlotState(4)
    assert [s.admit(f"t{i}") for i in range(4)] == [0, 1, 2, 3]
    s.check()
    assert s.release(1) == "t1"
    assert s.release(2) == "t2"
    # freed lanes are reused before never-used ones (LIFO keeps the
    # occupied prefix tight — the pow2 pass-width bound relies on it)
    assert s.admit("t4") == 2
    assert s.admit("t5") == 1
    s.check()


def test_slotstate_books_and_lookup():
    s = SlotState(2)
    s.admit("a")
    s.admit("b")
    assert s.occupancy == 2 and s.free_count == 0
    assert s.lane_of("b") == 1 and s.lane_of("nope") is None
    assert s.occupants() == {0: "a", 1: "b"}
    s.release(0)
    s.evict(1)
    assert (s.admitted, s.completed, s.evicted) == (2, 1, 1)
    s.check()


def test_slotstate_rejects_double_occupancy():
    s = SlotState(2)
    s.admit("a")
    with pytest.raises(ValueError):
        s.admit("a")  # a token occupies at most one lane
    s.admit("b")
    with pytest.raises(SlotsFull):
        s.admit("c")
    s.check()


def test_slotstate_rejects_freeing_a_free_lane():
    s = SlotState(2)
    s.admit("a")
    with pytest.raises(ValueError):
        s.release(1)
    with pytest.raises(ValueError):
        s.evict(5)
    s.release(0)
    with pytest.raises(ValueError):
        s.release(0)
    s.check()


def test_slotstate_rejects_bad_sizes():
    with pytest.raises(ValueError):
        SlotState(0)


# ------------------------------------ SlotState: property / fuzz coverage
def _walk(state, ops):
    """Drive ``state`` through (op, token) steps, mirroring it against a
    model dict; audits every invariant after every step and returns the
    terminal counts per token."""
    live = {}  # token -> lane (the model)
    done = []  # tokens that terminated (released or evicted)
    for op, token in ops:
        if op == "admit":
            if token in live:
                with pytest.raises(ValueError):
                    state.admit(token)
            elif len(live) == state.n_slots:
                with pytest.raises(SlotsFull):
                    state.admit(token)
            else:
                live[token] = state.admit(token)
        elif live:
            lane = live[sorted(live)[hash(token) % len(live)]]
            got = state.release(lane) if op == "release" else state.evict(lane)
            assert live.pop(got) == lane
            done.append(got)
        state.check()
        assert state.occupancy == len(live)
        assert state.occupants() == {v: k for k, v in live.items()}
    # exactly-once termination: every completion popped a live admission
    # (enforced by ``live.pop`` above), and the books partition every
    # admission into completed/evicted/still-live with nothing counted
    # twice
    assert len(done) == state.completed + state.evicted
    assert state.admitted == state.completed + state.evicted + len(live)


def test_slotstate_fuzz_walk_deterministic():
    rng = np.random.default_rng(7)
    for n_slots in (1, 2, 4, 8):
        ops = [
            (("admit", "release", "evict")[rng.integers(3)],
             f"t{rng.integers(n_slots * 2)}")
            for _ in range(600)
        ]
        _walk(SlotState(n_slots), ops)


@given(
    n_slots=st.sampled_from([1, 2, 4]),
    ops=st.lists(
        st.tuples(
            st.sampled_from(["admit", "release", "evict"]),
            st.integers(min_value=0, max_value=9).map("t{}".format),
        ),
        max_size=200,
    ),
)
@settings(max_examples=150, deadline=None)
def test_slotstate_property_invariants(n_slots, ops):
    _walk(SlotState(n_slots), ops)


# --------------------------------------------------------- AdmissionQueue
def test_admission_queue_fifo_close_and_drain():
    q = AdmissionQueue()
    for i in range(5):
        q.put(i)
    assert q.depth() == 5
    assert q.take(2) == [0, 1]
    assert q.drain() == [2, 3, 4]
    q.put(5)
    q.mark_pending(3)  # consumer-held items still count as backlog
    assert q.depth() == 4
    q.mark_pending(0)
    q.close()
    with pytest.raises(RuntimeError):
        q.put(6)
    assert q.take(10) == [5]  # queued work still drains after close...
    assert q.take(10) == []  # ...then the exit signal


def test_admission_queue_take_blocks_until_put():
    q = AdmissionQueue()
    got = []
    ready = threading.Event()

    def consumer():
        ready.set()
        got.extend(q.take(4))

    t = threading.Thread(target=consumer)
    t.start()
    ready.wait(5)
    q.put("x")
    t.join(5)
    assert got == ["x"]


# -------------------------------------- device ops: bitwise + lane purity
def test_resident_ops_roundtrip_and_purity(family_solvers):
    s = family_solvers[0]
    cls = type(s._bound)
    B0 = cls.blank_rhs(s.n, 4, np.float32)
    b0, b1 = rhs(s.n, 1), rhs(s.n, 2)
    B1 = cls.insert_lane(B0, 0, b0)
    B2 = cls.insert_lane(B1, 2, b1)
    # round-trip moves bits unchanged
    assert np.asarray(cls.extract_lane(B2, 0)).tobytes() == b0.tobytes()
    assert np.asarray(cls.extract_lane(B2, 2)).tobytes() == b1.tobytes()
    # insert is pure: the input bank kept its bits (in-flight passes
    # snapshot the bank; a mutating insert would corrupt them)
    assert np.asarray(cls.extract_lane(B0, 0)).tobytes() == (
        np.zeros(s.n, np.float32).tobytes()
    )
    assert np.asarray(cls.extract_lane(B1, 2)).tobytes() == (
        np.zeros(s.n, np.float32).tobytes()
    )


def test_solve_resident_matches_solve_bank_bitwise(family_solvers):
    bank = GroupBank()
    keys = []
    for i, s in enumerate(family_solvers):
        bank.add(i, s)
        keys.append(i)
    cls = type(family_solvers[0]._bound)
    n = family_solvers[0].n
    cols = [rhs(n, 10 + j) for j in range(4)]
    lane_keys = [keys[0], keys[1], keys[2], keys[0]]
    B = cls.blank_rhs(n, 4, np.float32)
    for j, c in enumerate(cols):
        B = cls.insert_lane(B, j, c)
    X_res = np.asarray(bank.solve_resident(lane_keys, B))
    X_bank = np.asarray(bank.solve(lane_keys, np.stack(cols, axis=1)))
    assert X_res.tobytes() == X_bank.tobytes()
    for j, (key, c) in enumerate(zip(lane_keys, cols)):
        ref = solver_oracle(family_solvers[key], c)
        assert X_res[:, j].tobytes() == ref.tobytes(), j


def test_neighbor_insert_never_perturbs_occupied_lane(family_solvers):
    # the lane-independence replay: solve with lane 0 occupied, then
    # churn every OTHER lane and re-solve — lane 0's bits must not move
    bank = GroupBank()
    for i, s in enumerate(family_solvers):
        bank.add(i, s)
    cls = type(family_solvers[0]._bound)
    n = family_solvers[0].n
    b_pinned = rhs(n, 42)
    B = cls.insert_lane(cls.blank_rhs(n, 4, np.float32), 0, b_pinned)
    lane_keys = [0, 1, 2, 1]
    want = np.asarray(
        cls.extract_lane(bank.solve_resident(lane_keys, B), 0)
    ).tobytes()
    for round_ in range(3):
        for j in (1, 2, 3):
            B = cls.insert_lane(B, j, rhs(n, 100 + 10 * round_ + j))
        got = np.asarray(
            cls.extract_lane(bank.solve_resident(lane_keys, B), 0)
        ).tobytes()
        assert got == want


# ----------------------------------------------------- engine-level units
def test_engine_normalizes_slots_to_pow2():
    assert SlotEngine(n_slots=5).n_slots == 8
    assert SlotEngine(n_slots=8).n_slots == 8
    assert SlotEngine(n_slots=1).n_slots == 1
    with pytest.raises(ValueError):
        SlotEngine(n_slots=0)


def test_ticket_double_fulfill_guard():
    t = SolveTicket("ab" * 32, 0)
    t._fulfill(np.zeros(3))
    with pytest.raises(RuntimeError):
        t._fulfill(np.ones(3))


# ------------------------------------------------ continuous service path
@pytest.fixture()
def cont_service():
    svc = SolveService(
        mode="continuous", max_batch=4, strategy=STRATEGY
    )
    yield svc
    svc.close()


def test_continuous_requires_slots_capability():
    with pytest.raises(ValueError, match="slots"):
        SolveService(mode="continuous", backend="pallas")


def test_continuous_rejects_unknown_mode():
    with pytest.raises(ValueError, match="mode"):
        SolveService(mode="batch")


def test_continuous_served_equals_direct_bitwise(cont_service, family):
    svc = cont_service
    fps = [svc.register(m) for m in family]
    tickets = []
    for i in range(24):
        fp = fps[i % len(fps)]
        b = rhs(N, 300 + i)
        tickets.append((svc.submit(fp, b), b))
    for ticket, b in tickets:
        x = ticket.result(timeout=60)
        want = direct_reference(
            ticket.served_by, b, ticket.batch_width, ticket.batch_position
        )
        assert x.tobytes() == want.tobytes()
    st_ = svc.stats()
    assert st_["serving"]["mode"] == "continuous"
    assert st_["slots"]["passes"] >= 1
    # every request went through a lane, none leaked to the worker path
    assert sum(
        occ * cnt for occ, cnt in st_["slots"]["occupancy_hist"].items()
    ) == len(tickets)


def test_continuous_numeric_update_serves_admitted_version(cont_service, family):
    svc = cont_service
    m = family[0]
    fp = svc.register(m)
    b = rhs(N, 50)
    x_v0 = svc.submit(fp, b).result(timeout=60)
    v1 = svc.numeric_update(fp, m.data * 3.0)
    assert v1 == 1
    t1 = svc.submit(fp, b)
    x_v1 = t1.result(timeout=60)
    want = direct_reference(
        t1.served_by, b, t1.batch_width, t1.batch_position
    )
    assert x_v1.tobytes() == want.tobytes()
    assert not np.array_equal(x_v0, x_v1)  # the new values actually landed
    # the superseded version retires once its in-flight work drains
    assert svc.pattern(fp).wait_retired(0, timeout=30)


def test_continuous_overflow_resolves_by_extra_passes(family):
    svc = SolveService(mode="continuous", n_slots=2, strategy=STRATEGY)
    try:
        fp = svc.register(family[0])
        svc.prewarm()
        bs = [rhs(N, 400 + i) for i in range(9)]
        tickets = [svc.submit(fp, b) for b in bs]
        for ticket, b in zip(tickets, bs):
            x = ticket.result(timeout=60)
            want = direct_reference(
                ticket.served_by, b, ticket.batch_width,
                ticket.batch_position,
            )
            assert x.tobytes() == want.tobytes()
            assert ticket.batch_position < 2  # never outside the 2 lanes
        eng = next(iter(svc._engines.values()))
        d = eng.describe()
        assert d["n_slots"] == 2
        assert d["admitted"] == d["completed"] == len(bs)
        assert d["passes"] >= (len(bs) + 1) // 2  # overflow => extra passes
    finally:
        svc.close()


def test_continuous_backpressure_rejects_beyond_max_queue(
    family, monkeypatch
):
    release = threading.Event()
    orig = SlotEngine._run_pass

    def stalled(self, reqs):
        release.wait(30)
        orig(self, reqs)

    monkeypatch.setattr(SlotEngine, "_run_pass", stalled)
    svc = SolveService(
        mode="continuous", max_queue=3, strategy=STRATEGY
    )
    try:
        fp = svc.register(family[0])
        tickets = [svc.submit(fp, rhs(N, 500 + i)) for i in range(8)]
        release.set()
        outcomes = []
        for t in tickets:
            try:
                t.result(timeout=60)
                outcomes.append("ok")
            except QueueFullError:
                outcomes.append("rejected")
        assert "rejected" in outcomes  # the bound actually bounced work
        assert "ok" in outcomes  # ...without starving admitted requests
        assert svc.stats()["rejected"] == outcomes.count("rejected")
    finally:
        release.set()
        svc.close()


def test_continuous_close_drains_without_stranding(family):
    svc = SolveService(mode="continuous", strategy=STRATEGY)
    fp = svc.register(family[0])
    svc.prewarm()
    bs = [rhs(N, 600 + i) for i in range(12)]
    tickets = [svc.submit(fp, b) for b in bs]
    report = svc.close(timeout=60)
    assert report["workers_alive"] == []
    assert report["pins_retained"] == 0
    for ticket, b in zip(tickets, bs):
        x = ticket.result(timeout=1)  # already fulfilled: close() drained
        want = direct_reference(
            ticket.served_by, b, ticket.batch_width, ticket.batch_position
        )
        assert x.tobytes() == want.tobytes()
    with pytest.raises(RuntimeError):
        svc.submit(fp, bs[0])


def test_continuous_concurrent_clients_bitwise(cont_service, family):
    svc = cont_service
    fps = [svc.register(m) for m in family]
    svc.prewarm()
    failures = []

    def client(seed):
        rng = np.random.default_rng(seed)
        for i in range(6):
            fp = fps[int(rng.integers(len(fps)))]
            b = rng.standard_normal(N).astype(np.float32)
            t = svc.submit(fp, b)
            x = t.result(timeout=60)
            want = direct_reference(
                t.served_by, b, t.batch_width, t.batch_position
            )
            if x.tobytes() != want.tobytes():
                failures.append((seed, i))

    threads = [
        threading.Thread(target=client, args=(900 + k,)) for k in range(6)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert failures == []


# ------------------------------------------------- fallback + degradation
def test_continuous_elastic_pattern_falls_back_to_microbatch(family):
    svc = SolveService(mode="continuous", strategy=STRATEGY)
    try:
        m = erdos_renyi_lower(140, 0.03, seed=77)
        # explicit elastic opt-in overrides the continuous-mode bsp
        # default; the bound cannot join a bank (supports_grouped=False)
        fp = svc.register(m, strategy="growlocal", mode="elastic")
        assert not svc.pattern(fp).groupable
        b = rhs(140, 7)
        t = svc.submit(fp, b)
        x = t.result(timeout=60)
        want = direct_reference(
            t.served_by, b, t.batch_width, t.batch_position
        )
        assert x.tobytes() == want.tobytes()
        assert svc._engines == {}  # served by the worker path, no lanes
    finally:
        svc.close()


def test_width_class_batching_elastic_pattern_falls_back_plain(family):
    # regression: width-class routing must skip non-groupable bounds
    # (elastic) and serve them on the plain per-pattern path
    svc = SolveService(width_class_batching=True, strategy=STRATEGY)
    try:
        fp_grp = svc.register(family[0])
        m = erdos_renyi_lower(140, 0.03, seed=78)
        fp_el = svc.register(m, strategy="growlocal", mode="elastic")
        assert svc.pattern(fp_grp).groupable
        assert not svc.pattern(fp_el).groupable
        pairs = []
        for i in range(6):
            fp, n = (fp_grp, N) if i % 2 else (fp_el, 140)
            b = rhs(n, 800 + i)
            pairs.append((svc.submit(fp, b), b))
        for t, b in pairs:
            x = t.result(timeout=60)
            want = direct_reference(
                t.served_by, b, t.batch_width, t.batch_position
            )
            assert x.tobytes() == want.tobytes()
    finally:
        svc.close()


def test_continuous_mode_pins_auto_selection_to_bsp(family):
    # left alone, strategy='auto' may flip deep patterns to elastic —
    # whose bounds silently dodge the slot path; continuous mode must
    # pin auto to bulk-synchronous so registration yields bankable plans
    svc = SolveService(mode="continuous")
    try:
        m = erdos_renyi_lower(150, 0.02, seed=79)
        fp = svc.register(m)
        assert svc.pattern(fp).groupable
    finally:
        svc.close()


# ----------------------------------------------------- dispatcher details
def test_dispatcher_close_is_idempotent_and_rejects_submits(
    family_solvers,
):
    d = SlotDispatcher(name="t")
    eng = SlotEngine(n_slots=2)
    assert d.alive()
    assert d.close(timeout=10)
    assert not d.alive()
    assert d.close(timeout=10)  # second close: still just True
    with pytest.raises(RuntimeError):
        d.submit(eng, SolveTicket("cd" * 32, 0), ("k", 0),
                 family_solvers[0], np.zeros(N, np.float32))


def test_slot_metrics_snapshot_shape(cont_service, family):
    svc = cont_service
    fp = svc.register(family[0])
    svc.submit(fp, rhs(N, 1)).result(timeout=60)
    snap = svc.stats()
    slots = snap["slots"]
    assert set(slots) >= {
        "passes", "n_slots", "occupancy_hist", "mean_occupancy",
        "time_in_slot_us",
    }
    for pct in ("p50", "p95", "p99", "p99.9"):
        assert pct in slots["time_in_slot_us"]
        assert pct in snap["latency_us"]
    assert snap["serving"]["n_slots"] == svc.n_slots
