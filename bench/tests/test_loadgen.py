"""The open-loop driver times each request from when it was due."""
import threading
import time

import numpy as np

from bench import harness, loadgen


def test_every_seed_gets_the_same_arrivals_in_another_order():
    a = loadgen.arrival_offsets(200.0, 5.0, harness.rng(1, 3))
    b = loadgen.arrival_offsets(200.0, 5.0, harness.rng(2, 3))
    assert len(a) == len(b) == 1000
    assert a[0] == b[0] == 0.0
    gaps = loadgen.arrival_gaps(200.0, 5.0)
    for offsets in (a, b):
        d = np.diff(offsets)
        assert np.abs(d[:, None] - gaps[None, :]).min(axis=1).max() < 1e-9
    assert not np.allclose(a, b)
    assert abs(gaps.mean() - 1 / 200.0) < 0.01 / 200.0


class _Ticket:
    def __init__(self):
        self.t_done = time.perf_counter()


def test_a_stall_counts_against_later_requests():
    """The server stalls 0.3 s on the third request. The requests due
    during the stall are sent late, and their latency from due time holds
    the wait, although each is served at once once sent."""
    stall = threading.Event()

    def submit(i):
        if i == 2 and not stall.is_set():
            stall.set()
            time.sleep(0.3)
        return _Ticket()

    due = np.arange(10) * 0.01
    t0, tickets, late = loadgen.run_open_loop(submit, due, list(range(10)))
    from_due = np.array([tk.t_done - (t0 + d) for tk, d in zip(tickets, due)])
    assert from_due[2] >= 0.29
    assert np.all(from_due[3:9] >= 0.29 - due[3:9] + due[2] - 0.01)
    assert late[3] >= 0.25
    assert from_due[:2].max() < 0.05
