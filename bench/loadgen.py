"""Open-loop load: requests sent on a fixed schedule, whatever the server does.

Copied from the program's ``repro.serve.loadgen.run_open_loop`` and changed:

- each request is timed from when it was due, not from when it was
  submitted, so a stall that delays later sends counts against them;
- the schedule and the request payloads are made before the window, from
  the seed: the arrival gaps are the same set for every seed (quantiles of
  the exponential distribution at the cell's rate), in an order the seed
  draws, so seeds change the order of the work and not its amount;
- the report gives how late the generator ran, so that a starved generator
  is not read as a fast server.
"""
from __future__ import annotations

import time

import numpy as np


def arrival_gaps(rate_hz: float, seconds: float) -> np.ndarray:
    """The gaps between arrivals, the same for every seed: the quantiles of
    the exponential distribution of mean 1 / ``rate_hz``, one per request of
    ``round(rate_hz * seconds)``."""
    count = max(int(round(rate_hz * seconds)), 1)
    q = (np.arange(count) + 0.5) / count
    return -np.log1p(-q) / rate_hz


def arrival_offsets(rate_hz: float, seconds: float, rng) -> np.ndarray:
    """Due times, in seconds from the start of the window: the first request
    at 0, then the gaps in an order ``rng`` draws (one gap is left over)."""
    gaps = rng.permutation(arrival_gaps(rate_hz, seconds))
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def run_open_loop(submit, due, payloads, *, annotate=None):
    """Submit ``payloads[i]`` at ``t0 + due[i]`` for every i, without waiting
    for results. ``submit(payload)`` returns a ticket. Returns
    ``(t0, tickets, lateness_s)``: the window's start on the
    ``time.perf_counter`` clock, one ticket per request, and how late each
    submit began against its due time."""
    tickets = [None] * len(due)
    late = np.zeros(len(due))
    t0 = time.perf_counter()
    for i, offset in enumerate(due):
        now = time.perf_counter()
        wait = t0 + offset - now
        if wait > 0:
            time.sleep(wait)
            now = time.perf_counter()
        late[i] = now - (t0 + offset)
        if annotate is None:
            tickets[i] = submit(payloads[i])
        else:
            with annotate("bench.submit"):
                tickets[i] = submit(payloads[i])
    return t0, tickets, late
