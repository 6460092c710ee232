"""GrowLocal — the paper's scheduler (§3, Algorithm 3.1).

Superstep formation: iterations with a growing length parameter alpha
(20, 30, 45, ... — factor 1.5). In an iteration, core 1 receives up to alpha
vertices (weight Omega_1); cores 2..k are filled until their weight reaches
Omega_1. The iteration's parallelization score is

    beta = sum_p Omega_p / (max_p Omega_p + L).

An iteration is *worthy* iff beta >= WORTHY_FACTOR * best beta seen in this
superstep (first iteration always worthy; Appendix B uses 0.97). Worthy
iterations are remembered and invalidated; alpha grows; the first unworthy
iteration finalizes the last worthy assignment as the superstep.

Vertex selection — Rule I: when assigning to core p, prefer vertices that are
executable *only on p* in this superstep (a parent was assigned to p since the
last barrier); among candidates, smallest ID. Exclusive-first is the
[PAKY24]-inspired rule; smallest-ID keeps consecutive matrix rows together,
which the reordering step (§5) then turns into locality.

Complexity: O(|E| log |V|) under the paper's Thm 3.1 assumptions — iteration
sizes grow geometrically, so speculative assignments are amortized by the
finalized superstep size.
"""
from __future__ import annotations

import heapq
from typing import List, Optional, Tuple

import numpy as np

from repro import obs
from repro.core.schedule import DEFAULT_L, Schedule, bsp_cost
from repro.sparse.dag import SolveDAG

ALPHA_INIT = 20
ALPHA_GROWTH = 1.5
WORTHY_FACTOR = 0.97

_FREE = -1  # claimed[] sentinel: executable on any core
_BLOCKED = -2  # parents on >= 2 distinct cores this superstep


def grow_local(
    dag: SolveDAG,
    k: int,
    *,
    L: float = DEFAULT_L,
    alpha_init: int = ALPHA_INIT,
    alpha_growth: float = ALPHA_GROWTH,
    worthy_factor: float = WORTHY_FACTOR,
    frontier_widening: bool = False,
) -> Schedule:
    """Run GrowLocal on ``dag`` for ``k`` cores; returns a valid Schedule.

    ``frontier_widening`` (beyond-paper; off here, so a direct call is the
    paper's algorithm): on single-/few-source DAGs the paper's worthiness
    rule never cuts — beta = Omega_1/(Omega_1 + L) increases monotonically
    while one core's exclusive chain swallows the whole DAG (their
    SuiteSparse filter, avg wavefront >= 2k, hides this regime). When the
    DAG has parallelism to unlock (avg wavefront >= 2) but the current
    superstep runs on a single core, we stop growing alpha: the barrier
    releases the frontier to the free pool and the next superstep engages
    all cores. ``widened_grow_local`` (the registry's ``"growlocal"``)
    applies the cut where it pays."""
    return _grow_local(
        dag, k, L, alpha_init, alpha_growth, worthy_factor, frontier_widening
    )[0]


def widened_grow_local(dag: SolveDAG, k: int, *, L: float = DEFAULT_L) -> Schedule:
    """GrowLocal with the frontier-widening cut, kept where it pays.

    A run in which the cut never fires is the paper's schedule bit for bit
    (many-source DAGs). Where it fires, the widened schedule is kept only if
    its ``bsp_cost`` beats the paper's; the ``obs`` counter
    ``inspector.growlocal.widen_cut`` then counts the supersteps the cut
    closed. PERF.md §6 quantifies the effect (the IC(0) factor of a 64^3
    grid at k 8: one superstep of T = n plan steps -> 77 supersteps, T =
    35,851)."""
    widened, cuts = _grow_local(
        dag, k, L, ALPHA_INIT, ALPHA_GROWTH, WORTHY_FACTOR, True
    )
    if cuts == 0:
        return widened
    faithful = grow_local(dag, k, L=L)
    if bsp_cost(dag, widened, L) < bsp_cost(dag, faithful, L):
        obs.counter_add("inspector.growlocal.widen_cut", cuts)
        return widened
    return faithful


def _grow_local(
    dag: SolveDAG,
    k: int,
    L: float,
    alpha_init: int,
    alpha_growth: float,
    worthy_factor: float,
    frontier_widening: bool,
) -> Tuple[Schedule, int]:
    """``grow_local``'s body; also returns how many supersteps the
    frontier-widening cut closed."""
    n = dag.n
    if n == 0:
        return Schedule(
            n=0,
            k=k,
            pi=np.zeros(0, np.int32),
            sigma=np.zeros(0, np.int32),
            rank=np.zeros(0, np.int64),
            n_supersteps=0,
        ), 0
    # Python lists, not arrays: the loops below touch one element at a
    # time, where a list index costs a fraction of a NumPy scalar access
    weights = dag.weights.tolist()
    child_ptr, child_idx = dag.child_ptr.tolist(), dag.child_idx.tolist()

    widen_cut = False
    if frontier_widening:
        from repro.sparse.dag import average_wavefront_size

        widen_cut = average_wavefront_size(dag) >= 2.0

    # --- global (cross-superstep) state -----------------------------------
    in_deg = dag.in_degrees()
    final_remaining = in_deg.tolist()  # unfinalized parents
    scheduled = [False] * n
    pi = [-1] * n
    sigma = [-1] * n
    rank = [0] * n
    free_heap: List[int] = np.nonzero(in_deg == 0)[0].tolist()
    heapq.heapify(free_heap)

    # --- per-iteration scratch (reset lazily via iter_tag) ----------------
    cur_done = [0] * n  # parents assigned this iteration
    claimed = [_FREE] * n
    iter_tag = [0] * n  # last iteration id touching v
    assigned_tag = [0] * n  # last iteration id assigning v
    iteration_id = 0

    n_scheduled = 0
    superstep = 0
    cuts = 0

    while n_scheduled < n:
        alpha = float(alpha_init)
        best_beta = -np.inf
        last_worthy: Optional[List[Tuple[int, int]]] = None
        prev_total = -1

        while True:
            iteration_id += 1
            assignment: List[Tuple[int, int]] = []  # (vertex, core) in order
            popped_free: List[int] = []
            excl_heaps: List[List[int]] = [[] for _ in range(k)]
            omega = [0.0] * k

            def _next_vertex(p: int) -> int:
                """Rule I pop for core p; -1 if nothing assignable."""
                eh = excl_heaps[p]
                while eh:
                    v = heapq.heappop(eh)
                    # exclusive entries are iteration-local; always fresh
                    return v
                while free_heap:
                    v = free_heap[0]
                    if scheduled[v] or assigned_tag[v] == iteration_id:
                        heapq.heappop(free_heap)  # stale
                        continue
                    heapq.heappop(free_heap)
                    popped_free.append(v)
                    return v
                return -1

            def _assign(v: int, p: int):
                assigned_tag[v] = iteration_id
                assignment.append((v, p))
                omega[p] += weights[v]
                for u in child_idx[child_ptr[v] : child_ptr[v + 1]]:
                    if iter_tag[u] != iteration_id:
                        iter_tag[u] = iteration_id
                        cur_done[u] = 0
                        claimed[u] = _FREE
                    cur_done[u] += 1
                    if claimed[u] == _FREE:
                        claimed[u] = p
                    elif claimed[u] != p:
                        claimed[u] = _BLOCKED
                    if (
                        final_remaining[u] - cur_done[u] == 0
                        and claimed[u] == p
                        and not scheduled[u]
                    ):
                        heapq.heappush(excl_heaps[p], u)

            # I. assign up to alpha vertices to core 1 (index 0)
            quota = max(1, int(alpha))
            for _ in range(quota):
                v = _next_vertex(0)
                if v < 0:
                    break
                _assign(v, 0)
            # cores 2..k: fill until Omega_p reaches Omega_1
            for p in range(1, k):
                while omega[p] < omega[0]:
                    v = _next_vertex(p)
                    if v < 0:
                        break
                    _assign(v, p)

            # II. parallelization score (weights are whole numbers, so the
            # sum is exact in any order)
            total_w = sum(omega)
            max_w = max(omega)
            beta = total_w / (max_w + L) if (max_w + L) > 0 else 0.0
            total_assigned = len(assignment)

            first_iteration = last_worthy is None
            worthy = first_iteration or beta >= worthy_factor * best_beta
            if widen_cut and worthy and not first_iteration:
                # economics of the cut: a barrier (price L) only pays off if
                # the superstep already carries >= L weight on a single
                # core — then stop growing and let the barrier release the
                # frontier to the free pool. (An unconditional cut drowns
                # in barrier cost.)
                active = sum(w > 0 for w in omega)
                if active <= 1 and total_w >= L:
                    worthy = False  # closes this superstep below
                    cuts += 1
            best_beta = max(best_beta, beta)

            exhausted = n_scheduled + total_assigned >= n
            stalled = total_assigned <= prev_total  # alpha growth gained nothing
            prev_total = total_assigned

            if worthy:
                last_worthy = assignment
                if exhausted or stalled:
                    finalize = last_worthy
                    # nothing to restore: pool entries already popped are
                    # exactly the free vertices of `finalize`
                    restore = []
                    break
                # invalidate: restore popped free vertices, grow alpha
                for v in popped_free:
                    heapq.heappush(free_heap, v)
                alpha *= alpha_growth
            else:
                finalize = last_worthy
                restore = popped_free  # current (rejected) iteration's pops
                break

        # --- finalize the superstep ---------------------------------------
        for v in restore:
            heapq.heappush(free_heap, v)
        chain_pos = [0] * k
        newly_ready: List[int] = []
        for (v, p) in finalize:
            scheduled[v] = True
            pi[v] = p
            sigma[v] = superstep
            rank[v] = chain_pos[p]
            chain_pos[p] += 1
            n_scheduled += 1
        for (v, p) in finalize:
            for u in child_idx[child_ptr[v] : child_ptr[v + 1]]:
                final_remaining[u] -= 1
                if final_remaining[u] == 0 and not scheduled[u]:
                    newly_ready.append(u)
        for u in newly_ready:
            heapq.heappush(free_heap, u)
        superstep += 1

    return Schedule(
        n=n,
        k=k,
        pi=np.array(pi, dtype=np.int32),
        sigma=np.array(sigma, dtype=np.int32),
        rank=np.array(rank, dtype=np.int64),
        n_supersteps=superstep,
    ), cuts
