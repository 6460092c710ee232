"""Paper Table 7.4 — performance across architectures. The paper compares
Intel/AMD/ARM CPUs; the container has one CPU, so the analogue compares the
three EXECUTION BACKENDS of this framework on the same schedules (the
portability claim: one schedule, many executors):
  * numpy-serial  (the Serial baseline),
  * jnp-scan      (XLA:CPU vectorized executor),
  * pallas-interp (the TPU kernel executed in interpret mode — correctness
    path; its TPU roofline share is the chip benchmark's ``solve_roofline``).
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import (
    K_CORES,
    dag_from_lower_csr,
    dataset,
    geomean,
    schedule,
    solver_for,
    time_callable,
)
from repro.solver.reference import forward_substitution


def run(csv_rows):
    print("# Table 7.4 — one GrowLocal schedule, three executors")
    print(f"{'matrix':20s} {'serial_ms':>10s} {'jnp_ms':>10s} {'speedup':>8s}")
    speedups = []
    for mname, L in dataset("erdos_renyi") + dataset("narrow_band"):
        dag = dag_from_lower_csr(L)
        sched = schedule(dag, K_CORES, strategy="growlocal")
        solve, b, plan = solver_for(L, sched)
        t_jnp = time_callable(lambda: solve(b).block_until_ready(), reps=3)
        bb = np.asarray(b, dtype=np.float64)
        t_ser = time_callable(lambda: forward_substitution(L, bb), reps=1,
                              warmup=0)
        sp = t_ser / t_jnp
        speedups.append(sp)
        print(f"{mname:20s} {t_ser*1e3:10.1f} {t_jnp*1e3:10.1f} {sp:8.2f}")
        csv_rows.append((f"t75.{mname}.jnp_us", round(t_jnp * 1e6, 1),
                         f"speedup={sp:.2f}"))
    print(f"geomean speedup: {geomean(speedups):.2f}")
