"""Sharded single-solve — halo exchange vs all-gather comm + wall clock.

Compares the two distributed executors on one multi-device CPU mesh:

  * **model** — the k-wide model-axis shard (``shard="model"``): every
    superstep broadcasts ALL x-fragments with a full ``all_gather``
    (O(k * T) values per device per solve);
  * **rows**  — the row partition (``shard="rows"``): per-shard resident
    x, one static halo exchange per superstep moving only the boundary
    values (``core.rowshard``).

Per matrix it reports wall clock for both, the comm volumes from the
partition's static model AND from live ``obs`` counters
(``rowshard.halo_values`` / ``rowshard.halo_bytes``, bumped per solve by
the bound), and the headline ``halo_ratio`` = halo traffic / all-gather
baseline. ``--smoke`` additionally asserts the sharded solve is bitwise
equal to the single-chip scan solve and that ``halo_ratio <= 0.25`` on
the banded instance (the acceptance bound; locality matrices are the
regime the §5 reorder makes contiguous). The full run includes an
N >= 1e6 narrow-band partitioned solve whose plan exceeds any single
shard's share — the scale the row partition exists for.

Output: human table + ``repro-bench-rows/v1`` JSON (``--json``), same
schema as ``benchmarks.run --json``.

  PYTHONPATH=src:. python -m benchmarks.shard_solve --smoke --json rows.json
  PYTHONPATH=src:. python -m benchmarks.shard_solve --n 1000000
"""
from __future__ import annotations

import os

# must precede the first jax import: jax locks the host device count at
# first init (same isolation tests/_mesh.py uses)
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
)

import argparse
import sys
import time

import numpy as np


def _mats(smoke: bool, n_big: int):
    from repro.sparse.generators import erdos_renyi_lower, narrow_band_lower

    if smoke:
        return [
            ("band_20k", narrow_band_lower(20_000, 0.12, 8, seed=2)),
            ("er_10k", erdos_renyi_lower(10_000, 2e-4, seed=9)),
        ]
    return [
        ("band_200k", narrow_band_lower(200_000, 0.12, 8, seed=2)),
        ("er_100k", erdos_renyi_lower(100_000, 2e-5, seed=9)),
        (f"band_{n_big // 1000}k", narrow_band_lower(n_big, 0.12, 8, seed=3)),
    ]


def _timeit(fn, reps: int) -> float:
    fn()  # warm (compile)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def run(args) -> int:
    import jax

    from benchmarks.common import write_json_rows
    from repro import obs
    from repro.pipeline import PlanCache, TriangularSolver

    n_dev = len(jax.devices())
    mesh = jax.make_mesh((1, n_dev), ("data", "model"))
    cache = PlanCache()
    csv_rows = []
    reps = 3 if args.smoke else 5
    print(f"# shard_solve — rows (halo) vs model (all_gather), "
          f"{n_dev}-device CPU mesh")
    print(f"{'matrix':12s} {'n':>9s} {'model_us':>10s} {'rows_us':>10s} "
          f"{'halo_ratio':>10s} {'halo_KiB':>9s} {'ag_KiB':>9s}")

    ok = True
    for name, L in _mats(args.smoke, args.n):
        n = L.n_rows
        b = np.random.default_rng(7).standard_normal(n).astype(np.float32)

        rows = TriangularSolver.plan(
            L, k=8, backend="distributed", mesh=mesh, shard="rows",
            cache=cache,
        )
        ex = rows.bound.describe()["exchange"]

        # live counters: one solve under tracing, report what the bound
        # actually recorded (the acceptance wants measured, not modeled).
        # A fresh buffer per matrix — the default buffer accumulates.
        with obs.tracing(obs.TraceBuffer(f"rows.{name}")) as buf:
            x_rows = np.asarray(rows.solve(b))
        counters = buf.counters()
        halo_vals = counters.get("rowshard.halo_values", 0)
        halo_bytes = counters.get("rowshard.halo_bytes", 0)
        assert halo_vals == ex["halo_values_per_solve"], (
            halo_vals, ex["halo_values_per_solve"])

        t_rows = _timeit(lambda: rows.solve(b), reps)

        # the model-axis baseline broadcasts everything; at bench scale
        # its per-solve all_gather volume comes straight from the model
        t_model = float("nan")
        if n <= args.model_cap:
            model = TriangularSolver.plan(
                L, k=8, backend="distributed", mesh=mesh, shard="model",
                cache=cache,
            )
            t_model = _timeit(lambda: model.solve(b), reps)

        ratio = ex["halo_ratio"]
        print(f"{name:12s} {n:9d} {t_model * 1e6:10.0f} "
              f"{t_rows * 1e6:10.0f} {ratio:10.4f} "
              f"{halo_bytes / 1024:9.1f} {ex['allgather_bytes'] / 1024:9.1f}")
        csv_rows += [
            (f"rows.{name}.us_per_solve", round(t_rows * 1e6, 1), ""),
            (f"rows.{name}.halo_ratio", round(ratio, 5), ""),
            (f"rows.{name}.halo_bytes", halo_bytes, "obs counter"),
            (f"rows.{name}.allgather_bytes", ex["allgather_bytes"], ""),
            (f"rows.{name}.exchange_rounds", ex["rounds"], ""),
        ]
        if not np.isnan(t_model):
            csv_rows.append(
                (f"model.{name}.us_per_solve", round(t_model * 1e6, 1), "")
            )

        if args.smoke or args.check:
            ref = TriangularSolver.plan(L, k=8, backend="scan", cache=cache)
            bitwise = np.array_equal(x_rows, np.asarray(ref.solve(b)))
            print(f"  bitwise vs scan: {bitwise}")
            csv_rows.append((f"rows.{name}.bitwise", int(bitwise), ""))
            if not bitwise:
                ok = False
        if name.startswith("band") and ratio > 0.25:
            print(f"  FAIL halo_ratio {ratio} > 0.25 on banded instance")
            ok = False

    if not args.smoke:
        # the scale claim: the partition exceeds any single shard's plan
        d = rows.bound.describe()
        per_shard = d["n_loc"] + d["n_halo"]
        print(f"N={n}: per-shard slots {per_shard} "
              f"({per_shard / n:.2%} of the full plan)")
        csv_rows.append(("rows.big.per_shard_frac",
                         round(per_shard / n, 4), ""))
        if per_shard >= n:
            ok = False

    if args.json:
        write_json_rows(args.json, csv_rows, ["shard_solve"],
                        smoke=args.smoke, devices=n_dev)
    if not ok:
        print("SMOKE FAILED", file=sys.stderr)
        return 1
    print("ok")
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--smoke", action="store_true",
                   help="small matrices, assert bitwise + halo_ratio bound")
    p.add_argument("--check", action="store_true",
                   help="bitwise-check vs scan even on the full run")
    p.add_argument("--json", metavar="PATH", default=None)
    p.add_argument("--n", type=int, default=1_000_000,
                   help="rows of the large narrow-band instance (full run)")
    p.add_argument("--model-cap", type=int, default=250_000,
                   help="skip the all_gather baseline above this n "
                        "(its O(k*T) traffic makes big runs pointless)")
    return run(p.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
