#!/usr/bin/env python3
"""Chip smoke test: drive the solver's public entry points once on a TPU.

    python chip_smoke.py                  # one chip: device, solve, pcg, serve, pallas
    python chip_smoke.py --chips 4        # four chips: the sharded solves only
    JAX_PLATFORMS=cpu python chip_smoke.py --size rehearsal   # CPU rehearsal

Everything runs in this one process (a chip belongs to one process), on
data generated from ``--seed``. Each phase prints one JSON line: its
name, pass/fail, wall time, compile time where it is known, and the
numbers it checked. The last line is

    {"ok": <bool>, "device": {"platform": ..., "kind": ..., "count": ...}}

``ok`` is true only when every phase passed, the platform is ``tpu``,
and no Pallas binding interpreted its kernel; otherwise the exit code is
1. At the default size a non-TPU platform stops the run after the device
phase. ``--size rehearsal`` runs every phase at a small size on any
platform, which proves the phases but never passes as a chip run.

Sizes (``full``): the single-chip phases use the IC(0) factor of the
7-point 3-D Poisson matrix on a 64^3 grid (n = 262,144, 1.04M factor
entries); ``--chips 4`` uses the narrow-band lower-triangular matrix of
``benchmarks/shard_solve.py``'s full run at n = 10^6 (2.02M entries).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SIZES = {
    "full": dict(nx=64, shard_n=1_000_000, n_rhs=16, n_requests=64),
    "rehearsal": dict(nx=8, shard_n=2_000, n_rhs=16, n_requests=64),
}
TOL = 1e-3  # f32 solve vs the scipy (f64) reference, relative to max|x|
PCG_TOL = 1e-6
CORPUS_PATTERNS = ("band_narrow", "er_sparse")


class PhaseFailed(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise PhaseFailed(msg)


def _rel_err(x, ref):
    import numpy as np

    x = np.asarray(x, np.float64).reshape(ref.shape[0], -1)
    ref = ref.reshape(ref.shape[0], -1)
    scale = np.maximum(np.abs(ref).max(axis=0), 1e-30)
    return float((np.abs(x - ref).max(axis=0) / scale).max())


def _reference(a, b, lower):
    from scipy.sparse.linalg import spsolve_triangular

    return spsolve_triangular(a.to_scipy().tocsr(), b, lower=lower)


def _timed_solve(solver, b):
    """First call (compile + run) and a second call (run only); returns
    the result and both wall times."""
    import numpy as np

    t0 = time.perf_counter()
    x = np.asarray(solver.solve(b))
    t1 = time.perf_counter()
    x2 = np.asarray(solver.solve(b))
    t2 = time.perf_counter()
    check(np.array_equal(x, x2), "repeat solve changed bits")
    return x, t1 - t0, t2 - t1


class Smoke:
    def __init__(self, args):
        self.args = args
        self.size = SIZES[args.size]
        self.ok = True
        self.interpreted = False
        self.device = None
        self._factors = {}
        from repro.pipeline import PlanCache

        # one cache for every phase: the pcg and serve phases reuse the
        # solve phase's plans (same pattern, same options)
        self.cache = PlanCache()

    # ------------------------------------------------------------ harness
    def run(self, name, fn):
        t0 = time.perf_counter()
        try:
            info = fn() or {}
            passed = True
        except Exception as e:  # reported below and fails the run
            traceback.print_exc()
            info = {"error": f"{type(e).__name__}: {e}"[:500]}
            passed = False
        line = {
            "phase": name,
            "pass": passed,
            "wall_s": round(time.perf_counter() - t0, 3),
            **info,
        }
        print(json.dumps(line, default=str), flush=True)
        self.ok &= passed
        return passed

    def factor(self, nx):
        """(A, L, L^T) for the 3-D Poisson matrix on an nx^3 grid."""
        if nx not in self._factors:
            from repro.sparse import transpose_csr
            from repro.sparse.generators import poisson3d_matrix
            from repro.sparse.ichol import ichol0

            A = poisson3d_matrix(nx)
            L = ichol0(A)
            self._factors[nx] = (A, L, transpose_csr(L))
        return self._factors[nx]

    def rng(self, salt):
        import numpy as np

        return np.random.default_rng([self.args.seed, salt])

    @property
    def on_tpu(self):
        return self.device is not None and self.device["platform"] == "tpu"

    # ------------------------------------------------------------- phases
    def phase_device(self):
        import jax

        devs = jax.devices()
        self.device = {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        }
        check(
            self.on_tpu or self.args.size == "rehearsal",
            f"platform is {self.device['platform']!r}, not 'tpu'",
        )
        check(
            len(devs) >= self.args.chips,
            f"{self.args.chips} chips requested, {len(devs)} visible",
        )
        return dict(self.device)

    def phase_solve(self):
        import numpy as np

        from repro.pipeline import TriangularSolver

        nx, m = self.size["nx"], self.size["n_rhs"]
        t0 = time.perf_counter()
        A, L, LT = self.factor(nx)
        t_factor = time.perf_counter() - t0
        n = L.n_rows
        t0 = time.perf_counter()
        lo = TriangularSolver.plan(
            L, strategy="auto", backend="scan", cache=self.cache
        )
        up = TriangularSolver.plan(
            LT, strategy="auto", lower=False, backend="scan",
            cache=self.cache,
        )
        t_plan = time.perf_counter() - t0
        self.lo = lo
        rng = self.rng(1)
        b1 = rng.standard_normal(n)
        B = rng.standard_normal((n, m))
        out = {"n": n, "factor_nnz": L.nnz, "ichol_s": round(t_factor, 3),
               "inspector_s": round(t_plan, 3)}
        compile_s = 0.0
        for name, solver, a, lower in (
            ("lower", lo, L, True), ("upper", up, LT, False)
        ):
            for tag, b in (("rhs1", b1), (f"rhs{m}", B)):
                x, first, again = _timed_solve(solver, b)
                err = _rel_err(x, _reference(a, b, lower))
                check(err <= TOL, f"{name} {tag}: rel err {err:.3e} > {TOL}")
                out[f"{name}_{tag}_relerr"] = err
                out[f"{name}_{tag}_solve_s"] = round(again, 4)
                compile_s += max(first - again, 0.0)
            if name == "lower":
                x_lo = x
        # refactorization: new values, same pattern, device-side refresh
        L2 = dataclasses.replace(
            L, data=L.data * self.rng(2).uniform(0.9, 1.1, L.nnz)
        )
        lo.numeric_update(L2)
        x2 = np.asarray(lo.solve(B))
        err = _rel_err(x2, _reference(L2, B, True))
        check(err <= TOL, f"numeric_update: rel err {err:.3e} > {TOL}")
        check(not np.array_equal(x2, x_lo), "numeric_update changed nothing")
        out["updated_relerr"] = err
        lo.numeric_update(L)  # restore: later phases share this plan
        check(
            np.array_equal(np.asarray(lo.solve(B)), x_lo),
            "restoring the values did not restore the bits",
        )
        st = lo.exec_plan.stats()
        out["compile_s"] = round(compile_s, 3)
        out["strategy"] = lo.strategy
        out["mode"] = lo.info()["mode"]
        out["plan"] = {
            k: st[k]
            for k in ("n_steps", "n_supersteps", "k", "W",
                      "row_slot_utilization", "nnz_slot_utilization",
                      "bytes_streamed")
        }
        return out

    def phase_pcg(self):
        import numpy as np

        from repro.solver import pcg_ichol

        A, _, _ = self.factor(self.size["nx"])
        b = self.rng(3).standard_normal(A.n_rows)
        t0 = time.perf_counter()
        x, iters, relres, info = pcg_ichol(
            A, b, strategy="auto", tol=PCG_TOL, cache=self.cache
        )
        wall = time.perf_counter() - t0
        r = b - A.to_scipy() @ np.asarray(x, np.float64)
        true_relres = float(np.linalg.norm(r) / np.linalg.norm(b))
        check(relres <= PCG_TOL, f"relres {relres:.3e} > {PCG_TOL}")
        check(true_relres <= 1e-4, f"true relres {true_relres:.3e}")
        return {
            "n": A.n_rows,
            "iterations": iters,
            "relres": relres,
            "true_relres": true_relres,
            "time_to_tol_s": round(wall, 3),
            "compile_s": None,  # inside time_to_tol_s, not separated
            "fwd_strategy": info["fwd_strategy"],
            "cache": info.get("cache"),
        }

    def phase_serve(self):
        import numpy as np

        from repro.autotune.corpus import corpus_entry
        from repro.serve import SolveService
        from repro.serve.service import direct_reference

        _, L, _ = self.factor(self.size["nx"])
        mats = [L] + [corpus_entry(c).matrix() for c in CORPUS_PATTERNS]
        # the factor's scheduler is the one the solve phase's auto
        # selection picked; the corpus patterns select their own
        strat = getattr(getattr(self, "lo", None), "strategy", "growlocal")
        out = {}
        for mode in ("continuous", "microbatch"):
            svc = SolveService(mode=mode, cache=self.cache)
            try:
                fps = [svc.register(mats[0], strategy=strat)] + [
                    svc.register(a) for a in mats[1:]
                ]
                n_req = self.size["n_requests"]
                jobs = [None] * n_req

                def client(c, n_clients=4):
                    rng = self.rng(100 + c)
                    for i in range(c, n_req, n_clients):
                        j = i % len(fps)
                        b = rng.standard_normal(mats[j].n_rows)
                        jobs[i] = (svc.submit(fps[j], b), b)

                t0 = time.perf_counter()
                threads = [
                    threading.Thread(target=client, args=(c,))
                    for c in range(4)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                check(None not in jobs, "a client thread failed to submit")
                errors = rejected = 0
                results = []
                for ticket, b in jobs:
                    if ticket.rejected:
                        rejected += 1
                        continue
                    try:
                        results.append((ticket, b, ticket.result(600)))
                    except Exception:
                        traceback.print_exc()
                        errors += 1
                served_s = time.perf_counter() - t0
                mismatches = sum(
                    not np.array_equal(
                        x,
                        direct_reference(
                            t.served_by, b, t.batch_width, t.batch_position
                        ),
                    )
                    for t, b, x in results
                )
            finally:
                close = svc.close(timeout=120)
            out[mode] = {
                "requests": n_req,
                "errors": errors,
                "rejected": rejected,
                "bitwise_mismatches": mismatches,
                "served_s": round(served_s, 3),
                "workers_alive": len(close["workers_alive"]),
            }
            check(
                errors == 0 and rejected == 0 and mismatches == 0
                and len(results) == n_req and not close["workers_alive"],
                f"{mode}: {out[mode]}",
            )
        return out

    def phase_pallas(self):
        import numpy as np

        from repro.pipeline import TriangularSolver

        _, L, _ = self.factor(self.size["nx"])
        lo = getattr(self, "lo", None)
        check(lo is not None, "needs the solve phase's scan solver")
        # the solve phase's auto pick, bound to the kernel in bulk mode
        options = lo.selection.options.replace(slack=0)
        t0 = time.perf_counter()
        pk = TriangularSolver.plan(
            L, strategy=lo.strategy, options=options, backend="pallas"
        )
        t_plan = time.perf_counter() - t0
        desc = pk.bound.describe()
        self.interpreted |= bool(desc["interpret"])
        check(
            desc["interpret"] is (not self.on_tpu),
            f"interpret={desc['interpret']} on {self.device['platform']}",
        )
        rng = self.rng(4)
        n, m = L.n_rows, self.size["n_rhs"]
        out = {"n": n, "interpret": desc["interpret"],
               "inspector_s": round(t_plan, 3), "k": desc["k"],
               "W": desc["W"], "n_steps": desc["n_steps"]}
        compile_s = 0.0
        for tag, b in (("rhs1", rng.standard_normal(n)),
                       (f"rhs{m}", rng.standard_normal((n, m)))):
            x, first, again = _timed_solve(pk, b)
            err = _rel_err(x, _reference(L, b, True))
            check(err <= TOL, f"{tag}: rel err {err:.3e} > {TOL}")
            x_scan = np.asarray(lo.solve(b))
            out[f"{tag}_relerr"] = err
            out[f"{tag}_solve_s"] = round(again, 4)
            out[f"{tag}_max_abs_diff_vs_scan"] = float(
                np.abs(x - x_scan).max()
            )
            out[f"{tag}_bitwise_vs_scan"] = bool(np.array_equal(x, x_scan))
            compile_s += max(first - again, 0.0)
        out["compile_s"] = round(compile_s, 3)
        return out

    def phase_sharded(self):
        """shard="model" and shard="rows" on a (1, 4) mesh against the
        same plan solved by the scan backend on one chip."""
        import jax
        import numpy as np

        from repro.pipeline import TriangularSolver
        from repro.sparse.generators import narrow_band_lower

        n, m = self.size["shard_n"], self.size["n_rhs"]
        L = narrow_band_lower(n, 0.12, 8, seed=self.args.seed + 3)
        # the mesh a user builds (README "Sharded solves")
        mesh = jax.make_mesh((1, 4), ("data", "model"))
        # one schedule core per model-axis device
        kw = dict(strategy="growlocal", k=4, cache=self.cache)
        t0 = time.perf_counter()
        one = TriangularSolver.plan(L, backend="scan", **kw)
        t_plan = time.perf_counter() - t0
        rng = self.rng(5)
        b1 = rng.standard_normal(n)
        B = rng.standard_normal((n, m))
        refs = {"rhs1": _reference(L, b1, True),
                f"rhs{m}": _reference(L, B, True)}
        base = {"rhs1": np.asarray(one.solve(b1)),
                f"rhs{m}": np.asarray(one.solve(B))}
        out = {"n": n, "nnz": L.nnz, "inspector_s": round(t_plan, 3),
               "n_supersteps": one.n_supersteps,
               "n_steps": one.exec_plan.n_steps}
        for shard in ("model", "rows"):
            t0 = time.perf_counter()
            s = TriangularSolver.plan(
                L, backend="distributed", mesh=mesh, shard=shard, **kw
            )
            res = {"bind_s": round(time.perf_counter() - t0, 3)}
            compile_s = 0.0
            for tag, b in (("rhs1", b1), (f"rhs{m}", B)):
                x, first, again = _timed_solve(s, b)
                err = _rel_err(x, refs[tag])
                check(err <= TOL, f"{shard} {tag}: rel err {err:.3e}")
                res[f"{tag}_relerr"] = err
                res[f"{tag}_solve_s"] = round(again, 4)
                res[f"{tag}_bitwise_vs_one_chip"] = bool(
                    np.array_equal(x, base[tag])
                )
                compile_s += max(first - again, 0.0)
            res["compile_s"] = round(compile_s, 3)
            res["exchange"] = s.bound.describe()["exchange"]
            out[shard] = res
        return out


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter
    )
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--size", choices=tuple(SIZES), default="full")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    smoke = Smoke(args)
    if smoke.run("device", smoke.phase_device):
        if args.chips == 4:
            phases = [("sharded", smoke.phase_sharded)]
        else:
            phases = [
                ("solve", smoke.phase_solve),
                ("pcg", smoke.phase_pcg),
                ("serve", smoke.phase_serve),
                ("pallas", smoke.phase_pallas),
            ]
        for name, fn in phases:
            smoke.run(name, fn)
    ok = smoke.ok and smoke.on_tpu and not smoke.interpreted
    print(json.dumps({"ok": ok, "device": smoke.device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
