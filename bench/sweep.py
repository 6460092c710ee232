#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate whose
backlog does not grow over the window.

    python bench/sweep.py --workload er_100k.serve --seed 5 --seconds 10 \\
        --rates 100,150,200,250,300

One process, one set-up, then one window per rate with the cell's traffic
but that rate. For each rate it prints one JSON line: completed requests
per second, latency percentiles from due time, how late the generator ran,
and the median latency of the window's first and last quarter of requests
(a backlog that grows shows as a last quarter far above the first).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

REPO = Path(__file__).resolve().parents[1]
for p in (REPO / "src", REPO):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402
from bench.drivers.open_loop import Server  # noqa: E402
from bench.run import devices_for  # noqa: E402
from bench.spec import Spec  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    spec = Spec(REPO)
    cell = spec.cell(args.workload)
    import jax

    devices = devices_for(jax, int(cell["chips"]), True)
    jax.config.update("jax_compilation_cache_dir",
                      harness.checkout_cache_dir(REPO))
    ctx = SimpleNamespace(
        args=args, config=spec.config(cell["config"]),
        traffic=spec.traffic(cell["traffic"]), devices=devices,
        generator=spec.plugin("generators",
                              spec.config(cell["config"])["matrix"]["generator"]))
    server = Server(ctx)
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            t = time.perf_counter()
            w = server.window(rate, args.seconds, args.seed + i)
            lat = np.asarray(w["latencies_s"]) * 1e3
            q = max(len(lat) // 4, 1)
            print(json.dumps({
                "rate_hz": rate,
                "completed_per_s": len(lat) / (time.perf_counter() - t),
                "failed": w["failed"],
                "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "p99_ms": float(np.percentile(lat, 99)),
                "first_quarter_p50_ms": float(np.median(lat[:q])),
                "last_quarter_p50_ms": float(np.median(lat[-q:])),
                "late_mean_ms": w["late_mean_s"] * 1e3,
                "batch_fill": w["batch_fill"],
            }), flush=True)
    finally:
        server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
