#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the accelerator it starts on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It builds the cell's inputs from the seed, plans and warms up the solver
(set-up), measures for ``--seconds``, compares a sample of the window's
answers with the plain reference, and prints one JSON line last on standard
output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics, from a
profiler trace of the window), ``device``, and ``checks`` (each number
compared, with its limit), with ``breakdown`` when traced. The checks are
also the last lines on standard error.

It exits non-zero, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for, or where the program is not beside it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
for p in (REPO / "src", REPO):  # the system under test, and this package
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import harness, roofline  # noqa: E402
from bench.spec import Spec  # noqa: E402


class NoAccelerator(RuntimeError):
    pass


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices_for(jax, chips: int, require_accelerator: bool):
    devs = jax.devices()
    if require_accelerator:
        if devs[0].platform != "tpu":
            raise NoAccelerator(
                f"JAX found no TPU (platform {devs[0].platform!r})")
        if len(devs) < chips:
            raise NoAccelerator(
                f"the cell asks for {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def checks(rec: dict, limits: dict) -> dict:
    """Each number compared, beside its limit: the widest relative error of
    a sampled answer (at most the limit), the requests never answered (at
    most 0) and the answers compared (at least the limit). A number that is
    not finite is reported as null, and fails."""
    err = rec["checks"]["rel_err"]
    return {
        "rel_err": {"value": err if math.isfinite(err) else None,
                    "limit": limits["rel_err"]},
        "unanswered": {"value": rec["failed"], "limit": 0},
        "compared": {"value": rec["checks"]["answers_compared"],
                     "limit": limits["min_compared"]},
    }


def passed(chk: dict) -> bool:
    err = chk["rel_err"]["value"]
    return (err is not None and err <= chk["rel_err"]["limit"]
            and chk["unanswered"]["value"] <= chk["unanswered"]["limit"]
            and chk["compared"]["value"] >= chk["compared"]["limit"])


def main(argv=None, *, root: Path = REPO, require_accelerator: bool = True):
    args = parse(argv)
    spec = Spec(root)
    cell = spec.cell(args.workload)
    config = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    limits = spec.limits(cell["name"])
    metrics = spec.metrics(cell["name"], bool(args.trace))
    readers = {m["name"]: spec.reader(m, bool(args.trace)) for m in metrics}
    driver = spec.plugin("drivers", traffic["driver"])
    generator = spec.plugin("generators", config["matrix"]["generator"])

    import jax

    try:
        devices = devices_for(jax, int(cell["chips"]), require_accelerator)
    except NoAccelerator as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 3
    jax.config.update("jax_compilation_cache_dir",
                      harness.checkout_cache_dir(root))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    kind = devices[0].device_kind
    ctx = SimpleNamespace(
        args=args, cell=cell, config=config, traffic=traffic,
        generator=generator, devices=devices, t_start=T_START,
        compiles=harness.CompileCounter(),
        peak=roofline.peaks(kind) if args.trace else None,
    )
    rec = driver.run(ctx)

    values = {}
    for m in metrics:
        v = readers[m["name"]](rec)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    chk = checks(rec, limits)
    ok = passed(chk) and rec["attempted"] > 0
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    result = {"correct": bool(ok), "attempted": int(rec["attempted"]),
              "failed": int(rec["failed"]), "metrics": values,
              "device": device}
    if args.trace:
        tr = rec["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["checks"] = chk

    print(json.dumps({"plans": rec.get("plans"),
                      "compiles_in_window": ctx.compiles.count,
                      "window": {k: v for k, v in rec["window"].items()
                                 if not isinstance(v, list)},
                      "trace": {k: v for k, v in rec.get("trace", {}).items()
                                if not isinstance(v, list)}},
                     default=str), file=sys.stderr)
    for name, c in chk.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.exit(code)
