#!/usr/bin/env python3
"""The control of a cell's correctness check: the plain reference put in the
program's place, computed in bfloat16, the precision below the float32 that
the configurations state. It has to come out as not correct.

    python bench/control.py --workload ic0_64.precond --seeds 11,12,13

For each seed it builds the cell's matrices and right-hand sides exactly as
a run does, answers a sample of them (the cell's ``sample`` size) with the
bfloat16 reference, and judges those answers with a run's own comparison
(``run.checks`` against the float64 reference, then ``run.passed``): it
prints ``correct``, which has to be false, and each number beside its
limit. The benchmark's runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import harness, reference, run  # noqa: E402
from bench.spec import Spec  # noqa: E402


def readings(spec: Spec, workload: str, seed: int) -> dict:
    cell = spec.cell(workload)
    config = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    generator = spec.plugin("generators", config["matrix"]["generator"])
    ops = generator.operators(config["matrix"], [int(seed), 7])
    refs = harness.references(ops)
    m = int(traffic.get("rhs", 1))
    pool = harness.rhs_pool(seed, int(traffic["pool"]), ops[0][0].shape[0], m)
    pick = harness.rng(seed, 2).permutation(len(pool))[: int(traffic["sample"])]
    err = max(
        reference.rel_err(reference.chain_solve(refs, pool[j], "bfloat16"),
                          reference.chain_solve(refs, pool[j]))
        for j in pick)
    chk = run.checks({"failed": 0, "checks": {
        "rel_err": err, "answers_compared": len(pick)}},
        spec.limits(workload))
    return {"workload": workload, "seed": seed,
            "correct": run.passed(chk), "checks": chk}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    spec = Spec(REPO)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(spec, args.workload, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
