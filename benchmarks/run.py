"""Benchmark harness — one module per paper table. Prints human tables to
stdout and a ``name,us_per_call,derived`` CSV block at the end; with
``--json PATH`` the same rows are written as machine-readable JSON
(schema ``repro-bench-rows/v1``, shared with ``benchmarks.serve_load``)
to seed the BENCH trajectory.

  PYTHONPATH=src python -m benchmarks.run                   # all tables
  PYTHONPATH=src python -m benchmarks.run t71 t72           # subset
  PYTHONPATH=src python -m benchmarks.run t7x --json out.json
  PYTHONPATH=src python -m benchmarks.run t71 --trace trace.json

``--trace PATH`` runs the selected tables under ``repro.obs`` tracing
and writes a Chrome ``trace_event`` file (open in Perfetto / chrome
about:tracing) plus the per-span aggregate as ``obs.*`` CSV rows.
"""
from __future__ import annotations

import argparse
import time

TABLES = {
    "t71": ("table71_speedups", "Table 7.1 speed-ups over Serial"),
    "t72": ("table72_barriers", "Table 7.2 barrier reduction"),
    "t73": ("table73_funnel", "§7.3 Funnel coarsening ablation"),
    "t74": ("table74_reorder", "Table 7.3 reordering ablation"),
    "t75": ("table75_arch", "Table 7.4 executors/architectures"),
    "t76": ("table76_scaling", "Table 7.5 core scaling"),
    "t77": ("table77_amortization", "Table 7.6 amortization threshold"),
    "t78": ("table78_blocks", "Table 7.7 block-parallel scheduling"),
    "t7x": ("table7x_auto", "Auto-strategy vs best/worst fixed (corpus)"),
    "roofline": ("kernel_roofline", "Kernel roofline"),
}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "tables", nargs="*",
        help=f"table keys to run (default: all of {', '.join(TABLES)})",
    )
    ap.add_argument(
        "--json", metavar="PATH", default=None,
        help="also write every row as machine-readable JSON to PATH",
    )
    ap.add_argument(
        "--trace", metavar="PATH", default=None,
        help="trace the run with repro.obs and write a Chrome "
             "trace_event JSON to PATH (spans also appear as obs.* rows)",
    )
    args = ap.parse_args()
    unknown = [t for t in args.tables if t not in TABLES]
    if unknown:
        ap.error(f"unknown tables {unknown}; available: {list(TABLES)}")
    which = args.tables or list(TABLES)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    trace_buf = None
    if args.trace:
        from repro import obs

        trace_buf = obs.enable()
    csv_rows = []
    for key in which:
        mod_name, desc = TABLES[key]
        print(f"\n===== {key}: {desc} =====", flush=True)
        t0 = time.time()
        mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
        mod.run(csv_rows)
        print(f"[{key} done in {time.time()-t0:.1f}s]", flush=True)
    if trace_buf is not None:
        from repro import obs

        obs.disable()
        obs.export_chrome_trace(args.trace, trace_buf)
        csv_rows.extend(obs.metrics_rows(trace_buf))
        print(f"\n[trace: {len(trace_buf)} spans -> {args.trace}]")
    print("\n# CSV: name,us_per_call,derived")
    for name, val, derived in csv_rows:
        print(f"{name},{val},{derived}")
    if args.json:
        from benchmarks.common import write_json_rows

        write_json_rows(args.json, csv_rows, which)


if __name__ == "__main__":
    main()
