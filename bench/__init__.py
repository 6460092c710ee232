"""Chip benchmark of the triangular solver: one cell per run, driven by data.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once and prints one JSON result line.
Configurations, traffic mixes, correctness limits and metric readers are
files under this directory, found by the names in ``BENCHMARK.json``.
"""
