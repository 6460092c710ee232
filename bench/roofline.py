"""The least time a triangular solve could take on a chip.

The work is counted from the matrix alone (n, its entries, the number of
right-hand sides m), never from the program's plan, so that a change to
padding, schedule or backend moves the share of the roofline and cannot move
the count. For one solve with m right-hand sides in float32:

- bytes = 8 nnz (a float32 value and an int32 column per entry)
  + 4 (n + 1) (row pointers) + 8 m n (b read, x written);
- flops = m (2 (nnz - n) + n): a multiply and an add per off-diagonal
  entry, a division per row.

The least time is the larger of bytes over the chip's HBM bandwidth and
flops over its peak rate; the peaks come from ``peaks.json`` by the device
kind that JAX reports. A device that is not in the table is an error.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def solve_work(n: int, nnz: int, m: int) -> dict:
    return {
        "bytes": 8 * nnz + 4 * (n + 1) + 8 * m * n,
        "flops": m * (2 * (nnz - n) + n),
    }


def peaks(device_kind: str, path: Path | None = None) -> dict:
    table = json.loads(Path(path or PEAKS).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in peaks.json; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]


def least_seconds(work: dict, peak: dict) -> tuple:
    """``(seconds, bound)``: the least time for ``work`` and which of
    ``"bytes"`` or ``"flops"`` sets it."""
    t_bytes = work["bytes"] / float(peak["hbm_bytes_per_s"])
    t_flops = work["flops"] / float(peak["flops_per_s"])
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
