"""From a profiler trace to the numbers the per-layer metrics read.

``jax.profiler`` writes an ``.xplane.pb``; ``ProfileData`` reads it. On a TPU
each chip is a plane ``/device:TPU:<i>`` with two lines of device events:
``XLA Modules``, one event per program run, and ``XLA Ops``, one event per
operation run, every iteration of a loop included. The benchmark's own host
spans (``jax.profiler.TraceAnnotation`` named ``bench.*``) lie on the host
plane, on the same clock.

The profiler keeps a bounded number of device events (on a TPU v5e about
six million: some 0.8 s of a 100,000-row scan solve, 1.3 s of the 64^3
factor's). A scan solve runs some tens of operations per plan step, so a
trace of a busy chip can end, on both lines, long before the window does.
The reduction therefore ends the window where the device record ends,
when that is more than ``CUT_S`` before the window's own end, and reports
by how much (``cut_s``):

- busy: the union of a chip's program runs (``XLA Modules``; the op line
  where a plane has none) inside the window, averaged over the chips that
  ran anything; idle share is 1 - busy / window;
- program: the program with the most device time on the first chip, its
  share of that chip's busy time, and its runs that lie whole inside the
  window (a run cut by the end of the record is left out): how many, and
  their device seconds;
- device_ops: the operations that took most device time in the window,
  summed by short name (``<instruction> <opcode>``), from the op line;
- idle_gaps: the longest stretches of the window with no program running
  on the first chip, each named by the ``bench.*`` host span that covers
  most of it (``host:none`` where the host was in none).
"""
from __future__ import annotations

import glob
import os
import re
from array import array

import numpy as np

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES, OPS = "XLA Modules", "XLA Ops"
OP_NAME = re.compile(r"^%?(\S+) = .*?\b([a-z][a-z0-9-]*)\(")
CUT_S = 0.05  # a record that ends this much before the window was cut
WHOLE_S = 1e-3  # a run this long before the end of a cut record is whole
TOP = 10


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def short_name(name: str) -> str:
    """``%fusion.90 = f32[8,16]{...} fusion(...), kind=...`` becomes
    ``fusion.90 fusion``; a program's name stays as it is."""
    m = OP_NAME.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name


class Events:
    """One line's events: start and end in ns, and a short name each."""

    def __init__(self):
        self.start = array("d")
        self.end = array("d")
        self.name_id = array("l")
        self.names = {}
        self._ids = {}  # full name -> id of its short name

    def add(self, name: str, start: float, dur: float) -> None:
        self.start.append(start)
        self.end.append(start + dur)
        i = self._ids.get(name)
        if i is None:
            i = self._ids[name] = self.names.setdefault(short_name(name),
                                                        len(self.names))
        self.name_id.append(i)

    def by_name(self, lo, hi) -> dict:
        """Device time by short name, clipped to ``[lo, hi]``."""
        s = np.clip(np.asarray(self.start), lo, hi)
        e = np.clip(np.asarray(self.end), lo, hi)
        sums = np.bincount(np.asarray(self.name_id), weights=e - s,
                           minlength=len(self.names))
        return {name: float(sums[i]) for name, i in self.names.items()
                if sums[i] > 0}


def read_xplane(path: str):
    """``(device, host)``: per device plane ``{line: Events}`` for the module
    and op lines it has; and the host's ``bench.*`` spans as
    ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device, host = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (MODULES, OPS):
                    ev = Events()
                    for e in line.events:
                        ev.add(e.name, e.start_ns, e.duration_ns)
                    if len(ev.start):
                        lines[line.name] = ev
            if lines:
                device[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    return device, host


def merge(start, end):
    """The union of intervals, as sorted disjoint ``(starts, ends)``."""
    s = np.asarray(start, np.float64)
    e = np.asarray(end, np.float64)
    if s.size == 0:
        return s, e
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, s.size - 1)
    return s[first], reach[last]


def _union(ev: Events, lo, hi):
    s, e = np.asarray(ev.start), np.asarray(ev.end)
    s, e = np.clip(s, lo, hi), np.clip(e, lo, hi)
    keep = e > s
    return merge(s[keep], e[keep])


def _label(lo, hi, spans):
    best, best_key = "host:none", None
    for name, s, e in spans:
        if name == WINDOW:
            continue
        cover = min(e, hi) - max(s, lo)
        if cover <= 0:
            continue
        key = (cover, -(e - s))  # most of the gap, then the innermost
        if best_key is None or key > best_key:
            best, best_key = f"host:{name}", key
    return best


def reduce(device: dict, host: list) -> dict:
    """``window_s``, ``busy_s``, ``cut_s`` (seconds), the ``program``
    (name, share of busy time, whole runs, their seconds) and the ``device_ops`` and
    ``idle_gaps`` breakdowns, each at most ten ``[name, seconds]``."""
    windows = [(s, e) for name, s, e in host if name == WINDOW]
    every = [ev for lines in device.values() for ev in lines.values()]
    if not windows and not every:
        raise ValueError("the trace holds no window span and no device op")
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        lo = min(min(ev.start) for ev in every)
        hi = max(max(ev.end) for ev in every)
    record_end = max((max(ev.end) for ev in every), default=hi)
    cut = hi - record_end if hi - record_end > CUT_S * 1e9 else 0.0
    hi -= cut
    busy, first, program = [], None, None
    totals = {}
    for name in sorted(device):
        lines = device[name]
        whole = lines.get(MODULES) or lines[OPS]
        s, e = _union(whole, lo, hi)
        if not s.size:
            continue
        busy.append(float((e - s).sum()))
        if first is None:
            first = (s, e)
            program = _program(whole, lo, hi, cut > 0, busy[-1])
        for op, dur in lines.get(OPS, whole).by_name(lo, hi).items():
            totals[op] = totals.get(op, 0.0) + dur
    n_chips = max(len(busy), 1)
    gaps = []
    if first is not None:
        s, e = first
        g_lo = np.concatenate([[lo], e])
        g_hi = np.concatenate([s, [hi]])
        length = g_hi - g_lo
        for i in np.argsort(-length, kind="stable")[:TOP]:
            if length[i] <= 0:
                break
            gaps.append([_label(g_lo[i], g_hi[i], host),
                         float(length[i]) * 1e-9])
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n_chips * 1e-9,
        "cut_s": cut * 1e-9,
        "chips_busy": len(busy),
        "program": program,
        "device_ops": [[op, dur / n_chips * 1e-9] for op, dur in ops],
        "idle_gaps": gaps,
    }


def _program(ev: Events, lo, hi, cut: bool, busy: float) -> dict:
    """The name with the most device time in ``[lo, hi]``, its share of the
    chip's ``busy`` time there, and its runs that lie whole inside it. Where
    the record was cut (``hi`` is its end), a run counts as whole only where
    the record goes on ``WHOLE_S`` past it."""
    times = ev.by_name(lo, hi)
    name = max(times, key=times.get)
    s, e = np.asarray(ev.start), np.asarray(ev.end)
    last = hi - WHOLE_S * 1e9 if cut else hi
    inside = np.flatnonzero((np.asarray(ev.name_id) == ev.names[name])
                            & (s >= lo) & (e <= last))
    return {"name": name, "runs": int(inside.size),
            "seconds": float((e[inside] - s[inside]).sum()) * 1e-9,
            "share": times[name] / busy}


def reduce_file(path: str) -> dict:
    return reduce(*read_xplane(path))
