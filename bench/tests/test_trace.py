"""The reduction from a profiler trace to busy time, idle share, device
time by name and idle gaps by host span."""
from pathlib import Path

import numpy as np
import pytest

from bench import trace

RECORDED = Path(__file__).parent / "data" / "tiny_tpu.xplane.pb"


def _events(*ops):
    ev = trace.Events()
    for name, start, dur in ops:
        ev.add(name, start, dur)
    return ev


def _chip(*ops, modules=None):
    """A device plane: its op line, and its module line if given."""
    lines = {trace.OPS: _events(*ops)}
    if modules is not None:
        lines[trace.MODULES] = _events(*modules)
    return lines


def test_merge_overlapping_and_nested():
    s, e = trace.merge([0, 5, 6, 30, 29], [10, 15, 7, 35, 31])
    assert s.tolist() == [0, 29]
    assert e.tolist() == [15, 35]
    s, e = trace.merge([], [])
    assert s.size == 0


def test_reduce_busy_idle_and_names():
    device = {"/device:TPU:0": _chip(("fusion.1", 0, 10), ("fusion.2", 5, 10),
                                     ("copy", 30, 5), ("fusion.1", 95, 20))}
    host = [("bench.window", 0, 100), ("bench.solve", 0, 40),
            ("bench.submit", 50, 60)]
    r = trace.reduce(device, host)
    assert r["window_s"] == pytest.approx(100e-9)
    # union inside the window: [0, 15] + [30, 35] + [95, 100]
    assert r["busy_s"] == pytest.approx(25e-9)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(15e-9)  # by name, in the window
    assert ops["fusion.2"] == pytest.approx(10e-9)
    gaps = r["idle_gaps"]
    assert gaps[0][0] == "host:bench.submit"  # [35, 95]: most under submit
    assert gaps[0][1] == pytest.approx(60e-9)
    assert gaps[1] == ["host:bench.solve", pytest.approx(15e-9)]


def test_reduce_averages_busy_over_chips():
    device = {"/device:TPU:0": _chip(("a", 0, 40)),
              "/device:TPU:1": _chip(("a", 0, 20))}
    r = trace.reduce(device, [("bench.window", 0, 100)])
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["chips_busy"] == 2


def test_host_only_trace(tmp_path):
    """A CPU trace has host spans and no device plane: no busy time."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    x = jnp.ones(8)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        with jax.profiler.TraceAnnotation("bench.solve"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    device, host = trace.read_xplane(trace.find_xplane(str(tmp_path)))
    assert device == {}
    assert {name for name, _, _ in host} >= {trace.WINDOW, "bench.solve"}
    r = trace.reduce(device, host)
    assert r["busy_s"] == 0.0 and r["window_s"] > 0


def test_short_names_of_hlo_ops():
    assert trace.short_name(
        "%fusion.90 = f32[8,16]{1,0:T(8,128)S(1)} fusion(f32[100001,16]{1,0}"
        " %get-tuple-element.247), kind=kCustom") == "fusion.90 fusion"
    assert trace.short_name(
        "%while.1 = (s32[]{:T(128)}, f32[8]{0}) while((s32[], f32[8]) "
        "%tuple.46), condition=%c") == "while.1 while"
    assert trace.short_name("jit__solve_scan_mrhs(1583)") == \
        "jit__solve_scan_mrhs(1583)"


def test_a_cut_record_ends_the_window():
    """The profiler kept the first 150 ms of a chip busy all through a
    400 ms window: the window ends where the record does, and the run the
    cut fell in is not a whole run of the program."""
    ms = 1_000_000  # ns
    modules = [("jit_solve(1)", 0, 50 * ms), ("jit_gather(2)", 50 * ms, ms),
               ("jit_solve(1)", 55 * ms, 50 * ms),
               ("jit_solve(1)", 105 * ms, 45 * ms)]
    ops = [("%while.1 = f32[8]{0} while(f32[8]{0} %t)", 0, 50 * ms),
           ("%while.1 = f32[8]{0} while(f32[8]{0} %t)", 55 * ms, 50 * ms),
           ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %t)", 105 * ms, 45 * ms)]
    host = [("bench.window", 0, 400 * ms), ("bench.solve", 0, 400 * ms)]
    r = trace.reduce({"/device:TPU:0": _chip(*ops, modules=modules)}, host)
    assert r["cut_s"] == pytest.approx(0.25)
    assert r["window_s"] == pytest.approx(0.15)
    assert r["busy_s"] == pytest.approx(0.146)
    # the gather's 1 ms is the rest of the busy time
    assert r["program"] == {"name": "jit_solve(1)", "runs": 2,
                            "seconds": pytest.approx(0.1),
                            "share": pytest.approx(145 / 146)}
    assert dict(r["device_ops"]) == {"while.1 while": pytest.approx(0.1),
                                     "fusion.3 fusion": pytest.approx(0.045)}
    assert r["idle_gaps"] == [["host:bench.solve", pytest.approx(0.004)]]
    # the same record inside a window that it covers is not cut, and every
    # run is whole
    host = [("bench.window", 0, 160 * ms)]
    r = trace.reduce({"/device:TPU:0": _chip(*ops, modules=modules)}, host)
    assert r["cut_s"] == 0.0 and r["window_s"] == pytest.approx(0.16)
    assert r["program"]["runs"] == 3
    assert r["idle_gaps"][0] == ["host:none", pytest.approx(0.01)]


def test_recorded_tpu_trace():
    """A trace recorded on a TPU v5e: three rounds of two small programs
    with a 2 ms host sleep between them, inside one window span."""
    device, host = trace.read_xplane(str(RECORDED))
    assert list(device) == ["/device:TPU:0"]
    r = trace.reduce(device, host)
    assert 0 < r["busy_s"] < r["window_s"]
    # the sleeps alone leave the device idle for at least 6 ms
    assert r["window_s"] - r["busy_s"] > 6e-3
    assert r["idle_gaps"][0][0] == "host:bench.solve"
    assert r["cut_s"] == 0.0
    assert r["program"]["runs"] == 3
    # one op of the warm-up lies before the window and is left out
    (lo, hi), = [(s, e) for name, s, e in host if name == trace.WINDOW]
    ops = device["/device:TPU:0"][trace.OPS]
    inside = sum(ops.by_name(lo, hi).values()) * 1e-9
    assert inside < sum(ops.by_name(-np.inf, np.inf).values()) * 1e-9
    assert np.isclose(sum(d for _, d in r["device_ops"]), inside)
