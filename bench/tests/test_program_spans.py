"""The ``program_span`` readers: the program's spans and request records,
split into set-up and window, and the four metrics read from them."""
import pytest

from bench import program_spans
from bench.spec import load
from bench.tests.tiny import REPO
from repro.obs import SpanRecord

READERS = ("batch_host_ms.serve", "service_ms.serve", "schedule_s",
           "bind_s")


def _reader(name):
    return load(REPO / "bench" / "layers" / f"{name}.py").read


def _span(name, t0, t1, tid=1, **args):
    return SpanRecord(name, name.split(".", 1)[0], tid, "t",
                      int(t0 * 1e9), int(t1 * 1e9), args)


@pytest.fixture(autouse=True)
def _program_tracing_off():
    """Loading a reader switches the program's tracing on, as in a traced
    run; a hand-built record ends no run, so switch it off again."""
    from repro import obs

    yield
    program_spans._buffer = None
    obs.disable()


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_without_an_obs_section(name):
    read = _reader(name)
    assert read({}) is None
    assert read({"serve": {}, "trace": {"window_s": 1.0}}) is None


HAND_BUILT = {"obs": {
    "window_start": 10.0,
    "setup": {"inspector.schedule": 4.5, "backend.bind": 0.25},
    "window": {"serve.microbatch": 30.0},
    "batches": [0.010, 0.020, 0.030],
    "requests": [[0.1, 0.12], [0.2, 0.14]],
    "counters": {},
}}


@pytest.mark.parametrize("name, value", [
    ("batch_host_ms.serve", 20.0),
    ("service_ms.serve", 130.0),
    ("schedule_s", 4.5),
    ("bind_s", 0.25),
])
def test_reader_value_on_a_hand_built_record(name, value):
    assert _reader(name)(HAND_BUILT) == pytest.approx(value)


def test_open_loop_window_is_its_requests():
    """Set-up's warm requests (ids 0, 1) and the window's (2..4): the
    window opens at its first request's submit; a batch's host seconds
    are its stack, dispatch and fulfil, not its wait."""
    spans = [
        _span("inspector.schedule", 0.0, 3.0),
        _span("inspector.schedule", 1.0, 2.0),  # nested: counted once
        _span("backend.bind", 3.0, 3.5),
        _span("serve.request", 4.0, 4.2, tid=0, id=0, batch=0, queue_s=0.1),
        _span("serve.request", 4.0, 4.2, tid=0, id=1, batch=0, queue_s=0.1),
        _span("serve.request", 10.0, 10.5, tid=0, id=2, batch=1,
              queue_s=0.2),
        _span("serve.request", 10.1, 10.5, tid=0, id=3, batch=1,
              queue_s=0.1),
        _span("serve.request", 10.6, 11.0, tid=0, id=4, batch=2,
              queue_s=0.1),
    ]
    for batch, t in ((0, 4.1), (1, 10.2), (2, 10.7)):
        spans += [
            _span("serve.batch.stack", t, t + 0.01, batch=batch),
            _span("serve.batch.dispatch", t + 0.01, t + 0.03, batch=batch),
            _span("serve.batch.wait", t + 0.03, t + 0.2, batch=batch),
            _span("serve.batch.fulfil", t + 0.2, t + 0.21, batch=batch),
        ]
    sec = program_spans.build({"driver": "open_loop", "attempted": 3},
                              spans, {"jit.trace.scan_mrhs": 5})
    assert sec["window_start"] == pytest.approx(10.0)
    assert sec["setup"]["inspector.schedule"] == pytest.approx(3.0)
    assert sec["setup"]["backend.bind"] == pytest.approx(0.5)
    assert "inspector.schedule" not in sec["window"]
    assert sec["batches"] == pytest.approx([0.04, 0.04])
    assert [q for q, _ in sec["requests"]] == pytest.approx([0.2, 0.1, 0.1])
    assert [s for _, s in sec["requests"]] == pytest.approx([0.3] * 3)
    assert sec["counters"] == {"jit.trace.scan_mrhs": 5}


def test_closed_loop_window_is_its_last_dispatches():
    """Two warm-up calls and three window calls of one solve each."""
    spans = [_span("backend.bind", 0.0, 1.0)] + [
        _span("executor.dispatch", t, t + 0.1) for t in (2, 3, 5, 6, 7)]
    sec = program_spans.build(
        {"driver": "closed_loop", "window": {"solves": 3}}, spans, {})
    assert sec["window_start"] == pytest.approx(5.0)
    assert sec["setup"]["executor.dispatch"] == pytest.approx(0.2)
    assert sec["window"]["executor.dispatch"] == pytest.approx(0.3)
    assert sec["batches"] == [] and sec["requests"] == []


def test_no_program_spans_leave_the_metrics_unread():
    """A program without the spans (an older one) reads nothing."""
    sec = program_spans.build({"driver": "open_loop", "attempted": 5},
                              [_span("serve.microbatch", 1.0, 2.0)], {})
    assert sec["window_start"] is None
    rec = {"obs": sec}
    for name in READERS:
        assert _reader(name)(rec) is None


def test_traced_serve_run_reports_the_program_spans(tmp_path, capsys,
                                                     monkeypatch):
    """A traced run of the small serve cell reports the four metrics, and
    the inspector's phases fit inside the benchmark's own clock."""
    import json

    from bench import roofline, run
    from bench.tests.tiny import add_tiny, copy_root

    root = add_tiny(copy_root(tmp_path))
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"devices": {"cpu": {
        "hbm_bytes_per_s": 1e11, "flops_per_s": 1e12}}}))
    monkeypatch.setattr(roofline, "PEAKS", peaks)
    argv = ["--workload", "er_tiny.serve", "--seed", "3000000041",
            "--seconds", "0.6", "--trace", "1"]
    assert run.main(argv, root=root, require_accelerator=False) == 0
    out, err = capsys.readouterr()
    res = json.loads(out.strip().splitlines()[-1])
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(READERS) <= set(m)
    assert 0 < m["schedule_s"] + m["bind_s"] <= m["inspector_s"]
    assert 0 < m["batch_host_ms.serve"]
    assert 0 < m["service_ms.serve"]
    diag = next(json.loads(line)["program_obs"] for line in err.splitlines()
                if line.startswith('{"program_obs"'))
    assert diag["requests"] == res["attempted"] - res["failed"]
    assert diag["dropped"] == 0


def test_untraced_run_leaves_program_tracing_off(tmp_path, capsys):
    from repro import obs

    from bench import run
    from bench.tests.tiny import add_tiny, copy_root

    root = add_tiny(copy_root(tmp_path))
    argv = ["--workload", "er_tiny.block16", "--seed", "3000000043",
            "--seconds", "0.3", "--trace", "0"]
    assert run.main(argv, root=root, require_accelerator=False) == 0
    assert not obs.is_enabled()
    assert program_spans._buffer is None
    assert "program_obs" not in capsys.readouterr().err
