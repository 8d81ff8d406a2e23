"""The engine-span readers (``decode_host_ms``, ``admit_host_ms``) on traces
recorded here on the CPU: a ``bench.window`` annotation around the
program's spans, as a ``--trace 1`` run of a serving cell leaves them."""

import time

import jax
import pytest

import harness
import trace_reduce
from repro.core import telemetry as tel

WORKLOAD = "granite-chat-steady"


def spans_module():
    return harness.load_module("metrics", "decode_host_ms")


def read_both(r):
    return [harness.load_module("metrics", name).read(r)
            for name in ("decode_host_ms", "admit_host_ms")]


def record(trace_dir, body):
    """Run ``body`` under the profiler, writing where a run of the cell
    writes; return the reading the readers get (the window's length)."""
    out = trace_dir / WORKLOAD
    out.mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(out))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = spans_module().newest_trace()
    data = jax.profiler.ProfileData.from_file(path)
    windows = trace_reduce.host_spans(data, ("bench.window",))["bench.window"]
    if not windows:
        return {"trace": {"window_s": 0.0, "modules": {}}}
    (w0, w1), = windows
    return {"trace": {"window_s": (w1 - w0) * 1e-9, "modules": {}}}


def decode_step(host_s, wait_s):
    with tel.span("serving.decode_step", proc="engine", step=0):
        time.sleep(host_s)
        with tel.span("serving.decode.wait", proc="engine"):
            time.sleep(wait_s)


def admit(host_s, wait_s):
    with tel.span("serving.admit", proc="engine", uid=0):
        time.sleep(host_s)
        with tel.span("serving.prefill", proc="engine"):
            with tel.span("serving.prefill.wait", proc="engine"):
                time.sleep(wait_s)


def engine_window():
    decode_step(0.060, 0.0)          # before the window: not counted
    admit(0.060, 0.0)
    with jax.profiler.TraceAnnotation("bench.window"):
        with tel.span("serving.step", proc="engine", step=0):
            admit(0.002, 0.015)
            for _ in range(3):
                decode_step(0.004, 0.015)


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    return tmp_path


def test_readers_take_host_time_outside_the_waits(trace_dir, capsys):
    r = record(trace_dir, engine_window)
    decode_ms, admit_ms = read_both(r)
    # the host sleeps 4 ms per step and 2 ms per admission outside the
    # waits; the waits (15 ms) and the spans before the window (60 ms) do
    # not count
    assert 4.0 <= decode_ms < 15.0
    assert 2.0 <= admit_ms < 15.0
    err = capsys.readouterr().err
    assert "device idle by program span" in err
    assert "serving.decode_step" in err and "3 x" in err


def test_a_trace_of_another_window_reads_nothing(trace_dir):
    r = record(trace_dir, engine_window)
    r["trace"]["window_s"] += 2e-6
    assert read_both(r) == [None, None]


def test_a_program_without_the_spans_reads_nothing(trace_dir):
    def parent():
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.step"):
                time.sleep(0.002)
    assert read_both(record(trace_dir, parent)) == [None, None]


def test_no_trace_reads_nothing(trace_dir):
    assert read_both({"trace": {"window_s": 1.0, "modules": {}}}) == \
        [None, None]


def test_both_readers_parse_the_trace_once(trace_dir, monkeypatch):
    r = record(trace_dir, engine_window)
    calls = []
    real = jax.profiler.ProfileData.from_file
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        lambda p: calls.append(p) or real(p))
    assert None not in read_both(r)
    assert None not in read_both(r)
    assert len(calls) == 1


def test_idle_gaps_go_to_the_innermost_span_holding_them():
    m = spans_module()
    gaps = m._idle_gaps([(10, 20), (30, 40), (35, 45)], 0, 50)
    assert gaps == [(0, 10), (20, 30), (45, 50)]
    spans = [(2, 48, "serving.step", 0), (3, 8, "serving.admit", 0),
             (21, 29, "serving.decode_step", 0),
             (22, 26, "serving.decode.wait", 0)]
    mids = [5, 25, 27, 47.5, 49]
    assert m._holders(spans, mids) == [
        "serving.admit", "serving.decode.wait", "serving.decode_step",
        "serving.step", None]


def test_idle_table_names_bench_spans_and_host_where_no_program_span(
        capsys):
    m = spans_module()
    ms = 10 ** 7                                  # 10 ms in ns
    found = {"window": (0, 100 * ms), "window_s": 1.0,
             "spans": [(10 * ms, 30 * ms, "serving.step", 0),
                       (12 * ms, 14 * ms, "python.gc", 0)]}
    bench = [(0, 60 * ms, "bench.step", 0)]
    modules = [(35 * ms, 55 * ms, "jit_decode_fn", 0)]
    m._log(found, bench, [(30 * ms, 40 * ms), (60 * ms, 90 * ms)], modules,
           {"trace": {}})
    rows = {line.split()[0]: [line.split()[i] for i in (1, 3, 7)]
            for line in capsys.readouterr().err.splitlines()
            if line.endswith(" inside a program")}
    # gaps 0-30 (midpoint 15: serving.step; the gc span ended at 14),
    # 40-60 (bench.step alone; its midpoint inside a program), 90-100
    # (nothing)
    assert rows == {"serving.step": ["0.300000", "50.000", "0.000000"],
                    "bench.step": ["0.200000", "33.333", "0.200000"],
                    "host": ["0.100000", "16.667", "0.000000"]}
