"""Telemetry-core tests: span nesting, ring eviction, disabled no-op,
spans on the JAX profiler's trace (engine spans, ``python.gc``), the
once-per-call (not once-per-trace) regression, the bounded attention
dispatch stream, the serving SLO percentiles, and the summarize CLI smoke
on a trace emitted by a real engine run."""

import gc
import glob
import os
import pathlib
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import telemetry as tel
from repro.core.telemetry import jaxmon
from repro.core.telemetry.recorder import NOOP_SPAN
from repro.models import attention as A
from repro.models import transformer as T
from repro.serving import Request, ServingEngine
from repro.serving.trace import latency_summary, synthetic_trace

CFG = get_config("granite-3-8b", smoke=True)


@pytest.fixture
def telem():
    """Fresh in-memory recorder for the test; restores the env default
    (off, unless REPRO_TELEMETRY is set) afterwards."""
    rec = tel.configure("on")
    yield rec
    tel.configure(os.environ.get(tel.ENV))


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.PRNGKey(0))


# --------------------------------------------------------------------------
# core: spans, ring, no-op
# --------------------------------------------------------------------------
def test_span_nesting_records_parent(telem):
    with tel.span("outer", proc="t") as outer:
        with tel.span("inner", proc="t"):
            with tel.span("leaf", proc="t"):
                pass
    spans = {e["name"]: e for e in telem.event_list()
             if e["kind"] == "span"}
    assert set(spans) == {"outer", "inner", "leaf"}
    assert spans["outer"]["parent"] is None
    assert spans["inner"]["parent"] == spans["outer"]["sid"] == outer.sid
    assert spans["leaf"]["parent"] == spans["inner"]["sid"]
    # children close before parents: dur nests
    assert spans["outer"]["dur"] >= spans["inner"]["dur"] >= \
        spans["leaf"]["dur"] >= 0.0
    # instants inherit the enclosing span as parent
    with tel.span("p") as p:
        tel.instant("mark")
    mark = [e for e in telem.event_list() if e["name"] == "mark"][0]
    assert mark["parent"] == p.sid


def test_ring_buffer_cap_evicts_oldest():
    rec = tel.configure("on", capacity=5)
    try:
        for i in range(12):
            tel.instant(f"e{i}")
        events = rec.event_list()
        assert len(events) == 5
        assert [e["name"] for e in events] == [f"e{i}" for i in range(7, 12)]
        assert rec.dropped == 7
        snap = rec.snapshot()
        assert snap["events_dropped"] == 7
        # aggregates never evict: counters survive ring churn
        tel.counter("c")
        for i in range(10):
            tel.instant("spam")
        assert rec.snapshot()["counters"]["c"] == 1.0
    finally:
        tel.configure(os.environ.get(tel.ENV))


def test_disabled_mode_is_noop():
    tel.configure("off")
    assert not tel.enabled() and tel.recorder() is None
    # shared stateless context manager — no per-call allocation
    assert tel.span("a", proc="x", k=1) is tel.span("b") is NOOP_SPAN
    with tel.span("a"):
        tel.instant("i")
        tel.counter("c")
    assert tel.events() == [] and tel.snapshot() == {}
    rec = tel.configure("on")
    tel.instant("now-recording")
    assert len(rec.event_list()) == 1
    tel.configure(os.environ.get(tel.ENV))


def test_configure_rejects_bad_mode():
    tel.configure("off")
    with pytest.raises(ValueError):
        tel.configure("yes-please")
    with pytest.raises(ValueError):
        tel.configure("jsonl:")
    assert not tel.enabled()

# --------------------------------------------------------------------------
# the profiler sink: spans on the trace's /host:CPU plane
# --------------------------------------------------------------------------
def _profile(tmp_path, body):
    """Run ``body`` under a JAX profiler session; return the host events of
    the trace as (line, name, start_ns, end_ns, stats) tuples."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    with warnings.catch_warnings():   # jaxlib's event_stats type warns
        warnings.simplefilter("ignore", DeprecationWarning)
        return [(line.name, e.name, e.start_ns, e.start_ns + e.duration_ns,
                 dict(e.stats))
                for plane in data.planes if plane.name == "/host:CPU"
                for line in plane.lines for e in line.events]


def _named(events, name):
    return [e for e in events if e[1] == name]


def _inside(inner, outer):
    return inner[0] == outer[0] and outer[2] <= inner[2] <= inner[3] <= \
        outer[3]


@pytest.mark.parametrize("ring", ["off", "on"])
def test_spans_land_on_the_profiler_trace(ring, tmp_path):
    rec = tel.configure(ring)
    try:
        def body():
            with tel.span("t.outer", proc="t", uid=7, kind="x"):
                with tel.span("t.inner", proc="t", step=3):
                    jax.block_until_ready(jnp.ones(4) + 1.0)
        events = _profile(tmp_path, body)
        after = tel.span("t.after")
    finally:
        tel.configure(os.environ.get(tel.ENV))
    outer, = _named(events, "t.outer")
    inner, = _named(events, "t.inner")
    assert outer[4] == {"uid": 7, "kind": "x"} and inner[4] == {"step": 3}
    assert _inside(inner, outer)
    if ring == "on":            # the ring records as before, beside it
        assert [e["name"] for e in rec.event_list()] == ["t.inner",
                                                         "t.outer"]
        assert after is not NOOP_SPAN
    else:                       # no profiler, no ring: the shared no-op
        assert after is NOOP_SPAN


def test_gc_collection_is_a_profiler_span(tmp_path):
    events = _profile(tmp_path, lambda: gc.collect(2))
    spans = _named(events, tel.GC_SPAN)
    assert spans and any(e[4] == {"generation": 2} for e in spans)


# --------------------------------------------------------------------------
# exporters
# --------------------------------------------------------------------------
def test_jsonl_round_trip_and_summary(telem, tmp_path):
    for i in range(10):
        with tel.span("op", proc="t", i=i):
            pass
    tel.counter("hits", 3)
    path = tmp_path / "trace.jsonl"
    n = tel.write_jsonl(str(path), telem, meta={"note": "test"})
    assert n == len(telem.event_list())
    doc = tel.read_events(str(path))
    assert doc["header"]["schema"] == tel.SCHEMA
    assert doc["header"]["note"] == "test"
    assert doc["footer"]["counters"] == {"hits": 3.0}
    summary = tel.summarize_file(str(path))
    s = summary["spans"]["op"]
    assert s["count"] == 10
    assert s["p50_ms"] <= s["p95_ms"] <= s["p99_ms"]
    assert s["total_ms"] >= s["p99_ms"]


def test_percentile_matches_numpy():
    vals = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0, 2.0, 6.0]
    for q in (0, 25, 50, 90, 95, 99, 100):
        assert tel.percentile(vals, q) == pytest.approx(
            float(np.percentile(vals, q)))
    assert tel.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        tel.percentile([], 50)


# --------------------------------------------------------------------------
# trace-time safety: execution events per call, compile events per trace
# --------------------------------------------------------------------------
def test_instrumented_jit_emits_once_per_call_not_per_trace():
    # input built (and synced) BEFORE counting starts, so only f's own
    # compilation can land in the compile counter
    x = jnp.arange(8, dtype=jnp.float32)
    jax.block_until_ready(x)

    @jax.jit
    def f(v):
        return v * 2.0 + 1.0

    rec = tel.configure("on")
    try:
        for _ in range(3):
            with tel.span("exec", proc="t"):
                jax.block_until_ready(f(x))
        events = rec.event_list()
        counters = rec.snapshot()["counters"]
    finally:
        tel.configure(os.environ.get(tel.ENV))
    execs = [e for e in events
             if e["kind"] == "span" and e["name"] == "exec"]
    assert len(execs) == 3                    # once per CALL
    # ... while jax compiled (and traced) the function exactly once
    assert counters[jaxmon.COMPILE_COUNTER] == 1
    compile_spans = [e for e in events
                     if e["kind"] == "span" and e["name"] == "jax.compile"]
    assert len(compile_spans) == 1


# --------------------------------------------------------------------------
# attention dispatch stream (the _DISPATCH_LOG lossiness fix)
# --------------------------------------------------------------------------
def test_dispatch_stream_keeps_concurrent_records():
    A.reset_dispatch_log()
    # two "engines" (or two benchmark rows) tracing back to back — the old
    # dict-keyed-by-kind log kept only the last writer per kind
    A._log("decode", backend="xla", tuning="n/a", params={})
    A._log("prefill", backend="xla", tuning="n/a", params={})
    A._log("decode", backend="pallas_interpret", tuning="miss-default",
           params={"bkv": 64})
    recs = A.dispatch_records()
    assert [r["kind"] for r in recs] == ["decode", "prefill", "decode"]
    assert [r["backend"] for r in recs if r["kind"] == "decode"] == \
        ["xla", "pallas_interpret"]
    # the last-per-kind view is API-compatible with the old log
    log = A.dispatch_log()
    assert log["decode"]["backend"] == "pallas_interpret"
    assert log["decode"]["params"] == {"bkv": 64}
    assert log["prefill"]["backend"] == "xla"
    assert "kind" not in log["decode"]
    A.reset_dispatch_log()
    assert A.dispatch_log() == {} and A.dispatch_records() == []


def test_dispatch_stream_is_bounded():
    A.reset_dispatch_log()
    for i in range(A.DISPATCH_LOG_CAP + 10):
        A._log("decode", backend="xla", tuning="n/a", params={}, seq=i)
    recs = A.dispatch_records()
    assert len(recs) == A.DISPATCH_LOG_CAP
    assert recs[-1]["seq"] == A.DISPATCH_LOG_CAP + 9   # newest kept
    A.reset_dispatch_log()


def test_dispatch_flows_into_telemetry(telem):
    A.reset_dispatch_log()
    A._log("decode", backend="pallas_interpret", tuning="miss-default",
           params={}, fallback="why not")
    names = [e["name"] for e in telem.event_list()]
    assert "attn.dispatch" in names
    counters = telem.snapshot()["counters"]
    assert counters["attn.dispatch.decode.pallas_interpret"] == 1.0
    assert counters["attn.dispatch.fallback"] == 1.0
    A.reset_dispatch_log()


# --------------------------------------------------------------------------
# serving SLO percentiles (trace.py satellite)
# --------------------------------------------------------------------------
def test_latency_summary_empty_trace_is_explicit():
    assert latency_summary([]) == {"requests": 0, "submitted": 0,
                                   "unfinished": 0}
    # submitted-but-never-finished requests are counted, never hidden
    reqs = synthetic_trace(3, vocab_size=32)
    assert latency_summary(reqs) == {"requests": 0, "submitted": 3,
                                     "unfinished": 3}


def test_latency_summary_p99_and_itl():
    reqs = synthetic_trace(10, vocab_size=64, rate=100.0, seed=3)
    for i, r in enumerate(reqs):
        r.t_first_token = r.arrival_time + 0.01
        r.t_done = r.arrival_time + 0.1 + 0.01 * i
        r.t_tokens = [r.t_first_token + 0.005 * k for k in range(4)]
    lat = latency_summary(reqs)
    assert lat["requests"] == 10
    for metric in ("latency", "ttft", "itl"):
        p50, p95, p99 = (lat[f"p{q}_{metric}_s"] for q in (50, 95, 99))
        assert p50 <= p95 <= p99
    assert lat["p50_itl_s"] == pytest.approx(0.005)
    # gaps are per-request consecutive diffs
    assert reqs[0].inter_token_gaps() == pytest.approx([0.005] * 3)
    # without per-token stamps the itl keys are absent, not wrong
    for r in reqs:
        r.t_tokens = []
    lat = latency_summary(reqs)
    assert "p99_itl_s" not in lat and lat["p99_latency_s"] > 0


# --------------------------------------------------------------------------
# engine lifecycle + CLI smoke (tier-1: tiny synthetic engine run)
# --------------------------------------------------------------------------
def _run_engine(params, eng=None, n=3):
    eng = eng or ServingEngine(params, CFG, num_slots=2, cache_len=32,
                               prefill_len=8)
    reqs = [Request(uid=i,
                    prompt=np.arange(2 + i, 6 + i, dtype=np.int32),
                    max_new_tokens=4) for i in range(n)]
    done = eng.run(reqs)
    return {r.uid: list(r.generated) for r in done}


def test_engine_lifecycle_events_and_cli_smoke(params, tmp_path):
    rec = tel.configure("on")
    try:
        toks_on = _run_engine(params)
        events = rec.event_list()
        path = tmp_path / "engine_trace.jsonl"
        tel.write_jsonl(str(path), rec)
    finally:
        tel.configure(os.environ.get(tel.ENV))

    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    # full lifecycle, one per request
    for name in ("serving.enqueue", "serving.slot_assign",
                 "serving.first_token", "serving.finish"):
        assert len(by_name[name]) == 3, name
    assert len(by_name["serving.prefill"]) == 3
    assert by_name["serving.decode_step"], "no decode-step spans"
    # decode steps nest under engine steps, which nest under serving.run
    run_sid = by_name["serving.run"][0]["sid"]
    step_sids = {e["sid"] for e in by_name["serving.step"]}
    assert all(e["parent"] == run_sid for e in by_name["serving.step"])
    assert all(e["parent"] in step_sids
               for e in by_name["serving.decode_step"])
    # the step's batch and queue ride on the decode span's attributes
    steps = [e["attrs"] for e in by_name["serving.decode_step"]]
    assert [a["step"] for a in steps] == list(range(len(steps)))
    assert all(0 < a["active"] <= 2 and a["queued"] >= 0 for a in steps)
    # lifecycle ordering per request uid
    for uid in range(3):
        ts = {n: [e["ts"] for e in by_name[n]
                  if e.get("attrs", {}).get("uid") == uid]
              for n in ("serving.enqueue", "serving.slot_assign",
                        "serving.first_token", "serving.finish")}
        assert ts["serving.enqueue"][0] <= ts["serving.slot_assign"][0] \
            <= ts["serving.first_token"][0] <= ts["serving.finish"][0]

    # telemetry must not change sampled tokens: bitwise vs the off run
    assert not tel.enabled()
    toks_off = _run_engine(params)
    assert toks_on == toks_off

    # the CLI end of the pipeline: summarize the emitted trace
    env = dict(os.environ,
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""))
    out = subprocess.run(
        [sys.executable, "-m", "repro.core.telemetry", "summarize",
         str(path)],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr
    assert "serving.decode_step" in out.stdout
    assert "p99_ms" in out.stdout or "p99" in out.stdout
    assert "serving.requests_finished = 3" in out.stdout


def test_engine_spans_on_the_profiler_trace(params, tmp_path):
    assert not tel.enabled()
    toks_off = _run_engine(params)
    eng = ServingEngine(params, CFG, num_slots=2, cache_len=32,
                        prefill_len=8)
    before = dict(eng.stats)
    got = {}
    events = _profile(tmp_path, lambda: got.update(_run_engine(params, eng)))
    assert got == toks_off          # greedy tokens bitwise, profiler on/off
    grew = {k: eng.stats[k] - before[k] for k in eng.stats}
    steps = _named(events, "serving.decode_step")
    admits = _named(events, "serving.admit")
    assert len(steps) == grew["decode_steps"] > 0
    assert len(admits) == grew["prefill_calls"] == 3
    assert len(_named(events, "serving.submit")) == 3
    assert [e[4]["step"] for e in steps] == list(range(len(steps)))
    assert all(0 < e[4]["active"] <= 2 and "queued" in e[4] for e in steps)
    for child in ("serving.decode.dispatch", "serving.decode.wait",
                  "serving.decode.emit"):
        kids = _named(events, child)
        assert len(kids) == len(steps), child
        assert all(sum(_inside(k, s) for s in steps) == 1 for k in kids)
    prefills = _named(events, "serving.prefill")
    waits = _named(events, "serving.prefill.wait")
    assert len(prefills) == len(waits) == 3
    assert all(any(_inside(w, p) for p in prefills) for w in waits)
    assert all(any(_inside(p, a) for a in admits) for p in prefills)
    assert {e[4]["uid"] for e in admits} == {0, 1, 2}
    engine_steps = _named(events, "serving.step")
    assert all(any(_inside(s, t) for t in engine_steps) for s in steps)


def test_engine_programs_keep_the_names_the_mfu_readers_read(params):
    eng = ServingEngine(params, CFG, num_slots=2, cache_len=32,
                        prefill_len=8)
    toks = jnp.zeros((1, 8), jnp.int32)
    prefill = eng._prefill.lower(eng.params, toks, jnp.asarray([4], jnp.int32),
                                 np.int32(0), eng._base_key, eng.caches)
    decode = eng._decode.lower(eng.params, jnp.asarray(eng.tok_buf),
                               jnp.asarray(eng.pos_buf),
                               jnp.zeros((2, 2), jnp.uint32), eng.caches)
    root = pathlib.Path(__file__).resolve().parents[1]
    for lowered, module, reader in ((prefill, "jit_prefill_fn", "prefill_mfu"),
                                    (decode, "jit_decode_fn", "decode_mfu")):
        assert lowered.as_text().startswith(f"module @{module} "), module
        text = (root / "bench" / "metrics" / f"{reader}.py").read_text()
        assert f'"{module}"' in text, reader
