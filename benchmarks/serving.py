"""Serving-engine SLO benchmark: Poisson rate sweep x cache layout x backend.

Drives the fixed-shape continuous-batching engine (v2) with Poisson-ish
synthetic arrival traces (repro/serving/trace.py) on a smoke-size model.
The sweep has three dimensions:

  * attention backend — the plain-XLA oracle first (the before), then the
    Pallas registry path (compiled on TPU, interpret elsewhere — the after);
  * ``cache_layout`` — ``contiguous`` (one (num_slots, cache_len) KV row
    per slot) vs ``paged`` (shared block pool + per-slot block tables);
  * arrival rate — each (backend, layout) engine serves the SAME request
    trace at several requests-per-second rates, so the row set shows how
    TTFT/ITL percentiles degrade as load approaches saturation.

One engine per (backend, layout) is compiled once — a warmup trace hits
every rung of the prefill bucket ladder plus the decode program, so the
compile count is bounded at ``len(PREFILL_BUCKETS) + 1`` per engine and the
timed runs must not retrace (the row is annotated `RETRACED` if one does;
``jax_compile_events_timed`` > 0 is the same signal machine-side).  Each
(backend, layout, rate) cell emits one row:

    serving[<backend>/<layout>@<rate>rps],<us_per_decode_step>,<tok/s +
    TTFT/latency/ITL p50/p95/p99 + attn dispatch provenance>

A row whose trace does not fully drain FAILS the benchmark (RuntimeError):
``latency_summary`` reports ``submitted``/``unfinished`` precisely so
half-served traces cannot masquerade as clean SLO percentiles.

Since PR 8 the whole run records through ``repro.core.telemetry``: request
lifecycles, the engine's spans, attention dispatch events, and — via the
``jax.monitoring`` bridge — an XLA compile-event counter per row.  The
trace is exported next to the artifact as ``BENCH_serving_trace.jsonl``
(feed to ``python -m repro.core.telemetry summarize``).

A machine-readable artifact is written to ``BENCH_serving.json`` (schema
``repro.serving/v4``; v3 had a single rate and a single cache layout and no
drain accounting; v2 lacked the SLO columns; v1 was one CSV row).
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from benchmarks.common import emit
from repro.configs import get_config
from repro.core import telemetry as tel
from repro.core.portable import on_tpu
from repro.core.telemetry.jaxmon import COMPILE_COUNTER
from repro.models import attention as A
from repro.models import transformer as T
from repro.serving import (Request, ServingEngine, latency_summary,
                           synthetic_trace)

ARCH = "granite-3-8b"
NUM_SLOTS = 4
CACHE_LEN = 64
PREFILL_BUCKETS = (8, 16)
BLOCK_SIZE = 8
MAX_NEW = 16
RATES_RPS = (10.0, 50.0, 200.0)
CACHE_LAYOUTS = ("contiguous", "paged")
ARTIFACT = "BENCH_serving.json"
SCHEMA = "repro.serving/v4"


def _prov(log: Dict[str, Dict[str, Any]], kind: str) -> str:
    d = log.get(kind, {})
    bk = d.get("backend", "?")
    if d.get("fallback"):
        return f"{kind}={bk}(fallback)"
    tuning = d.get("tuning", "?")
    return f"{kind}={bk}" + (f"/{tuning}" if bk != "xla" else "")


def _compile_count() -> float:
    return tel.snapshot().get("counters", {}).get(COMPILE_COUNTER, 0.0)


def _ms(lat: Dict[str, float], key: str) -> Optional[float]:
    v = lat.get(key)
    return v * 1e3 if v is not None else None


def _warmup_trace(cfg) -> List[Request]:
    """One request per ladder bucket (exact-fit prompts), so every prefill
    shape AND the decode shape compile before anything is timed."""
    rng = np.random.default_rng(7)
    return [
        Request(uid=10_000 + i,
                prompt=rng.integers(2, cfg.vocab_size, b).astype(np.int32),
                max_new_tokens=4, arrival_time=0.0)
        for i, b in enumerate(sorted(PREFILL_BUCKETS))]


def _build_engine(params, cfg, backend: str, layout: str) -> ServingEngine:
    kwargs: Dict[str, Any] = {}
    if layout == "paged":
        kwargs["block_size"] = BLOCK_SIZE
    return ServingEngine(params, cfg, num_slots=NUM_SLOTS,
                         cache_len=CACHE_LEN,
                         prefill_buckets=PREFILL_BUCKETS,
                         attn_backend=backend, cache_layout=layout, **kwargs)


def _one_rate(eng: ServingEngine, cfg, backend: str, layout: str,
              rate: float, n_requests: int, dispatch_log,
              ) -> Tuple[Dict[str, Any], List[Dict[str, Any]]]:
    """One timed row: the warmed (backend, layout) engine serves the trace
    at `rate` requests/second."""
    compiles_before = _compile_count()
    traces_before = (eng.stats["prefill_traces"], eng.stats["decode_traces"])
    steps_before = eng.stats["decode_steps"]
    toks_before = eng.stats["tokens_generated"]

    trace = synthetic_trace(n_requests, vocab_size=cfg.vocab_size,
                            rate=rate, max_prompt=max(PREFILL_BUCKETS),
                            max_new_tokens=MAX_NEW, seed=1)
    t0 = time.perf_counter()
    done = eng.run(trace)
    wall = time.perf_counter() - t0
    compiles_after = _compile_count()

    steps = eng.stats["decode_steps"] - steps_before
    toks = eng.stats["tokens_generated"] - toks_before
    del done  # the engine mutates the trace's Request objects in place —
    # summarizing the full submitted trace is what makes unfinished visible
    lat = latency_summary(trace)
    if lat["unfinished"] > 0:
        raise RuntimeError(
            f"serving[{backend}/{layout}@{rate:g}rps]: trace did not drain "
            f"({lat['unfinished']}/{lat['submitted']} requests unfinished) "
            f"— SLO percentiles would be meaningless")
    retraced = (eng.stats["prefill_traces"],
                eng.stats["decode_traces"]) != traces_before

    # this row's telemetry: drain the ring so per-row events never evict
    # each other across rows, summarize the spans, count compiles
    rec = tel.recorder()
    row_events = rec.drain() if rec is not None else []
    row_tel = {
        "spans": tel.summarize_events(row_events),
        "jax_compile_events": compiles_after - compiles_before,
        "jax_compile_events_timed": compiles_after - compiles_before,
    }

    def fmt(key):
        v = _ms(lat, key)
        return f"{v:.1f}" if v is not None else "n/a"

    derived = (f"{toks / wall:.1f} tok/s "
               f"ttft p50 {fmt('p50_ttft_s')} "
               f"p95 {fmt('p95_ttft_s')} p99 {fmt('p99_ttft_s')} ms "
               f"itl p50 {fmt('p50_itl_s')} "
               f"p95 {fmt('p95_itl_s')} p99 {fmt('p99_itl_s')} ms "
               f"lat p50 {fmt('p50_latency_s')} "
               f"p95 {fmt('p95_latency_s')} p99 {fmt('p99_latency_s')} ms "
               f"({n_requests} reqs @ {rate:g} rps slots={NUM_SLOTS}) "
               f"compiles={row_tel['jax_compile_events']:.0f} "
               f"{_prov(dispatch_log, 'prefill')} "
               f"{_prov(dispatch_log, 'decode')}"
               + (" RETRACED" if retraced else ""))
    emit(f"serving[{backend}/{layout}@{rate:g}rps]",
         wall / max(steps, 1), derived)
    row = {
        "backend": backend,
        "cache_layout": layout,
        "rate_rps": rate,
        "resolved": dict(eng.attn_backends),
        "tok_s": toks / wall,
        "us_per_decode_step": wall / max(steps, 1) * 1e6,
        "ttft_p50_ms": _ms(lat, "p50_ttft_s"),
        "ttft_p95_ms": _ms(lat, "p95_ttft_s"),
        "ttft_p99_ms": _ms(lat, "p99_ttft_s"),
        "itl_p50_ms": _ms(lat, "p50_itl_s"),
        "itl_p95_ms": _ms(lat, "p95_itl_s"),
        "itl_p99_ms": _ms(lat, "p99_itl_s"),
        "latency_p50_ms": _ms(lat, "p50_latency_s"),
        "latency_p95_ms": _ms(lat, "p95_latency_s"),
        "latency_p99_ms": _ms(lat, "p99_latency_s"),
        "requests": n_requests,
        "submitted": lat["submitted"],
        "unfinished": lat["unfinished"],
        "retraced": retraced,
        "jax_compile_events": row_tel["jax_compile_events"],
        "telemetry": row_tel,
        "dispatch": dispatch_log,
    }
    return row, row_events


def _one_engine(params, cfg, backend: str, layout: str, n_requests: int,
                ) -> Tuple[List[Dict[str, Any]], List[Dict[str, Any]],
                           Dict[str, Any]]:
    """Warm one (backend, layout) engine through the whole bucket ladder,
    then serve the rate sweep on it."""
    A.reset_dispatch_log()
    compiles_before = _compile_count()
    eng = _build_engine(params, cfg, backend, layout)
    eng.run(_warmup_trace(cfg))
    log = A.dispatch_log()
    warm_compiles = _compile_count() - compiles_before
    # the bounded-compile contract: one prefill program per ladder rung,
    # one decode program — never a shape per prompt length
    if eng.stats["prefill_traces"] > len(PREFILL_BUCKETS):
        raise RuntimeError(
            f"serving[{backend}/{layout}]: {eng.stats['prefill_traces']} "
            f"prefill traces for a {len(PREFILL_BUCKETS)}-bucket ladder")
    if eng.stats["decode_traces"] != 1:
        raise RuntimeError(
            f"serving[{backend}/{layout}]: expected exactly one decode "
            f"trace, got {eng.stats['decode_traces']}")
    rec = tel.recorder()
    events = rec.drain() if rec is not None else []   # warmup events

    rows = []
    for rate in RATES_RPS:
        row, row_events = _one_rate(eng, cfg, backend, layout, rate,
                                    n_requests, log)
        row["warmup_jax_compile_events"] = warm_compiles
        rows.append(row)
        events.extend(row_events)
    engine_meta = {
        "backend": backend,
        "cache_layout": layout,
        "prefill_traces": eng.stats["prefill_traces"],
        "decode_traces": eng.stats["decode_traces"],
        "warmup_jax_compile_events": warm_compiles,
    }
    return rows, events, engine_meta


def run(smoke: bool = False, json_path: str = ARTIFACT) -> Dict[str, Any]:
    cfg = get_config(ARCH, smoke=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))

    # record the whole run; respect an env-configured recorder
    # (REPRO_TELEMETRY=jsonl:... keeps its exit flush), else enable an
    # in-memory one for the duration of the benchmark
    owned = not tel.enabled()
    if owned:
        tel.configure("on")

    try:
        # before: the status-quo plain-XLA path; after: the registry Pallas
        # kernels (compiled on TPU, interpret mode on a CPU host — relative
        # numbers only there, see benchmarks/common.py)
        backends = ["xla", "pallas" if on_tpu() else "pallas_interpret"]
        n_requests = 5 if smoke else 16

        rows: List[Dict[str, Any]] = []
        engines: List[Dict[str, Any]] = []
        events: List[Dict[str, Any]] = []
        for bk in backends:
            for layout in CACHE_LAYOUTS:
                erows, eevents, emeta = _one_engine(params, cfg, bk, layout,
                                                    n_requests)
                rows.extend(erows)
                events.extend(eevents)
                engines.append(emeta)

        rec = tel.recorder()
        events.extend(rec.drain() if rec is not None else [])
        stem = json_path[:-5] if json_path.endswith(".json") else json_path
        trace_jsonl = f"{stem}_trace.jsonl"
        meta = {"benchmark": "serving", "arch": ARCH, "schema_of": SCHEMA}
        if rec is not None:
            snap = rec.snapshot()     # counters survive the drains
            snap["span_summary"] = tel.summarize_events(events)
            tel.write_jsonl(trace_jsonl, events, meta=meta,
                            footer_data=snap)
        else:  # pragma: no cover - recorder always on here
            snap = {}

        artifact = {
            "schema": SCHEMA,
            "arch": ARCH,
            "smoke": bool(smoke),
            "platform": jax.devices()[0].platform,
            "num_slots": NUM_SLOTS,
            "cache_len": CACHE_LEN,
            "prefill_buckets": list(PREFILL_BUCKETS),
            "block_size": BLOCK_SIZE,
            "cache_layouts": list(CACHE_LAYOUTS),
            "rates_rps": list(RATES_RPS),
            "jax_compile_events": snap.get("counters", {}).get(
                COMPILE_COUNTER, 0.0),
            "telemetry": snap,
            "trace_jsonl": trace_jsonl,
            "engines": engines,
            "rows": rows,
        }
        with open(json_path, "w") as f:
            json.dump(artifact, f, indent=1, sort_keys=True)
        return artifact
    finally:
        if owned:
            tel.configure("off")


if __name__ == "__main__":
    run()
