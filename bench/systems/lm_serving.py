"""A decoder LM served by the program's ``ServingEngine`` under open-loop
traffic.

Set-up (counted in ``setup_s``): the weights, made on the device from the
seed in one jitted call by the reference module, in the program's parameter
layout; the engine at the configuration's slots, cache length, prefill
buckets and attention backend; and one warm-up request per prefill bucket,
which loads (or compiles) every program the window will run; then the
pre-roll, the schedule's first ``preroll_s`` seconds (the cell file), which
fills the slots to their steady state.  The window opens on that steady
state, submits each request of the seeded schedule when it is due and
steps the engine (``ServingEngine.submit`` / ``step``) until it closes; the
requests that arrived in it are then served to the end.  Their served
tokens are compared with the plain float32 reference.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import harness

#: seconds past the window's close within which every request that arrived
#: in it has to finish; one that does not counts as failed
DRAIN_LIMIT_S = 60.0
#: uids of the warm-up requests, clear of the schedule's 0, 1, 2, ...
WARMUP_UID = 1 << 30
#: seconds before the window opens at which a traced run starts the profiler
PROFILER_LEAD_S = 3.0
#: the program's RMSNorm epsilon, fixed in ``repro.models.common.apply_norm``
PROGRAM_NORM_EPS = 1e-6


def model_config(cfg: Dict):
    """The program's ``ModelConfig`` for a configuration file.  Raises where
    the configuration as run (its ``departures`` applied) asks for what the
    program cannot do."""
    from repro.configs.base import ModelConfig
    run = harness.load_module("refs", cfg["reference"]).as_run(cfg)
    if run["rms_norm_eps"] != PROGRAM_NORM_EPS:
        raise ValueError(f"rms_norm_eps as run is {run['rms_norm_eps']}; the "
                         f"program's is {PROGRAM_NORM_EPS}")
    e = cfg["engine"]
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=d, n_heads=h, n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg.get("head_dim", d // h), norm="rmsnorm", mlp="swiglu",
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=cfg["torch_dtype"], compute_dtype=cfg["torch_dtype"],
        attn_backend=e["attn_backend"])


def make_params(key, cfg: Dict, mcfg):
    """The reference module's seeded weights in the program's layout, made
    on the device in one jitted call; checked against the layout the
    program's own ``init_params`` would give."""
    from repro.models import transformer as T
    ref = harness.load_module("refs", cfg["reference"])
    w = ref.widths(cfg)
    vpad = mcfg.padded_vocab

    def build(k):
        emb = jnp.pad(ref.embedding(k, w), ((0, vpad - w["v"]), (0, 0)))
        lay = ref.stacked_layers(k, w)
        seg = {"ln1": {"scale": lay["ln1"]}, "ln2": {"scale": lay["ln2"]},
               "attn": {n: lay[n] for n in ("wq", "wk", "wv", "wo")},
               "mlp": {n: lay[n] for n in ("w_gate", "w_up", "w_down")}}
        return {"embed": emb, "final_norm": {"scale": ref.final_norm(k, w)},
                "eager": {}, "segments": [seg]}

    want = jax.eval_shape(lambda: T.init_params(mcfg, key))
    got = jax.eval_shape(build, key)
    if (jax.tree.structure(want) != jax.tree.structure(got)
            or jax.tree.leaves(jax.tree.map(
                lambda a, b: (a.shape, a.dtype) != (b.shape, b.dtype),
                want, got)).count(True)):
        raise ValueError(f"weights do not fit the program's layout: "
                         f"{want} vs {got}")
    return jax.jit(build)(key)


def make_engine(params, cfg: Dict, mcfg):
    from repro.serving import ServingEngine
    e = cfg["engine"]
    return ServingEngine(params, mcfg, num_slots=e["slots"],
                         cache_len=e["cache_len"],
                         prefill_buckets=tuple(e["prefill_buckets"]),
                         attn_backend=e["attn_backend"],
                         cache_layout=e["cache_layout"], temperature=0.0)


def warm_up(engine, cfg: Dict) -> None:
    """One request per prefill bucket, at the bucket's own length, two
    tokens each: every program the window runs is loaded and run once.
    Fails if any attention call fell back from the configured backend."""
    from repro.models import attention as A
    from repro.serving import Request
    A.reset_dispatch_log()
    reqs = [Request(uid=WARMUP_UID + i, prompt=np.full(b, 1, np.int32),
                    max_new_tokens=2)
            for i, b in enumerate(engine.prefill_buckets)]
    for r in reqs:
        engine.submit(r)
    while engine.active_count() or len(engine.queue):
        engine.step()
    want = cfg["engine"]["attn_backend"]
    for rec in A.dispatch_records():
        if rec["backend"] != want or "fallback" in rec:
            raise RuntimeError(f"attention left the {want!r} backend: {rec}")


def requests(schedule: List[Dict]):
    from repro.serving import Request
    return [Request(uid=s["uid"], prompt=s["prompt"],
                    max_new_tokens=s["max_new_tokens"], arrival_time=s["due"])
            for s in schedule]


def serve(engine, reqs, seconds: float, *,
          on_start: Callable[[], float], on_end: Callable[[float], Any],
          preroll: float = 0.0, arm: Optional[Callable[[], None]] = None,
          annotate: Optional[Callable] = None) -> Dict[str, Any]:
    """Open loop: submit each request when it is due and step the engine
    while it has work; open the window once the ``preroll`` seconds have
    passed, close it ``seconds`` later, then serve what arrived to the end
    (at most ``DRAIN_LIMIT_S`` more).  ``arm``, if given, is called
    ``PROFILER_LEAD_S`` before the window opens.

    Request times are on the engine's clock, whose 0 is where the schedule
    puts the window's start; the window opens at the first step boundary
    after it, ``window_start`` seconds later on that clock.  Returns
    ``window_start``, the generator's lateness per window request (seconds)
    and whether the drain finished.
    """
    span = annotate or (lambda name: contextlib.nullcontext())
    origin = time.perf_counter() + preroll
    engine._t0 = origin
    n, i, late = len(reqs), 0, []
    start = None                      # the window's start, engine clock
    closed = False
    while True:
        now = time.perf_counter() - origin
        if start is None:
            if arm is not None and now >= -PROFILER_LEAD_S:
                arm()
                arm = None
                now = time.perf_counter() - origin
            if now >= 0.0:
                start = on_start() - origin
                now = start
        while i < n and reqs[i].arrival_time <= now:
            engine.submit(reqs[i])
            if reqs[i].arrival_time >= 0.0:
                late.append(now - reqs[i].arrival_time)
            i += 1
        if start is not None:
            if not closed and now >= start + seconds:
                on_end(origin + start + seconds)
                closed = True
            if now >= start + seconds + DRAIN_LIMIT_S:
                break
        if not (engine.active_count() or len(engine.queue)):
            if i >= n and start is not None:
                break
            due = [reqs[i].arrival_time] if i < n else []
            if start is None:
                due.append(0.0 if arm is None else -PROFILER_LEAD_S)
            with span("bench.wait_arrival"):
                time.sleep(max(0.0, min(min(due) - now, 0.05)))
            continue
        with span("bench.step"):
            engine.step(now)
    if not closed:
        time.sleep(max(0.0, origin + start + seconds - time.perf_counter()))
        on_end(origin + start + seconds)
    return {"window_start": start, "lateness": late,
            "drained": not (engine.active_count() or len(engine.queue))}


def in_window(reqs) -> List:
    """The requests the schedule put in the window (not the pre-roll)."""
    return [r for r in reqs if r.arrival_time >= 0.0]


def summarize(reqs, start: float, seconds: float) -> Dict[str, Any]:
    """End-to-end metrics of the window ``[start, start + seconds]`` (engine
    clock): every token emitted inside it per second, pre-roll requests'
    too; the 95th percentile of time to the first token from when the
    request was due, over every request that arrived in the window; and the
    95th percentile of every inter-token gap that ended inside it, pooled
    over requests."""
    end = start + seconds
    arrived = in_window(reqs)
    tokens = sum(1 for r in reqs for t in r.t_tokens if start <= t <= end)
    ttft = [r.t_first_token - r.arrival_time for r in arrived if r.t_tokens]
    gaps = [b - a for r in reqs for a, b in zip(r.t_tokens, r.t_tokens[1:])
            if start < b <= end]
    waits = [r.t_admitted - r.arrival_time for r in arrived
             if r.t_admitted == r.t_admitted]
    return {"tok_s": tokens / seconds,
            "ttft_p95_ms": 1e3 * harness.percentile(ttft, 95),
            "itl_p95_ms": 1e3 * harness.percentile(gaps, 95),
            "ttft_p50_ms": 1e3 * harness.percentile(ttft, 50),
            "itl_p50_ms": 1e3 * harness.percentile(gaps, 50),
            "queue_wait_p95_ms": 1e3 * harness.percentile(waits, 95),
            "tokens_in_window": tokens, "gaps": len(gaps)}


def unfinished(reqs) -> int:
    return sum(1 for r in reqs
               if not r.finished or len(r.generated) != r.max_new_tokens)


def check_sample(reqs, n: int, seed: int) -> List:
    """``n`` finished requests of the window drawn from the seed, the
    longest among them."""
    done = [r for r in in_window(reqs) if r.finished]
    if not done:
        return []
    longest = max(done, key=lambda r: r.prompt_len + len(r.generated))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(int(seed) % (1 << 64))
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


def records(reqs, start: float) -> Dict[str, Any]:
    """What the per-layer readers need, per request, on the window's clock
    (0 = the window's start, as in the trace); ``window`` marks the requests
    that arrived in it."""
    return {"requests": [
        {"due": r.arrival_time - start, "admitted": r.t_admitted - start,
         "prompt_len": r.prompt_len, "window": r.arrival_time >= 0.0,
         "tokens": [t - start for t in r.t_tokens]}
        for r in reqs]}


def setup(ctx: "harness.Run", seed: int):
    cfg = ctx.config
    mcfg = model_config(cfg)
    params = make_params(harness.seed_key(seed), cfg, mcfg)
    engine = make_engine(params, cfg, mcfg)
    del params
    warm_up(engine, cfg)
    return engine


def schedule(ctx: "harness.Run", seed: int, rate: float, seconds: float,
             preroll: float = 0.0):
    gen = harness.load_module("generators", ctx.traffic["generator"])
    return gen.schedule(ctx.traffic, rate=rate, seconds=seconds, seed=seed,
                        vocab=ctx.config["vocab_size"], preroll=preroll)


def run(ctx: "harness.Run") -> Dict[str, Any]:
    cfg, cell = ctx.config, ctx.cell
    preroll = float(cell["preroll_s"])
    engine = setup(ctx, ctx.seed)
    reqs = requests(schedule(ctx, ctx.seed, cell["rate_rps"], ctx.seconds,
                             preroll))
    annotate = jax.profiler.TraceAnnotation if ctx.trace else None
    out = serve(engine, reqs, ctx.seconds, on_start=ctx.start_window,
                on_end=ctx.end_window, preroll=preroll,
                arm=ctx.start_profiler, annotate=annotate)
    ctx.finish()
    ctx.read_memory()
    late = np.asarray(out["lateness"]) * 1e3
    start = out["window_start"]
    harness.log(f"window opened {start!r} s after its schedule; generator "
                f"lateness ms: p50 {np.percentile(late, 50)!r} "
                f"p99 {np.percentile(late, 99)!r} max {late.max()!r} over "
                f"{len(late)} requests; compiles in window "
                f"{ctx.compiles_in_window}; engine stats {engine.stats}")
    e2e = summarize(reqs, start, ctx.seconds)
    harness.log(f"window: {e2e}")
    sample = check_sample(reqs, cell["check_requests"], ctx.seed)
    del engine                      # free the program's state first
    gap = max_gap(ctx, sample)
    arrived = in_window(reqs)
    failed = unfinished(arrived)
    return {
        "end_to_end": {k: e2e[k] for k in ("tok_s", "ttft_p95_ms",
                                           "itl_p95_ms")},
        "attempted": len(arrived), "failed": failed,
        "checks": {"logit_gap": harness.check(gap,
                                              cell["limits"]["logit_gap"]),
                   "unfinished": harness.check(failed, 0)},
        "records": records(reqs, start),
    }


def max_gap(ctx: "harness.Run", sample) -> float:
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over the sample (inf if nothing was served)."""
    if not sample:
        return float("inf")
    ref = harness.load_module("refs", ctx.config["reference"])
    key = harness.seed_key(ctx.seed)
    length = ctx.config["engine"]["cache_len"]
    t = time.perf_counter()
    gaps = [float(ref.served_gaps(key, ctx.config, r.prompt, r.generated,
                                  length).max()) for r in sample]
    harness.log(f"reference: {len(sample)} requests, "
                f"{sum(len(r.generated) for r in sample)} served tokens, "
                f"gaps {gaps}, {time.perf_counter() - t:.3f} s")
    return max(gaps)


def calibrate(ctx: "harness.Run", seeds) -> None:
    """Per seed: a short window at the cell's rate on fresh weights, then
    the program's widest gap and the control's, on the same sample."""
    ref = harness.load_module("refs", ctx.config["reference"])
    length = ctx.config["engine"]["cache_len"]
    for seed in seeds:
        engine = setup(ctx, seed)
        reqs = requests(schedule(ctx, seed, ctx.cell["rate_rps"],
                                 ctx.seconds))
        serve(engine, reqs, ctx.seconds, on_start=time.perf_counter,
              on_end=lambda t: None)
        del engine
        sample = check_sample(reqs, ctx.cell["check_requests"], seed)
        key = harness.seed_key(seed)
        both = [ref.control_gaps(key, ctx.config, r.prompt, r.generated,
                                 length) for r in sample]
        prog = [float(p.max()) for p, _ in both]
        ctrl = [float(c.max()) for _, c in both]
        print(f"calibrate seed {seed} unfinished {unfinished(reqs)} tokens "
              f"{sum(len(r.generated) for r in sample)} program {prog} "
              f"control {ctrl}", flush=True)
