"""Continuous-batching engine tests: padded-prefill correctness, greedy
equivalence with unbatched decode over several engine geometries, the cache
donated to both programs, fixed-shape/bounded-compile contracts, ``run`` as
a loop over ``submit``/``step``, FIFO admission, request and geometry
validation, and the slot/queue plumbing."""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import telemetry as tel
from repro.models import transformer as T
from repro.serving import Request, RequestQueue, ServingEngine, SlotAllocator
from repro.serving.trace import latency_summary, synthetic_trace
from repro.training import serve_step as SS

CFG = get_config("granite-3-8b", smoke=True)


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.PRNGKey(0))


def _requests(lens, max_new=6, arrivals=None, rng_seed=0):
    rng = np.random.default_rng(rng_seed)
    return [Request(uid=i,
                    prompt=rng.integers(2, CFG.vocab_size, L)
                    .astype(np.int32),
                    max_new_tokens=max_new,
                    arrival_time=0.0 if arrivals is None else arrivals[i])
            for i, L in enumerate(lens)]


# --------------------------------------------------------------------------
# padded prefill correctness (the left-pad-attends-over-pad-0 bug)
# --------------------------------------------------------------------------
def test_leftpad_positions():
    pos = T.leftpad_positions(jnp.asarray([3, 5, 1]), 5)
    np.testing.assert_array_equal(
        np.asarray(pos),
        [[-1, -1, 0, 1, 2], [0, 1, 2, 3, 4], [-1, -1, -1, -1, 0]])


def test_padded_prefill_matches_unpadded(params):
    """Left-padded mixed-batch prefill with lengths == per-row unpadded."""
    rng = np.random.default_rng(3)
    lens = [3, 8, 5]
    S = 8
    prompts = [rng.integers(2, CFG.vocab_size, L).astype(np.int32)
               for L in lens]
    batch = np.zeros((len(lens), S), np.int32)
    for i, p in enumerate(prompts):
        batch[i, S - len(p):] = p
    last, _, _ = SS.prefill(params, CFG, jnp.asarray(batch), cache_len=32,
                            lengths=jnp.asarray(lens))
    for i, p in enumerate(prompts):
        ref, _, _ = SS.prefill(params, CFG, jnp.asarray(p)[None],
                               cache_len=32)
        np.testing.assert_allclose(np.asarray(last[i], np.float32),
                                   np.asarray(ref[0], np.float32),
                                   rtol=2e-4, atol=2e-4)


def test_padded_prefill_sliding_window_pads_dropped(params):
    """Pads must not clobber ring-buffer slots when the window is shorter
    than the padded length (pos -1 would alias slot window-1)."""
    wcfg = dataclasses.replace(CFG, window=8)
    wparams = T.init_params(wcfg, jax.random.PRNGKey(5))
    rng = np.random.default_rng(6)
    L, S = 6, 12                          # 6 pads > window slack
    prompt = rng.integers(2, wcfg.vocab_size, L).astype(np.int32)
    batch = np.zeros((1, S), np.int32)
    batch[0, S - L:] = prompt
    last, _, _ = SS.prefill(wparams, wcfg, jnp.asarray(batch), cache_len=32,
                            lengths=jnp.asarray([L]))
    ref, _, _ = SS.prefill(wparams, wcfg, jnp.asarray(prompt)[None],
                           cache_len=32)
    np.testing.assert_allclose(np.asarray(last[0], np.float32),
                               np.asarray(ref[0], np.float32),
                               rtol=2e-4, atol=2e-4)


def test_padded_prefill_decode_positions_consistent(params):
    """Decode after masked prefill continues at the TRUE prompt length and
    matches unpadded prefill+decode of the same prompt."""
    rng = np.random.default_rng(4)
    lens = [3, 6]
    S = 6
    batch = np.zeros((2, S), np.int32)
    prompts = [rng.integers(2, CFG.vocab_size, L).astype(np.int32)
               for L in lens]
    for i, p in enumerate(prompts):
        batch[i, S - len(p):] = p
    last, caches, _ = SS.prefill(params, CFG, jnp.asarray(batch),
                                 cache_len=32, lengths=jnp.asarray(lens))
    tok = jnp.argmax(last, -1)[:, None].astype(jnp.int32)
    pos = jnp.asarray(lens, jnp.int32)[:, None]     # true lengths, not S
    logits, _ = SS.decode_step(params, CFG, tok, pos, caches)
    for i, p in enumerate(prompts):
        out = SS.generate(params, CFG, jnp.asarray(p)[None],
                          max_new_tokens=2, cache_len=32)
        assert int(tok[i, 0]) == int(out[0, 0])
        assert int(jnp.argmax(logits[i])) == int(out[0, 1])


# --------------------------------------------------------------------------
# engine: greedy equivalence + fixed-shape contract
# --------------------------------------------------------------------------
#: (engine keywords, prompt lengths, max_new_tokens, request rng seed,
#: config changes) — each a geometry the engine must serve bit-exactly
GEOMETRIES = {
    "two-slots": (dict(num_slots=2, cache_len=48, prefill_len=16),
                  [3, 9, 12, 5, 7], 6, 0, {}),
    "one-slot": (dict(num_slots=1, cache_len=48, prefill_len=16),
                 [3, 9, 12, 5, 7], 6, 0, {}),
    # six requests through two slots over two prefill buckets
    "conformance-trace": (dict(num_slots=2, cache_len=32,
                               prefill_buckets=(8, 16)),
                          [3, 9, 12, 5, 16, 1], 4, 42, {}),
    "four-slots-ladder": (dict(num_slots=4, cache_len=48,
                               prefill_buckets=(4, 8, 16)),
                          [3, 4, 7, 2, 12, 16, 9], 6, 1, {}),
    # every layer a ring of 8 positions, shorter than most prompts
    "window": (dict(num_slots=2, cache_len=48, prefill_len=16),
               [3, 9, 12, 5, 7], 6, 0, {"window": 8}),
}


@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_engine_matches_unbatched_greedy(params, geometry):
    """Ragged prompts through slot recycling == per-request unbatched
    greedy decode, token for token (bitwise: continuous batching is a
    scheduling concern and may never change a sampled token)."""
    kw, lens, max_new, seed, changes = GEOMETRIES[geometry]
    cfg = dataclasses.replace(CFG, **changes)
    reqs = _requests(lens, max_new=max_new, rng_seed=seed)
    eng = ServingEngine(params, cfg, **kw)
    done = eng.run(reqs)
    assert len(done) == len(lens)
    for r in done:
        ref = SS.generate(params, cfg, jnp.asarray(r.prompt)[None],
                          max_new_tokens=max_new, cache_len=kw["cache_len"])
        np.testing.assert_array_equal(np.asarray(r.generated),
                                      np.asarray(ref[0]))


def test_engine_single_compiled_shape(params):
    """Slot recycling admits queued requests with NO recompilation: one
    compiled prefill shape + one compiled decode shape for the whole trace,
    including arrivals landing mid-decode."""
    arrivals = [0.0, 0.0, 0.0, 0.05, 0.1, 0.15]
    reqs = _requests([4, 11, 6, 3, 16, 8], max_new=5, arrivals=arrivals)
    eng = ServingEngine(params, CFG, num_slots=3, cache_len=64,
                        prefill_len=16)
    done = eng.run(reqs)
    assert len(done) == 6
    assert eng.stats["prefill_calls"] == 6
    assert eng.stats["prefill_traces"] == 1, eng.stats
    assert eng.stats["decode_traces"] == 1, eng.stats


def test_engine_sampled_continuations_differ(params):
    """Per-request key streams: identical prompts in different slots/batches
    must not sample identical continuations (the PRNGKey(i)-reuse bug)."""
    rng = np.random.default_rng(9)
    prompt = rng.integers(2, CFG.vocab_size, 6).astype(np.int32)
    reqs = [Request(uid=i, prompt=prompt.copy(), max_new_tokens=12)
            for i in range(4)]
    eng = ServingEngine(params, CFG, num_slots=2, cache_len=48,
                        prefill_len=16, temperature=1.0)
    done = eng.run(reqs)
    gens = {tuple(r.generated) for r in done}
    assert len(gens) > 1, "all requests sampled the same continuation"


@pytest.mark.parametrize("prompt_len,max_new", [(0, 4), (9, 4), (8, 32)],
                         ids=["empty", "over-bucket", "over-cache_len"])
def test_engine_rejects_invalid_requests(params, prompt_len, max_new):
    eng = ServingEngine(params, CFG, num_slots=2, cache_len=32,
                        prefill_len=8)
    with pytest.raises(ValueError):
        eng.submit(Request(uid=0, prompt=np.arange(prompt_len,
                                                   dtype=np.int32),
                           max_new_tokens=max_new))
    assert not eng.queue


@pytest.mark.parametrize("kw", [
    dict(prefill_buckets=(0, 8)), dict(prefill_buckets=(8, 64)),
    dict(prefill_buckets=()), dict(cache_layout="paged")],
    ids=["bucket-0", "bucket-over-cache_len", "empty-ladder",
         "paged-layout"])
def test_engine_rejects_bad_geometry(params, kw):
    with pytest.raises(ValueError):
        ServingEngine(params, CFG, num_slots=2, cache_len=32, **kw)


def test_engine_rejects_stateful_archs(params):
    cfg = get_config("rwkv6-3b", smoke=True)
    with pytest.raises(NotImplementedError):
        ServingEngine(params, cfg)


# --------------------------------------------------------------------------
# bucket ladder, donation, the run loop, scheduler edges
# --------------------------------------------------------------------------
def _oracle_tokens(params, req, cache_len):
    ref = SS.generate(params, CFG, jnp.asarray(req.prompt)[None],
                      max_new_tokens=req.max_new_tokens, cache_len=cache_len)
    return [int(t) for t in np.asarray(ref)[0]]


@pytest.mark.parametrize("temperature", [0.0, 1.0],
                         ids=["greedy", "sampled"])
def test_engine_donates_its_cache(params, temperature):
    """Both programs take the KV cache over: after a step the cache the
    engine held before it is deleted, every prefill and decode call counts
    ``serving.cache.donated`` and none ``serving.cache.kept``, and the
    tokens still match: greedy ones unbatched decode, sampled ones (per-slot
    key splits) a fresh engine serving the same trace."""
    kw = dict(num_slots=2, cache_len=48, prefill_len=16,
              temperature=temperature)
    rec = tel.configure("on")
    try:
        eng = ServingEngine(params, CFG, **kw)
        reqs = _requests([3, 9, 12, 5, 7], max_new=6)
        for r in reqs:
            eng.submit(r)
        held = jax.tree.leaves(eng.caches)
        eng.step()
        assert all(a.is_deleted() for a in held)
        assert not any(a.is_deleted() for a in jax.tree.leaves(eng.caches))
        while eng.active_count() or len(eng.queue):
            eng.step()
        counters = rec.snapshot()["counters"]
    finally:
        tel.configure(os.environ.get(tel.ENV))
    calls = eng.stats["prefill_calls"] + eng.stats["decode_steps"]
    assert eng.stats["prefill_calls"] == 5 and calls > 5
    assert counters.get("serving.cache.donated") == calls
    assert "serving.cache.kept" not in counters
    if temperature == 0.0:
        want = {r.uid: _oracle_tokens(params, r, 48) for r in reqs}
    else:
        again = ServingEngine(params, CFG, **kw).run(
            _requests([3, 9, 12, 5, 7], max_new=6))
        want = {r.uid: list(r.generated) for r in again}
    assert {r.uid: list(r.generated) for r in reqs} == want


def test_prefill_bucket_ladder_bounds_compiles(params):
    """One compiled prefill shape per ladder rung actually used, one decode
    shape total — never a shape per prompt length."""
    eng = ServingEngine(params, CFG, num_slots=2, cache_len=48,
                        prefill_buckets=(4, 8, 16))
    assert eng.prefill_len == 16
    done = eng.run(_requests([3, 4, 7, 2], max_new=4))    # buckets 4 + 8
    assert len(done) == 4
    assert eng.stats["prefill_traces"] == 2, eng.stats
    assert eng.stats["decode_traces"] == 1, eng.stats
    done = eng.run(_requests([12, 6], max_new=4))         # adds bucket 16
    assert len(done) == 2
    assert eng.stats["prefill_traces"] == 3, eng.stats
    assert eng.stats["decode_traces"] == 1, eng.stats
    # bucket choice is padding only: tokens still match unbatched decode
    for r in done:
        assert list(r.generated) == _oracle_tokens(params, r, 48)


def test_run_is_a_loop_over_submit_and_step(params):
    """``run(trace)`` and a hand-driven ``submit``/``step`` loop, as the
    benchmark drives the engine, give equal tokens and equal stats."""
    kw = dict(num_slots=2, cache_len=48, prefill_buckets=(8, 16))
    ran = ServingEngine(params, CFG, **kw)
    done = ran.run(_requests([3, 9, 12, 5, 7], max_new=6))
    stepped = ServingEngine(params, CFG, **kw)
    reqs = _requests([3, 9, 12, 5, 7], max_new=6)
    for r in reqs:
        stepped.submit(r)
    fin = []
    while stepped.active_count() or len(stepped.queue):
        fin += stepped.step()
    assert len(done) == len(fin) == 5
    assert ({r.uid: list(r.generated) for r in done}
            == {r.uid: list(r.generated) for r in fin})
    assert ran.stats == stepped.stats


def test_fifo_head_of_line(params):
    """With one slot, requests are admitted (and so finish) in arrival
    order, whatever their lengths."""
    arrivals = [0.0, 0.0, 0.01, 0.02]
    reqs = _requests([12, 3, 9, 2], max_new=3, arrivals=arrivals)
    eng = ServingEngine(params, CFG, num_slots=1, cache_len=32,
                        prefill_len=16)
    done = eng.run(reqs)
    assert [r.uid for r in done] == [0, 1, 2, 3]
    admitted = [r.t_admitted for r in reqs]
    assert admitted == sorted(admitted)
    assert all(a.t_done <= b.t_admitted for a, b in zip(reqs, reqs[1:]))


def test_run_waits_for_future_arrivals(params):
    """``run`` admits nothing before it is due: it idles until a request's
    arrival time, and then serves it."""
    reqs = _requests([3, 5, 4], max_new=2, arrivals=[0.0, 0.15, 0.3])
    eng = ServingEngine(params, CFG, num_slots=2, cache_len=32,
                        prefill_len=8)
    done = eng.run(reqs)
    assert len(done) == 3
    for r in reqs:
        assert r.t_admitted >= r.arrival_time
        assert r.t_first_token >= r.t_admitted


def test_kernel_registry_does_not_import_the_server():
    """The kernel registry is the lower layer: importing it registers every
    kernel and leaves no ``repro.serving`` module loaded."""
    code = ("import sys, repro.kernels\n"
            "print(sorted(m for m in sys.modules\n"
            "             if m.split('.')[:2] == ['repro', 'serving']))\n")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [src] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "[]", proc.stdout


def test_exact_fit_prompt(params):
    """prompt_len == prefill_len (no pad at all) must serve, and one token
    longer must be rejected."""
    eng = ServingEngine(params, CFG, num_slots=2, cache_len=32,
                        prefill_len=8)
    reqs = _requests([8], max_new=4)
    done = eng.run(reqs)
    assert len(done) == 1
    assert list(done[0].generated) == _oracle_tokens(params, done[0], 32)
    with pytest.raises(ValueError):
        eng.submit(Request(uid=9, prompt=np.arange(2, 11, dtype=np.int32),
                           max_new_tokens=4))


def test_eos_on_prefill_token(params):
    """A request whose very first sampled token is EOS finishes at prefill:
    slot freed immediately, exactly one generated token."""
    req = _requests([5], max_new=8)[0]
    first = _oracle_tokens(params, req, 32)[0]
    eng = ServingEngine(params, CFG, num_slots=2, cache_len=32,
                        prefill_len=8)
    req = _requests([5], max_new=8)[0]
    req.eos_id = first
    done = eng.run([req])
    assert len(done) == 1 and done[0].generated == [first]
    assert eng.stats["decode_steps"] == 0
    assert eng.slots.available() == 2


def test_finish_and_admit_same_step(params):
    """A request can finish in the same step() call that admits another:
    slot bookkeeping and tokens both stay exact."""
    reqs = _requests([3, 5], max_new=3)
    # far enough out that A's prefill (which advances the admission clock)
    # can't make B ready inside the first step
    reqs[1].arrival_time = 50.0
    eng = ServingEngine(params, CFG, num_slots=2, cache_len=32,
                        prefill_len=8)
    for r in reqs:
        eng.submit(r)
    fin = eng.step(now=0.0)   # admits A only (B "arrives" at 50); A at 2/3
    assert fin == [] and eng.active_count() == 1
    fin = eng.step(now=50.0)  # admits B AND finishes A in its decode half
    assert [r.uid for r in fin] == [0]
    assert eng.active_count() == 1
    while eng.active_count():
        fin += eng.step(now=50.0)
    assert {r.uid for r in fin} == {0, 1}
    for r in reqs:
        assert list(r.generated) == _oracle_tokens(params, r, 32)


def test_admission_clock_recomputed_per_admit(params):
    """Two requests admitted in one step() must not share a stale clock:
    the second's t_admitted includes the first's prefill duration."""
    reqs = _requests([5, 7], max_new=2)
    eng = ServingEngine(params, CFG, num_slots=2, cache_len=32,
                        prefill_len=8)
    for r in reqs:
        eng.submit(r)
    eng.step(now=0.0)
    assert reqs[0].t_admitted == 0.0
    assert reqs[1].t_admitted > reqs[0].t_admitted


# --------------------------------------------------------------------------
# plumbing: slots, queue, trace
# --------------------------------------------------------------------------
def test_slot_allocator_cycle():
    sa = SlotAllocator(2)
    a, b = sa.alloc(), sa.alloc()
    assert {a, b} == {0, 1} and sa.available() == 0
    with pytest.raises(RuntimeError):
        sa.alloc()
    sa.free(a)
    assert sa.alloc() == a
    sa.free(b)
    with pytest.raises(ValueError):
        sa.free(b)


def test_queue_arrival_gating():
    q = RequestQueue()
    q.submit(Request(uid=0, prompt=np.ones(2, np.int32), max_new_tokens=1,
                     arrival_time=0.0))
    q.submit(Request(uid=1, prompt=np.ones(2, np.int32), max_new_tokens=1,
                     arrival_time=1.0))
    assert q.pop_ready(0.5).uid == 0
    assert q.pop_ready(0.5) is None      # uid 1 hasn't arrived yet
    assert q.next_arrival() == 1.0
    assert q.pop_ready(2.0).uid == 1
    assert not q


def test_synthetic_trace_and_summary():
    reqs = synthetic_trace(10, vocab_size=64, rate=100.0, seed=2)
    arrivals = [r.arrival_time for r in reqs]
    assert arrivals == sorted(arrivals) and arrivals[0] > 0
    assert all(4 <= r.prompt_len <= 16 for r in reqs)
    for i, r in enumerate(reqs):
        r.t_first_token = r.arrival_time + 0.01
        r.t_done = r.arrival_time + 0.1 + 0.01 * i
    lat = latency_summary(reqs)
    assert 0.1 <= lat["p50_latency_s"] <= 0.2
    assert lat["p50_ttft_s"] == pytest.approx(0.01)
    assert lat["submitted"] == 10 and lat["unfinished"] == 0


def test_latency_summary_counts_unfinished():
    """Unfinished requests must show up in the counts, not silently vanish
    from the SLO denominator."""
    reqs = synthetic_trace(6, vocab_size=64, rate=100.0, seed=3)
    for r in reqs[:4]:                   # only 4 of 6 complete
        r.t_first_token = r.arrival_time + 0.01
        r.t_done = r.arrival_time + 0.1
    lat = latency_summary(reqs)
    assert lat["requests"] == 4
    assert lat["submitted"] == 6
    assert lat["unfinished"] == 2
    empty = latency_summary(synthetic_trace(3, vocab_size=64, seed=4))
    assert empty == {"requests": 0, "submitted": 3, "unfinished": 3}
