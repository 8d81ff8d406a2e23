"""Continuous-batching engine tests: padded-prefill correctness, greedy
equivalence with unbatched decode (both cache layouts, both driver loops),
the cache donated to both programs, fixed-shape/bounded-compile contracts,
paged-pool admission gating, and the slot/block/queue plumbing."""

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
from repro.serving import (BlockAllocator, Request, RequestQueue,
                           ServingEngine, SlotAllocator)
from repro.serving.slots import RESERVED_BLOCKS, TRASH_BLOCK
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
    import dataclasses
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
def test_engine_matches_unbatched_greedy(params):
    """Ragged prompts through slot recycling == per-request unbatched
    greedy decode, token for token."""
    reqs = _requests([3, 9, 12, 5, 7], max_new=6)
    eng = ServingEngine(params, CFG, num_slots=2, cache_len=48,
                        prefill_len=16)
    done = eng.run(reqs)
    assert len(done) == 5
    for r in done:
        ref = SS.generate(params, CFG, jnp.asarray(r.prompt)[None],
                          max_new_tokens=6, cache_len=48)
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


def test_engine_rejects_oversized(params):
    eng = ServingEngine(params, CFG, num_slots=2, cache_len=32,
                        prefill_len=8)
    with pytest.raises(ValueError):
        eng.submit(Request(uid=0, prompt=np.arange(9, dtype=np.int32),
                           max_new_tokens=4))
    with pytest.raises(ValueError):
        eng.submit(Request(uid=1, prompt=np.arange(8, dtype=np.int32),
                           max_new_tokens=32))


def test_engine_rejects_stateful_archs(params):
    cfg = get_config("rwkv6-3b", smoke=True)
    with pytest.raises(NotImplementedError):
        ServingEngine(params, cfg)


# --------------------------------------------------------------------------
# engine v2: paged KV layout, bucket ladder, threaded loop, scheduler edges
# --------------------------------------------------------------------------
def _oracle_tokens(params, req, cache_len):
    ref = SS.generate(params, CFG, jnp.asarray(req.prompt)[None],
                      max_new_tokens=req.max_new_tokens, cache_len=cache_len)
    return [int(t) for t in np.asarray(ref)[0]]


def test_paged_engine_matches_unbatched_and_contiguous(params):
    """The paged pool + block tables are pure layout: greedy tokens must
    bit-match both the contiguous engine and unbatched decode."""
    def serve(layout):
        eng = ServingEngine(params, CFG, num_slots=2, cache_len=48,
                            prefill_len=16, cache_layout=layout,
                            block_size=8)
        done = eng.run(_requests([3, 9, 12, 5, 16, 1], max_new=6))
        return {r.uid: list(r.generated) for r in done}, eng

    got_paged, eng = serve("paged")
    got_contig, _ = serve("contiguous")
    assert got_paged == got_contig
    for r in _requests([3, 9, 12, 5, 16, 1], max_new=6):
        assert got_paged[r.uid] == _oracle_tokens(params, r, 48)
    # every page returned, every table row parked on the trash page
    assert eng.balloc.available() == eng.balloc.capacity()
    assert np.all(eng.block_tables == TRASH_BLOCK)


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_engine_donates_its_cache(params, layout):
    """Both programs take the KV cache over: after a step the cache the
    engine held before it is deleted, every prefill and decode call counts
    ``serving.cache.donated`` and none ``serving.cache.kept``, and greedy
    tokens still match unbatched decode."""
    rec = tel.configure("on")
    try:
        eng = ServingEngine(params, CFG, num_slots=2, cache_len=48,
                            prefill_len=16, cache_layout=layout,
                            block_size=8)
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
    for r in reqs:
        assert list(r.generated) == _oracle_tokens(params, r, 48)


def test_paged_pool_admission_gating(params):
    """A pool smaller than the slot count's worth of rows serializes
    admissions on free pages (FIFO head-of-line) without changing tokens."""
    # 6 pages of 8 = room for at most two of these requests' reservations
    # (12 + 5 -> 2 + 1 pages, 9 + 5 -> 2 pages, ...), far below 4 slots
    eng = ServingEngine(params, CFG, num_slots=4, cache_len=16,
                        prefill_len=8, cache_layout="paged", block_size=8,
                        num_blocks=RESERVED_BLOCKS + 2)
    reqs = _requests([3, 8, 5, 2, 7], max_new=6)
    done = eng.run(reqs)
    assert len(done) == 5
    for r in done:
        assert list(r.generated) == _oracle_tokens(params, r, 16)
    assert eng.balloc.available() == eng.balloc.capacity()


def test_paged_request_larger_than_pool_rejected(params):
    eng = ServingEngine(params, CFG, num_slots=2, cache_len=16,
                        prefill_len=8, cache_layout="paged", block_size=2,
                        num_blocks=RESERVED_BLOCKS + 3)   # 6 positions max
    with pytest.raises(ValueError):
        eng.submit(Request(uid=0, prompt=np.arange(2, 8, dtype=np.int32),
                           max_new_tokens=4))             # needs 5 pages


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


def test_threaded_loop_matches_sync(params):
    """run_threaded (injector + admission threads, bounded backpressure
    queue) produces bitwise the sync loop's greedy tokens."""
    def serve(threaded):
        eng = ServingEngine(params, CFG, num_slots=2, cache_len=48,
                            prefill_buckets=(8, 16), cache_layout="paged",
                            block_size=8)
        reqs = _requests([3, 9, 12, 5, 7], max_new=6,
                         arrivals=[0.0, 0.0, 0.01, 0.02, 0.03])
        done = eng.run_threaded(reqs) if threaded else eng.run(reqs)
        assert eng.stats["requests_finished"] == 5
        return {r.uid: list(r.generated) for r in done}

    assert serve(threaded=True) == serve(threaded=False)


def test_threaded_vs_sync_subprocess(params):
    """Tier-1 end-to-end check in a fresh interpreter: the threaded and
    synchronous loops serve the same trace to bitwise-identical tokens."""
    code = """
import numpy as np, jax
from repro.configs import get_config
from repro.models.transformer import init_params
from repro.serving import ServingEngine, Request

cfg = get_config("granite-3-8b", smoke=True)
params = init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(5)
def reqs():
    return [Request(uid=i, prompt=p.copy(), max_new_tokens=5,
                    arrival_time=0.005 * i)
            for i, p in enumerate(prompts)]
prompts = [rng.integers(2, cfg.vocab_size, L).astype(np.int32)
           for L in (3, 9, 12, 5)]
out = {}
for threaded in (False, True):
    eng = ServingEngine(params, cfg, num_slots=2, cache_len=32,
                        prefill_buckets=(8, 16), cache_layout="paged",
                        block_size=8)
    done = eng.run_threaded(reqs()) if threaded else eng.run(reqs())
    out[threaded] = {r.uid: list(r.generated) for r in done}
assert len(out[False]) == 4 and out[True] == out[False], out
print("THREADED_BITWISE_OK")
"""
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "THREADED_BITWISE_OK" in proc.stdout


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
                        prefill_len=8, cache_layout="paged", block_size=8)
    req = _requests([5], max_new=8)[0]
    req.eos_id = first
    done = eng.run([req])
    assert len(done) == 1 and done[0].generated == [first]
    assert eng.stats["decode_steps"] == 0
    assert eng.slots.available() == 2
    assert eng.balloc.available() == eng.balloc.capacity()


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


def test_block_allocator_cycle():
    ba = BlockAllocator(num_blocks=RESERVED_BLOCKS + 4, block_size=8)
    assert ba.capacity() == 4 and ba.available() == 4
    # positions written: prompt_len + max_new - 1 (last token never cached)
    assert ba.blocks_for(1, 1) == 1      # 1 position -> 1 page
    assert ba.blocks_for(8, 1) == 1      # 8 positions, exact fit
    assert ba.blocks_for(8, 2) == 2      # 9 positions spill a page
    assert ba.blocks_for(3, 6) == 1
    a = ba.alloc(2)
    assert a == [RESERVED_BLOCKS, RESERVED_BLOCKS + 1]   # dense, low first
    assert ba.available() == 2 and ba.in_use() == 2
    with pytest.raises(RuntimeError):
        ba.alloc(3)                      # pool exhausted
    ba.free(a)
    assert ba.available() == 4
    with pytest.raises(ValueError):
        ba.free([a[0]])                  # double free
    with pytest.raises(ValueError):
        ba.free([0])                     # reserved sentinel page
    with pytest.raises(ValueError):
        BlockAllocator(num_blocks=RESERVED_BLOCKS, block_size=8)
