"""Fixed-shape continuous-batching decode engine (v2).

The engine owns the KV cache for ``num_slots`` concurrent requests and a
small, bounded set of compiled programs that never grows as requests
arrive/finish:

  * prefill — one compiled shape PER BUCKET of the prefill ladder
    (``prefill_buckets``, default a single bucket).  Each prompt is
    left-padded to the smallest bucket that fits and masked via position -1
    (models/transformer.leftpad_positions), so a short prompt no longer pays
    for the maximum prefill shape and the compile count stays bounded at the
    ladder size.  The freshly-built single-row cache is scattered into the
    engine cache at the assigned slot (MaxText-style prefill-insert).
  * decode — ONE shape for ALL slots, (num_slots, 1).  Inactive slots decode
    garbage whose sampled tokens are ignored and whose cache writes land in
    storage no active request reads — the shape never changes, so requests
    joining or leaving mid-decode cause no recompilation.

Two KV-cache layouts (``cache_layout=``), bitwise-identical in their greedy
outputs:

  * ``"contiguous"`` — one (num_slots, cache_len) row per slot (engine v1).
  * ``"paged"``      — a shared (num_blocks, block_size) page pool with
    per-slot block tables (vLLM idiom; see serving/paged.py).  Requests own
    only the pages their positions need, admission is gated on free pages,
    and the decode program gathers the pool through the tables into the
    same contiguous view the v1 program consumed — still one compiled
    decode shape.

Scheduling is slot-granular continuous batching: a FIFO queue admits work
into freed slots between decode steps (head-of-line: if the head request
does not fit — no slot, or not enough free pages — nothing behind it jumps
ahead), each slot tracks its own absolute position, and every request owns
an independent PRNG key stream folded from its uid.

Two driver loops share the same admission/decode core:

  * ``run``          — synchronous: admit-then-decode per step.
  * ``run_threaded`` — producer/consumer (MaxText JetThread+queue idiom):
    an injector thread sleeps until each arrival and feeds a BOUNDED
    backpressure queue, an admission thread blocks on capacity and prefills
    under the engine lock, and the decode loop runs on the calling thread.
    Greedy tokens are bitwise-identical to the synchronous loop because
    per-request sampling is independent of interleaving.

Supported models: decoder-only attention archs (dense / MoE / SWA).  RWKV
and SSM/hybrid state caches and encoder-decoder memory are per-request state
this slot scatter does not yet carry; MoE capacity routing is batch-coupled,
so MoE outputs can differ from unbatched decode.

Telemetry (``repro.core.telemetry``): the request lifecycle is a chain of
instants on the ``engine`` track — ``serving.enqueue`` ->
``serving.slot_assign`` -> ``serving.first_token`` -> ``serving.finish`` —
recorded in the ring when ``REPRO_TELEMETRY`` is on.  The host work is
split into spans, recorded in the ring and, while a JAX profiler session
collects, on the trace's ``/host:CPU`` plane beside the device's programs::

    serving.submit       submit(): validation, the key's fold_in, enqueue
    serving.step         step(): admissions, then one decode step
      serving.admit      _admit(): one request into its slot
        serving.prefill  the prefill call and its wait
          serving.prefill.wait     int(tok0), blocking on the device
      serving.decode_step          _decode_once() for a non-empty batch
        serving.decode.dispatch    key splits, uploads, the decode call
        serving.decode.wait        np.asarray(toks), blocking on the device
        serving.decode.emit        per-slot token loop and any finish

Both programs take the KV cache over (``donate_argnames``): XLA writes the
new cache into the old one's buffers, and the engine keeps only the
returned cache.  After each prefill and decode call the counter
``serving.cache.donated`` counts a cache taken over, and
``serving.cache.kept`` one the program left alive beside its copy.

``serving.decode_step`` carries ``step``, ``active`` and ``queued``.  Every
span opens and closes in Python around the compiled programs, never inside
them: telemetry changes no compiled shape and no sampled token.
"""

from __future__ import annotations

import dataclasses
import queue as _queue
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import telemetry as tel
from repro.models.attention import resolve_attention_backend
from repro.models.transformer import forward, init_caches
from repro.training.serve_step import decode_step, sample, sample_per_slot
from repro.serving.paged import (gather_caches, init_paged_caches,
                                 scatter_decode, scatter_prefill)
from repro.serving.request import Request, RequestQueue
from repro.serving.slots import (RESERVED_BLOCKS, TRASH_BLOCK, BlockAllocator,
                                 SENTINEL_BLOCK, SlotAllocator)


def scatter_slot_cache(big, small, slot):
    """Insert a batch=1 cache pytree into the engine cache at `slot`.

    Eager-layer leaves are (batch, ...); scan-segment leaves are stacked
    (n_layers, batch, ...) — the batch axis is 0 vs 1 respectively.
    """
    def upd(axis):
        return lambda b, s: jax.lax.dynamic_update_slice_in_dim(
            b, s.astype(b.dtype), slot, axis)

    return {
        "eager": jax.tree.map(upd(0), big["eager"], small["eager"]),
        "segments": [jax.tree.map(upd(1), bg, sm)
                     for bg, sm in zip(big["segments"], small["segments"])],
    }


def _count_donation(old) -> None:
    """Count whether the program just called took the cache ``old`` over
    (``serving.cache.donated``) or left it alive beside its new copy
    (``serving.cache.kept``)."""
    kept = not jax.tree.leaves(old)[0].is_deleted()
    tel.counter("serving.cache.kept" if kept else "serving.cache.donated",
                proc="engine")


class JetThread(threading.Thread):
    """Thread that records its exception instead of dying silently (MaxText
    offline-inference idiom) — the driver re-raises after join."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.error: Optional[BaseException] = None

    def run(self) -> None:
        try:
            super().run()
        except BaseException as exc:        # noqa: BLE001 — surfaced on join
            self.error = exc


class ServingEngine:
    def __init__(self, params, cfg: ModelConfig, *, num_slots: int = 4,
                 cache_len: int = 128, prefill_len: int = 32,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 temperature: float = 0.0, seed: int = 0,
                 attn_backend: Optional[str] = None,
                 cache_layout: str = "contiguous", block_size: int = 16,
                 num_blocks: Optional[int] = None):
        if cfg.rwkv or cfg.ssm_state or cfg.is_encoder_decoder:
            raise NotImplementedError(
                "slot engine supports decoder-only attention archs; "
                f"{cfg.name} carries per-request recurrent/encoder state")
        if prefill_buckets is None:
            buckets: Tuple[int, ...] = (int(prefill_len),)
        else:
            buckets = tuple(sorted({int(b) for b in prefill_buckets}))
        if not buckets or buckets[0] < 1:
            raise ValueError("prefill buckets must be positive")
        if buckets[-1] > cache_len:
            raise ValueError("prefill_len must fit in cache_len")
        if attn_backend is not None:
            cfg = dataclasses.replace(cfg, attn_backend=attn_backend)
        if cache_layout not in ("contiguous", "paged"):
            raise ValueError(f"unknown cache_layout {cache_layout!r}")
        self.params = params
        self.cfg = cfg
        # what the compiled programs will actually dispatch to (env var
        # applied; an unavailable backend raises here) — benchmark rows
        # report this
        self.attn_backends = {
            kind: resolve_attention_backend(kind, cfg.attn_backend)
            for kind in ("prefill", "decode")}
        self.num_slots = num_slots
        self.cache_len = cache_len
        self.prefill_buckets = buckets
        self.prefill_len = buckets[-1]       # largest admissible prompt
        self.temperature = temperature
        self.cache_layout = cache_layout

        if cache_layout == "paged":
            if num_blocks is None:
                # default: same KV footprint as the contiguous layout
                num_blocks = (num_slots * (cache_len // max(1, block_size))
                              + RESERVED_BLOCKS)
            self.block_size = block_size
            self.num_blocks = num_blocks
            self.pages_per_slot = cache_len // block_size
            self.balloc = BlockAllocator(num_blocks, block_size)
            self.block_tables = np.full(
                (num_slots, self.pages_per_slot), TRASH_BLOCK, np.int32)
            self._slot_blocks: List[List[int]] = [[] for _ in range(num_slots)]
            self.caches = init_paged_caches(
                cfg, num_slots=num_slots, cache_len=cache_len,
                block_size=block_size, num_blocks=num_blocks)
        else:
            self.caches = init_caches(cfg, num_slots, cache_len)
        self.tok_buf = np.zeros((num_slots, 1), np.int32)
        self.pos_buf = np.zeros((num_slots, 1), np.int32)
        self.slot_req: List[Optional[Request]] = [None] * num_slots
        self.slots = SlotAllocator(num_slots)
        self.queue = RequestQueue()
        self._base_key = jax.random.PRNGKey(seed)
        self._t0 = time.perf_counter()
        # run_threaded: every engine mutation happens under this lock; the
        # condition signals capacity changes (finish) and admissions
        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)

        self.stats: Dict[str, int] = {
            "prefill_traces": 0, "decode_traces": 0,
            "prefill_calls": 0, "decode_steps": 0,
            "requests_finished": 0, "tokens_generated": 0,
        }
        self._build_fns()

    # ------------------------------------------------------------------
    def _build_fns(self) -> None:
        cfg, cache_len, temp = self.cfg, self.cache_len, self.temperature
        stats = self.stats

        if self.cache_layout == "paged":
            ns, bs = self.num_slots, self.block_size

            def prefill_fn(params, tokens, lengths, table_row, slot, key,
                           caches):
                stats["prefill_traces"] += 1    # runs only when (re)traced
                small = init_caches(cfg, 1, cache_len)
                logits, small, _ = forward(params, cfg, tokens, caches=small,
                                           lengths=lengths, last_only=True)
                caches = scatter_prefill(caches, small, table_row, slot, cfg,
                                         cache_len=cache_len, block_size=bs)
                return sample(logits[:, -1], key, temp)[0], caches

            def decode_fn(params, tokens, positions, keys, caches, tables):
                stats["decode_traces"] += 1
                contig = gather_caches(caches, tables, cfg, num_slots=ns,
                                       cache_len=cache_len, block_size=bs)
                logits, contig = decode_step(params, cfg, tokens, positions,
                                             contig)
                caches = scatter_decode(caches, contig, positions[:, 0],
                                        tables, cfg, cache_len=cache_len,
                                        block_size=bs)
                return sample_per_slot(logits, keys, temp), caches
        else:
            def prefill_fn(params, tokens, lengths, slot, key, caches):
                stats["prefill_traces"] += 1    # runs only when (re)traced
                small = init_caches(cfg, 1, cache_len)
                logits, small, _ = forward(params, cfg, tokens, caches=small,
                                           lengths=lengths, last_only=True)
                caches = scatter_slot_cache(caches, small, slot)
                return sample(logits[:, -1], key, temp)[0], caches

            def decode_fn(params, tokens, positions, keys, caches):
                stats["decode_traces"] += 1
                logits, caches = decode_step(params, cfg, tokens, positions,
                                             caches)
                return sample_per_slot(logits, keys, temp), caches

        # the engine never reads a cache it has passed in again, so both
        # programs update it in place instead of copying it whole
        self._prefill = jax.jit(prefill_fn, donate_argnames=("caches",))
        self._decode = jax.jit(decode_fn, donate_argnames=("caches",))

    def _clock(self) -> float:
        return time.perf_counter() - self._t0

    def active_count(self) -> int:
        return self.slots.in_use()

    def _bucket_for(self, prompt_len: int) -> int:
        """Smallest ladder bucket that fits the prompt."""
        for b in self.prefill_buckets:
            if prompt_len <= b:
                return b
        raise AssertionError("unreachable: submit validated prompt_len")

    # ------------------------------------------------------------------
    def _validate(self, req: Request) -> None:
        if req.prompt_len < 1 or req.prompt_len > self.prefill_len:
            raise ValueError(
                f"prompt length {req.prompt_len} outside [1, "
                f"{self.prefill_len}]")
        if req.prompt_len + req.max_new_tokens > self.cache_len:
            raise ValueError("prompt + max_new_tokens exceeds cache_len")
        if self.cache_layout == "paged":
            need = self.balloc.blocks_for(req.prompt_len, req.max_new_tokens)
            if need > self.balloc.capacity():
                raise ValueError(
                    f"request needs {need} KV pages but the pool holds only "
                    f"{self.balloc.capacity()}")

    def submit(self, req: Request) -> None:
        with tel.span("serving.submit", proc="engine", uid=req.uid,
                      prompt_len=req.prompt_len):
            self._validate(req)
            if req.key is None:
                req.key = jax.random.fold_in(self._base_key, req.uid)
            self.queue.submit(req)
            tel.instant("serving.enqueue", proc="engine", uid=req.uid,
                        prompt_len=req.prompt_len,
                        max_new_tokens=req.max_new_tokens,
                        queue_depth=len(self.queue))

    def _has_capacity(self, req: Request) -> bool:
        """Can `req` be admitted right now?  A free slot always; the paged
        layout additionally needs the request's full page reservation."""
        if not self.slots.available():
            return False
        if self.cache_layout == "paged":
            return (self.balloc.available()
                    >= self.balloc.blocks_for(req.prompt_len,
                                              req.max_new_tokens))
        return True

    def _finish(self, slot: int, req: Request, now: float,
                finished: List[Request]) -> None:
        req.t_done = now
        self.slot_req[slot] = None
        self.slots.free(slot)
        if self.cache_layout == "paged":
            self.balloc.free(self._slot_blocks[slot])
            self._slot_blocks[slot] = []
            # inactive again: route this slot's garbage decode writes to
            # the trash page so they never land in a mapped page
            self.block_tables[slot] = TRASH_BLOCK
        self.stats["requests_finished"] += 1
        finished.append(req)
        tel.instant("serving.finish", proc="engine", uid=req.uid, slot=slot,
                    tokens=len(req.generated),
                    latency_s=req.t_done - req.arrival_time)
        tel.counter("serving.requests_finished", proc="engine")

    def _admit(self, req: Request, now: float,
               finished: List[Request]) -> None:
        slot = self.slots.alloc()
        L = req.prompt_len
        bucket = self._bucket_for(L)
        with tel.span("serving.admit", proc="engine", uid=req.uid,
                      slot=slot, prompt_len=L, bucket=bucket):
            self.slot_req[slot] = req
            req.t_admitted = now
            tel.instant("serving.slot_assign", proc="engine", uid=req.uid,
                        slot=slot, queued_s=now - req.arrival_time)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, bucket - L:] = req.prompt                # left-pad
            if self.temperature > 0.0:
                req.key, sub = jax.random.split(req.key)
            else:
                sub = req.key   # greedy: sample() never consumes the key
            if self.cache_layout == "paged":
                n_pages = self.balloc.blocks_for(L, req.max_new_tokens)
                pages = self.balloc.alloc(n_pages)       # full lifetime up
                self._slot_blocks[slot] = pages          # front: decode never
                row = np.full(self.pages_per_slot, SENTINEL_BLOCK, np.int32)
                row[:n_pages] = pages                    # hits an unowned page
                self.block_tables[slot] = row
            with tel.span("serving.prefill", proc="engine", uid=req.uid,
                          slot=slot, prompt_len=L, bucket=bucket):
                old = self.caches
                if self.cache_layout == "paged":
                    tok0, self.caches = self._prefill(
                        self.params, jnp.asarray(toks),
                        jnp.asarray([L], jnp.int32), jnp.asarray(row),
                        np.int32(slot), sub, self.caches)
                else:
                    tok0, self.caches = self._prefill(
                        self.params, jnp.asarray(toks),
                        jnp.asarray([L], jnp.int32), np.int32(slot), sub,
                        self.caches)
                _count_donation(old)
                with tel.span("serving.prefill.wait", proc="engine"):
                    tok0 = int(tok0)     # device sync
            self.stats["prefill_calls"] += 1
            now = self._clock()
            req.t_first_token = now
            req.t_tokens.append(now)
            tel.instant("serving.first_token", proc="engine", uid=req.uid,
                        slot=slot, ttft_s=now - req.arrival_time)
            req.generated.append(tok0)
            self.stats["tokens_generated"] += 1
            if (len(req.generated) >= req.max_new_tokens
                    or tok0 == req.eos_id):
                self._finish(slot, req, now, finished)
                return
            self.tok_buf[slot, 0] = tok0
            self.pos_buf[slot, 0] = L    # true length, not padded length

    # ------------------------------------------------------------------
    def _decode_once(self, finished: List[Request]) -> int:
        """Decode one token for every slot; appends newly finished requests
        to `finished` and returns how many finished."""
        active = self.active_count()
        if active == 0:
            return 0
        with tel.span("serving.decode_step", proc="engine",
                      step=self.stats["decode_steps"], active=active,
                      queued=len(self.queue)):
            n0 = len(finished)
            with tel.span("serving.decode.dispatch", proc="engine"):
                keys = np.zeros((self.num_slots, 2), np.uint32)
                if self.temperature > 0.0:   # greedy never reads the keys
                    for s, req in enumerate(self.slot_req):
                        if req is not None:
                            req.key, sub = jax.random.split(req.key)
                            keys[s] = np.asarray(sub)
                old = self.caches
                if self.cache_layout == "paged":
                    toks, self.caches = self._decode(
                        self.params, jnp.asarray(self.tok_buf),
                        jnp.asarray(self.pos_buf), jnp.asarray(keys),
                        self.caches, jnp.asarray(self.block_tables))
                else:
                    toks, self.caches = self._decode(
                        self.params, jnp.asarray(self.tok_buf),
                        jnp.asarray(self.pos_buf), jnp.asarray(keys),
                        self.caches)
                _count_donation(old)
            with tel.span("serving.decode.wait", proc="engine"):
                toks = np.asarray(toks)      # device sync
            self.stats["decode_steps"] += 1
            now = self._clock()
            with tel.span("serving.decode.emit", proc="engine"):
                for s, req in enumerate(self.slot_req):
                    if req is None:          # inactive slot: token ignored
                        continue
                    t = int(toks[s])
                    req.generated.append(t)
                    req.t_tokens.append(now)
                    self.stats["tokens_generated"] += 1
                    if (len(req.generated) >= req.max_new_tokens
                            or t == req.eos_id):
                        self._finish(s, req, now, finished)
                    else:
                        self.tok_buf[s, 0] = t
                        self.pos_buf[s, 0] += 1
            return len(finished) - n0

    def step(self, now: Optional[float] = None) -> List[Request]:
        """Admit ready requests into free slots, then decode one token for
        every slot.  Returns the requests that finished this step."""
        with tel.span("serving.step", proc="engine",
                      step=self.stats["decode_steps"]):
            if now is None:
                now = self._clock()
            finished: List[Request] = []
            first = True
            while self.slots.available():
                if not first:
                    # prefill takes real time: recompute the clock so later
                    # admits in the same step get honest t_admitted/queued_s
                    # and requests that arrived mid-prefill are checked now,
                    # not next step (stale-`now` admission bug)
                    now = max(now, self._clock())
                head = self.queue.peek_ready(now)
                if head is None or not self._has_capacity(head):
                    break                # FIFO head-of-line: no queue jumping
                self._admit(self.queue.pop_ready(now), now, finished)
                first = False
            self._decode_once(finished)
            return finished

    def run(self, requests: Sequence[Request]) -> List[Request]:
        """Serve a trace to completion, synchronously.  Resets the engine
        clock to 0, so `arrival_time` fields are relative to this call."""
        self._t0 = time.perf_counter()
        with tel.span("serving.run", proc="engine",
                      requests=len(requests), num_slots=self.num_slots):
            for req in sorted(requests, key=lambda r: r.arrival_time):
                self.submit(req)
            finished: List[Request] = []
            while self.queue or self.active_count():
                now = self._clock()
                if self.active_count() == 0 and not self.queue.has_ready(now):
                    # idle: sleep until the next arrival (capped so clock
                    # drift can't oversleep), not a 1 ms busy-spin
                    nxt = self.queue.next_arrival()
                    time.sleep(min(max(0.0, nxt - now), 0.05))
                    continue
                finished.extend(self.step(now))
        return finished

    # ------------------------------------------------------------------
    def run_threaded(self, requests: Sequence[Request], *,
                     backpressure: Optional[int] = None,
                     poll_s: float = 0.02) -> List[Request]:
        """Serve a trace with concurrent arrival injection, admission, and
        decode (MaxText JetThread+queue idiom).

        * injector thread — sleeps until each request's wall-clock arrival,
          then puts it on a BOUNDED queue (default ``2 * num_slots``); a put
          into a full queue blocks, which is the backpressure.
        * admission thread — pops arrivals, waits on the engine condition
          until the request fits (free slot + free pages), then prefills
          under the engine lock.
        * decode loop — runs here on the calling thread, also under the
          lock; finishing a request notifies the admission thread.

        Greedy tokens are bitwise-identical to ``run`` on the same trace:
        each request's continuation depends only on its own prompt and key
        stream, never on which step admitted it.
        """
        reqs = sorted(requests, key=lambda r: r.arrival_time)
        for r in reqs:                   # fail on the caller, not a thread
            self._validate(r)
            if r.key is None:
                r.key = jax.random.fold_in(self._base_key, r.uid)
        if backpressure is None:
            backpressure = max(2, 2 * self.num_slots)
        arrivals: _queue.Queue = _queue.Queue(maxsize=backpressure)
        finished: List[Request] = []
        admission_done = threading.Event()
        abort = threading.Event()
        self._t0 = time.perf_counter()

        def _put(item) -> bool:
            while not abort.is_set():
                try:
                    arrivals.put(item, timeout=poll_s)
                    return True
                except _queue.Full:
                    continue
            return False

        def inject() -> None:
            for r in reqs:
                wait = r.arrival_time - self._clock()
                if wait > 0:
                    time.sleep(wait)
                tel.instant("serving.enqueue", proc="engine", uid=r.uid,
                            prompt_len=r.prompt_len,
                            max_new_tokens=r.max_new_tokens,
                            queue_depth=arrivals.qsize())
                if not _put(r):
                    return
            _put(None)                   # sentinel: trace fully injected

        def admit() -> None:
            while not abort.is_set():
                try:
                    r = arrivals.get(timeout=poll_s)
                except _queue.Empty:
                    continue
                if r is None:
                    break
                with self._cond:
                    while not self._has_capacity(r):
                        if abort.is_set():
                            return
                        self._cond.wait(poll_s)
                    self._admit(r, self._clock(), finished)
                    self._cond.notify_all()
            admission_done.set()

        threads = [JetThread(target=inject, name="serving-inject",
                             daemon=True),
                   JetThread(target=admit, name="serving-admit",
                             daemon=True)]
        with tel.span("serving.run", proc="engine", requests=len(reqs),
                      num_slots=self.num_slots, mode="threaded",
                      backpressure=backpressure):
            for t in threads:
                t.start()
            while True:
                with self._cond:
                    if self.active_count():
                        if self._decode_once(finished):
                            self._cond.notify_all()   # capacity freed
                    elif admission_done.is_set():
                        break
                    else:
                        self._cond.wait(poll_s)
                if any(t.error is not None for t in threads):
                    break
            abort.set()
            for t in threads:
                t.join()
        for t in threads:
            if t.error is not None:
                raise t.error
        return finished
