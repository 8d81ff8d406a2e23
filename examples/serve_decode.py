"""Serving driver: fixed-shape continuous batching on the slot engine.

    PYTHONPATH=src python examples/serve_decode.py --requests 12 --slots 4

Requests arrive on a Poisson-ish trace with ragged prompt lengths and are
admitted into freed KV-cache slots mid-decode (continuous batching at slot
granularity).  The engine compiles exactly two programs — one (1,
prefill_len) masked prefill and one (num_slots, 1) decode step — and never
recompiles as requests arrive/finish: prompts are left-padded to the fixed
prefill shape with pads masked out of attention (no attending over pad
token 0), decode positions track each request's TRUE prompt length, and
every request samples from its own PRNG key stream (no repeated
continuations across batches).

``--attn-backend`` picks the registry attention backend the two compiled
programs dispatch to (``xla`` oracle / ``pallas`` on TPU /
``pallas_interpret`` host-sim — see models/attention).  After serving, two
finished greedy requests are replayed through the *unbatched*
``serve_step.generate`` loop under the same backend and the token-level
bit-match result is printed.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import configure_compile_cache
from repro.models import transformer as T
from repro.serving import ServingEngine, latency_summary, synthetic_trace
from repro.training import serve_step as SS


def main() -> None:
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--prefill-len", type=int, default=16)
    ap.add_argument("--rate", type=float, default=50.0,
                    help="mean request arrival rate (requests/second)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--attn-backend", default=None,
                    choices=["xla", "pallas", "pallas_interpret"],
                    help="registry attention backend (default: plain-XLA "
                         "oracle path; REPRO_ATTN_BACKEND overrides)")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(params, cfg, num_slots=args.slots,
                           cache_len=args.cache_len,
                           prefill_len=args.prefill_len,
                           temperature=args.temperature,
                           attn_backend=args.attn_backend)
    print(f"attention dispatch: requested={args.attn_backend or 'auto'} "
          f"resolved prefill={engine.attn_backends['prefill']} "
          f"decode={engine.attn_backends['decode']}")

    trace = synthetic_trace(args.requests, vocab_size=cfg.vocab_size,
                            rate=args.rate, max_prompt=args.prefill_len,
                            max_new_tokens=args.max_new)
    t_start = time.time()
    done = engine.run(trace)
    dt = time.time() - t_start

    for req in sorted(done, key=lambda r: r.uid):
        print(f"req {req.uid:3d} prompt_len {req.prompt_len:2d} "
              f"latency {req.latency() * 1e3:7.1f} ms "
              f"tokens {req.generated[:8]}...")
    lat = latency_summary(done)
    s = engine.stats
    print(f"\nserved {len(done)} requests, {s['tokens_generated']} tokens "
          f"in {dt:.2f}s ({s['tokens_generated'] / dt:.1f} tok/s)")
    print(f"latency p50 {lat['p50_latency_s'] * 1e3:.1f} ms "
          f"p95 {lat['p95_latency_s'] * 1e3:.1f} ms "
          f"p99 {lat['p99_latency_s'] * 1e3:.1f} ms; "
          f"ttft p50 {lat['p50_ttft_s'] * 1e3:.1f} ms "
          f"p99 {lat['p99_ttft_s'] * 1e3:.1f} ms"
          + (f"; itl p50 {lat['p50_itl_s'] * 1e3:.2f} ms "
             f"p99 {lat['p99_itl_s'] * 1e3:.2f} ms"
             if "p99_itl_s" in lat else ""))
    print(f"compiled shapes: prefill x{s['prefill_traces']} "
          f"decode x{s['decode_traces']} "
          f"({s['prefill_calls']} prefills, {s['decode_steps']} decode steps)")
    assert s["prefill_traces"] == 1 and s["decode_traces"] == 1, \
        "engine recompiled — fixed-shape contract violated"

    if args.temperature == 0.0:
        # oracle-vs-Pallas dispatch demo: replay two finished requests
        # through the unbatched generate loop under the SAME backend — the
        # batched↔unbatched greedy bit-match must hold per backend
        for req in sorted(done, key=lambda r: r.uid)[:2]:
            want = SS.generate(params, engine.cfg,
                               jnp.asarray(np.asarray(req.prompt)[None]),
                               max_new_tokens=len(req.generated),
                               cache_len=args.cache_len,
                               attn_backend=args.attn_backend)
            match = req.generated == list(np.asarray(want[0]))
            print(f"bit-match vs unbatched greedy (req {req.uid}, "
                  f"backend={engine.attn_backends['decode']}): "
                  f"{'OK' if match else 'MISMATCH'}")
            assert match, "batched decode diverged from unbatched"


if __name__ == "__main__":
    main()
