"""Bring-up run of the main path on a TPU: compiled Pallas, no fallback.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the sharded science path, four chips

One chip runs these phases in order, in one process, catching nothing:

1. Science kernels at the paper's sizes through the portable registry with
   the compiled ``pallas`` backend, each validated against its XLA oracle
   at the registry's tolerance (``PortableKernel.validate``): stencil7 at
   L=512, the five BabelStream ops at 2^25 elements, miniBUDE on the
   bm1-shaped ``make_deck()`` deck, and Hartree-Fock on a 64-atom helium
   lattice.
2. A ``ServingEngine`` over granite-3-8b at its published widths, depth cut
   to fit one chip, random weights from a seed, ``attn_backend="pallas"``.
   It serves the requests once through ``ServingEngine.run``, asserts that
   the dispatch log shows ``pallas`` prefill and decode with no fallback,
   asserts the engine's compile bound, and checks greedy tokens against
   the unbatched ``serve_step.generate`` loop.

``--four-chips`` runs only the sharded science path and the single-device
results it is compared with: stencil7 at L=512 in slab and pencil
decompositions under ``xla_shard`` (halo overlap on) and ``shard_pallas``,
and ``shard_pallas`` BabelStream triad and miniBUDE, each at its bitwise
contract, with every output shard's device printed.

Each phase prints its seconds (compilation included where it says so),
shapes and the backend each call dispatched to.  The last line of standard
output is one JSON object naming the device; any failure exits nonzero
before it.  Without a TPU the script exits nonzero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.kernels  # noqa: E402,F401  (registers every backend)
from repro.configs import get_config  # noqa: E402
from repro.core import conformance  # noqa: E402
from repro.core.portable import get_kernel  # noqa: E402
from repro.kernels.hartree_fock import ref as hf_ref  # noqa: E402
from repro.kernels.minibude import ops as mb_ops  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402
from repro.models import attention as A  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serving import Request, ServingEngine  # noqa: E402
from repro.training import serve_step as SS  # noqa: E402

SEED = 0
STENCIL_L = 512
STREAM_N = 1 << 25
HF_ATOMS = 64

ARCH = "granite-3-8b"
#: 8 of granite's 40 layers: ~2.0 B float32 parameters, ~8 GB of the
#: chip's 16 GB, leaving room for the KV caches and the compiled programs
SERVE_LAYERS = 8
SLOTS = 4
CACHE_LEN = 1024
PREFILL_BUCKETS = (128,)
REQUESTS = 8
MAX_NEW = 16
#: the greedy check's near-tie tolerance, in units of the largest
#: reference logit.  The engine and the unbatched loop run the same bf16
#: math in different shapes (a left-padded 128-token bucket and a 4-slot
#: decode batch, against the exact prompt at batch 1), so the compiler may
#: tile their matmuls differently and round the bf16 logits differently by
#: a few units of 2^-7.  A differing token is accepted only where the
#: reference's best logit beats the engine's token by less than that.
NEAR_TIE = 4 * 2.0 ** -7


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg) -> None:
    if not ok:
        raise RuntimeError(msg)


def peak_bytes() -> int:
    return jax.devices()[0].memory_stats()["peak_bytes_in_use"]


def shapes(args) -> str:
    return " ".join(f"{a.dtype}{list(a.shape)}" for a in args)


def validate(name: str, args, backend: str, **kwargs) -> None:
    """Oracle vs ``backend`` at the registry tolerance, then one warm call
    of ``backend`` alone."""
    kernel = get_kernel(name)
    t0 = time.perf_counter()
    kernel.validate(*args, backend=backend, **kwargs)
    t_val = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(kernel(*args, backend=backend, **kwargs))
    t_warm = time.perf_counter() - t0
    log(f"  {name} [{backend}] {shapes(args)}: matches {kernel.oracle} at "
        f"tol={conformance.oracle_tolerance(name, backend)}; validate "
        f"{t_val:.3f} s (compile included), warm call {t_warm:.4f} s")


# --------------------------------------------------------------------------
# phase 1: science kernels at the paper's sizes
# --------------------------------------------------------------------------
def science_phase(backend: str, *, stencil_l: int, stream_n: int,
                  deck_kwargs: dict, hf_atoms: int) -> None:
    t0 = time.perf_counter()
    keys = jax.random.split(jax.random.PRNGKey(SEED), 4)
    log(f"phase science: backend={backend}")
    u = jax.random.normal(keys[0], (stencil_l,) * 3, jnp.float32)
    validate("stencil7", (u,), backend)
    del u
    a, b, c = (jax.random.normal(k, (stream_n,), jnp.float32)
               for k in keys[1:])
    for op, args in (("copy", (a,)), ("mul", (c,)), ("add", (a, b)),
                     ("triad", (b, c)), ("dot", (a, b))):
        validate(f"babelstream.{op}", args, backend)
    del a, b, c
    validate("minibude.fasten", mb_ops.make_deck(**deck_kwargs), backend)
    validate("hartree_fock.twoel", (hf_ref.helium_lattice(hf_atoms),
                                    hf_ref.initial_density(hf_atoms)),
             backend)
    log(f"phase science: ok in {time.perf_counter() - t0:.3f} s, "
        f"peak_bytes_in_use {peak_bytes()}")


# --------------------------------------------------------------------------
# phase 2: granite-width server
# --------------------------------------------------------------------------
def serve_config(layers: int):
    full = get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=layers)
    log(f"model {ARCH}: depth cut to {layers} of {full.n_layers} layers "
        f"(fits one chip); widths as published: d_model {cfg.d_model}, "
        f"{cfg.n_heads} query / {cfg.n_kv_heads} KV heads, head_dim "
        f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; "
        f"param_dtype {cfg.param_dtype}, compute_dtype {cfg.compute_dtype}")
    return cfg


def make_requests(vocab: int, n: int, max_prompt: int, max_new: int):
    rng = np.random.default_rng(SEED)
    return [Request(uid=i,
                    prompt=rng.integers(2, vocab, int(rng.integers(
                        max_prompt // 4, max_prompt + 1))).astype(np.int32),
                    max_new_tokens=max_new)
            for i in range(n)]


def serve_once(params, cfg, backend: str, *, slots: int, cache_len: int,
               buckets, requests):
    """Serve ``requests`` on a fresh engine; check dispatch and compiles.
    Returns {uid: generated tokens}."""
    A.reset_dispatch_log()
    engine = ServingEngine(params, cfg, num_slots=slots, cache_len=cache_len,
                           prefill_buckets=buckets, attn_backend=backend)
    t0 = time.perf_counter()
    done = engine.run(requests)
    dt = time.perf_counter() - t0
    s = engine.stats
    check(len(done) == len(requests), f"{len(done)} of {len(requests)} done")
    check(all(len(r.generated) == r.max_new_tokens for r in done),
          "a request stopped short")
    records = A.dispatch_records()
    kinds = {rec["kind"] for rec in records}
    check(kinds == {"prefill", "decode"}, records)
    for rec in records:
        check(rec["backend"] == backend and "fallback" not in rec, rec)
    check(s["decode_traces"] == 1, s)
    check(s["prefill_traces"] <= len(engine.prefill_buckets), s)
    log(f"  serve {len(done)} requests, {s['tokens_generated']} "
        f"tokens, {slots} slots, cache_len {cache_len}, buckets "
        f"{list(engine.prefill_buckets)}: {dt:.3f} s (compile included); "
        f"dispatch prefill={engine.attn_backends['prefill']} "
        f"decode={engine.attn_backends['decode']}, {len(records)} "
        f"dispatch records, no fallback; prefill_traces "
        f"{s['prefill_traces']} decode_traces {s['decode_traces']}; "
        f"peak_bytes_in_use {peak_bytes()}")
    return {r.uid: list(r.generated) for r in done}


def greedy_check(params, cfg, backend: str, req: Request, generated,
                 cache_len: int) -> None:
    """Batched engine vs unbatched ``serve_step.generate`` under the same
    backend: tokens must match, or differ first at a near tie."""
    want = [int(t) for t in np.asarray(SS.generate(
        params, cfg, jnp.asarray(req.prompt[None]),
        max_new_tokens=len(generated), cache_len=cache_len,
        attn_backend=backend)[0])]
    if want == generated:
        log(f"  greedy bit-match vs unbatched (req {req.uid}, prompt "
            f"{req.prompt_len}, {len(want)} tokens, backend={backend}): OK")
        return
    i = next(j for j, (w, g) in enumerate(zip(want, generated)) if w != g)
    ctx = np.concatenate([req.prompt, np.asarray(generated[:i], np.int32)])
    last, _, _ = SS.prefill(params, cfg, jnp.asarray(ctx[None]),
                            cache_len=cache_len, attn_backend=backend)
    ref = np.asarray(last[0, :cfg.vocab_size], np.float32)  # no pad columns
    gap = float(ref.max() - ref[generated[i]])
    tol = NEAR_TIE * float(np.abs(ref).max())
    log(f"  greedy MISMATCH vs unbatched (req {req.uid}) at token {i}: "
        f"engine {generated[i]} vs unbatched {want[i]}; reference logits "
        f"{ref[generated[i]]:.5f} vs {ref[want[i]]:.5f} (max "
        f"{ref.max():.5f}), gap {gap:.5f}, near-tie tolerance {tol:.5f}")
    check(gap <= tol, "batched decode diverged beyond a near tie")


def serving_phase(backend: str, *, layers: int, slots: int, cache_len: int,
                  buckets, n_requests: int, max_new: int) -> None:
    t0 = time.perf_counter()
    log(f"phase serving: backend={backend}")
    cfg = serve_config(layers)
    params = jax.jit(T.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(SEED))
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    n_bytes = sum(int(x.nbytes) for x in jax.tree.leaves(params))
    log(f"  params: {n_params} ({n_bytes} bytes), init "
        f"{time.perf_counter() - t0:.3f} s")
    requests = make_requests(cfg.vocab_size, n_requests, max(buckets),
                             max_new)
    log(f"  requests: {n_requests}, prompt lengths "
        f"{[r.prompt_len for r in requests]}, {max_new} new tokens each")
    served = serve_once(params, cfg, backend, slots=slots,
                        cache_len=cache_len, buckets=buckets,
                        requests=requests)
    engine_cfg = dataclasses.replace(cfg, attn_backend=backend)
    for req in requests[:2]:
        greedy_check(params, engine_cfg, backend, req, served[req.uid],
                     cache_len)
    log(f"phase serving: ok in {time.perf_counter() - t0:.3f} s, "
        f"peak_bytes_in_use {peak_bytes()}")


# --------------------------------------------------------------------------
# four chips: the sharded science path
# --------------------------------------------------------------------------
def check_sharded(name: str, args, backend: str, twin: str, n: int,
                  **kwargs) -> None:
    """``backend`` on ``n`` devices vs the single-device ``twin``, bitwise,
    with the output spread over ``n`` distinct devices."""
    kernel = get_kernel(name)
    t0 = time.perf_counter()
    want = jax.block_until_ready(kernel(*args, backend=twin))
    got = jax.block_until_ready(kernel(*args, backend=backend, **kwargs))
    dt = time.perf_counter() - t0
    devices = [str(s.device) for s in got.addressable_shards]
    check(len(set(devices)) == n, f"output not on {n} devices: {devices}")
    w, g = np.asarray(want), np.asarray(got)
    check(np.array_equal(w, g),
          f"{name}[{backend}] {kwargs}: {int(np.sum(w != g))} cells differ")
    log(f"  {name} [{backend} {kwargs}] {shapes(args)}: bitwise == {twin}; "
        f"{dt:.3f} s (compile included); shards on {devices}")


def four_chip_phase(n: int, *, stencil_l: int, stream_n: int,
                    deck_kwargs: dict) -> None:
    t0 = time.perf_counter()
    log(f"phase four-chip: {n} devices")
    u = jax.random.normal(jax.random.PRNGKey(SEED), (stencil_l,) * 3,
                          jnp.float32)
    for decomp, grid in (("slab", (n, 1)), ("pencil", (n // 2, 2))):
        check_sharded("stencil7", (u,), "xla_shard", "xla", n,
                      decomp=decomp, shard_grid=grid, overlap=True)
        check_sharded("stencil7", (u,), "shard_pallas", "pallas", n,
                      decomp=decomp, shard_grid=grid)
    del u
    keys = jax.random.split(jax.random.PRNGKey(SEED + 1), 2)
    b, c = (jax.random.normal(k, (stream_n,), jnp.float32) for k in keys)
    check_sharded("babelstream.triad", (b, c), "shard_pallas", "pallas", n,
                  num_shards=n)
    del b, c
    check_sharded("minibude.fasten", mb_ops.make_deck(**deck_kwargs),
                  "shard_pallas", "pallas", n, num_shards=n)
    log(f"phase four-chip: ok in {time.perf_counter() - t0:.3f} s, "
        f"peak_bytes_in_use {peak_bytes()}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded science path on four chips")
    args = ap.parse_args(argv)

    configure_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX platform {dev.platform!r})")
    count = len(jax.devices())
    log(f"device: platform {dev.platform}, device_kind {dev.device_kind}, "
        f"count {count}")
    t0 = time.perf_counter()
    if args.four_chips:
        if count != 4:
            raise SystemExit(f"chip_smoke: --four-chips needs 4 devices, "
                             f"have {count}")
        four_chip_phase(count, stencil_l=STENCIL_L, stream_n=STREAM_N,
                        deck_kwargs={})
    else:
        science_phase("pallas", stencil_l=STENCIL_L, stream_n=STREAM_N,
                      deck_kwargs={}, hf_atoms=HF_ATOMS)
        serving_phase("pallas", layers=SERVE_LAYERS, slots=SLOTS,
                      cache_len=CACHE_LEN, buckets=PREFILL_BUCKETS,
                      n_requests=REQUESTS, max_new=MAX_NEW)
    log(f"all phases ok in {time.perf_counter() - t0:.3f} s; "
        f"peak_bytes_in_use {peak_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}),
        flush=True)


if __name__ == "__main__":
    main()
