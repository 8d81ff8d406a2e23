"""GQA attention: full / sliding-window / cross, prefill + ring-buffer decode.

Masks are position-based: the KV cache carries the absolute position of every
slot (-1 = empty), so full caches and sliding-window ring buffers share one
code path.  Softmax accumulates in fp32.

Since PR 6 this module is a *registry consumer*: ``attend`` is a dispatcher
that routes prefill-shaped calls to the ``attention.flash`` portable kernel
and single-query decode calls to ``attention.decode`` (see
``kernels/flash_attention/ops.py``), with backend selection via the
``REPRO_ATTN_BACKEND`` env var or an explicit ``backend=`` argument
(``ModelConfig.attn_backend`` threads it here; a requested backend that
cannot run here raises ``BackendUnavailableError``), and tuned block sizes
injected from the persistent tuning cache.  The plain-XLA math lives in ``attend_xla`` — the
registry oracle for both kernel entries, and the path every call takes when
no backend is requested, so training and default serving are bitwise
unchanged.

Dispatch happens at trace time (all decisions are static on shapes/flags),
and each routing decision lands in a bounded dispatch stream so benchmarks
can report *which* backend and tuning provenance a timed program actually
used (``reset_dispatch_log`` / ``dispatch_log`` for the last decision per
kind, ``dispatch_records`` for the full history) — and, when telemetry is
enabled, as ``attn.dispatch`` events on the shared trace.

Soundness contract for the Pallas prefill route: positions must be
index-aligned up to a non-negative per-row left-pad offset (``pos[i] <= i``,
real tokens contiguous, -1 pads) — exactly what ``leftpad_positions`` and
training's ``arange`` produce.  ``attention_apply`` clears the
``k_index_aligned`` hint whenever the KV ring buffer can wrap
(``cache_len < s``) or cross-attention memory carries arbitrary positions,
and the dispatcher then keeps those calls on the XLA path.
"""

from __future__ import annotations

import math
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import telemetry as tel
from repro.models.common import Params, apply_rope, dense_init

NEG_INF = -1e30

ATTN_BACKEND_ENV = "REPRO_ATTN_BACKEND"

#: dispatcher kind -> registry kernel name
ATTN_KERNELS = {"prefill": "attention.flash", "decode": "attention.decode"}


def attention_init(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, dtype, n_layers_scale: int = 1) -> Params:
    ks = jax.random.split(key, 4)
    out_scale = 1.0 / math.sqrt(2 * n_layers_scale)
    return {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, n_kv_heads * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, n_kv_heads * head_dim, dtype),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model, dtype,
                         out_scale),
    }


def init_cache(batch: int, cache_len: int, n_kv_heads: int, head_dim: int,
               dtype) -> Dict[str, jnp.ndarray]:
    return {
        "k": jnp.zeros((batch, cache_len, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, cache_len, n_kv_heads, head_dim), dtype),
        "pos": jnp.full((batch, cache_len), -1, jnp.int32),
    }


def _gqa_scores(q, k, n_kv_heads):
    """q (B,S,H,Dh), k (B,T,Kv,Dh) -> (B,Kv,G,S,T) fp32 logits."""
    b, s, h, dh = q.shape
    g = h // n_kv_heads
    qg = q.reshape(b, s, n_kv_heads, g, dh)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32)
    return logits * (1.0 / math.sqrt(dh))


def _gqa_combine(weights, v):
    """weights (B,Kv,G,S,T), v (B,T,Kv,Dh) -> (B,S,H,Dh)."""
    b, kv, g, s, t = weights.shape
    # keep v in its storage dtype; accumulate in f32 (avoids materializing
    # an f32 copy of the full KV cache on the decode path)
    out = jnp.einsum("bkgst,btkd->bskgd", weights.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, kv * g, v.shape[-1])


def _masked_softmax(logits, mask):
    logits = jnp.where(mask, logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - jax.lax.stop_gradient(m))
    return e / jnp.sum(e, axis=-1, keepdims=True)


CHUNKED_THRESHOLD = 2048  # use flash-style path when S_q * T is large


def attend_xla(q, k, v, q_pos, k_pos, *, n_kv_heads: int, causal: bool,
               window: int = 0,
               bf16_intermediates: bool = False) -> jnp.ndarray:
    """Plain-XLA position-masked GQA attention — the registry oracle.

    q (B,S,H,Dh), k/v (B,T,Kv,Dh); q_pos (B,S) / k_pos (B,T) absolute
    positions, k_pos == -1 marks empty cache slots.  window > 0 additionally
    restricts to q_pos - k_pos < window.  Long sequences dispatch to the
    flash-style chunked path automatically.
    """
    s, t = q.shape[1], k.shape[1]
    if s >= CHUNKED_THRESHOLD and t >= CHUNKED_THRESHOLD \
            and s % 512 == 0 and t % 1024 == 0:
        from repro.models.chunked_attention import attend_chunked
        return attend_chunked(q, k, v, q_pos, k_pos, n_kv_heads=n_kv_heads,
                              causal=causal, window=window,
                              bf16_intermediates=bf16_intermediates)
    logits = _gqa_scores(q, k, n_kv_heads)              # (B,Kv,G,S,T)
    qp = q_pos[:, None, None, :, None]
    kp = k_pos[:, None, None, None, :]
    mask = kp >= 0
    if causal:
        mask &= kp <= qp
    if window:
        mask &= (qp - kp) < window
    weights = _masked_softmax(logits, mask)
    return _gqa_combine(weights, v).astype(q.dtype)


# --------------------------------------------------------------------------
# registry dispatch
# --------------------------------------------------------------------------
#: how many routing decisions the bounded dispatch stream retains.  The
#: pre-PR-8 log was a dict keyed only by kind — concurrent engines or
#: repeated per-backend benchmark rows silently overwrote each other's
#: records; the stream keeps the full recent history (oldest evicted).
DISPATCH_LOG_CAP = 256

_DISPATCH_RECORDS = tel.RingLog(capacity=DISPATCH_LOG_CAP)


def reset_dispatch_log() -> None:
    """Clear the trace-time routing record (call before (re)compiling the
    program whose dispatch you want to observe)."""
    _DISPATCH_RECORDS.clear()


def dispatch_log() -> Dict[str, Dict[str, Any]]:
    """Snapshot of the *last* routing decision per dispatch kind
    (``"prefill"`` / ``"decode"``): resolved backend, tuning provenance
    (``"exhaustive"`` / ``"coordinate"`` / ``"miss-default"``), injected
    params, and the reason when a Pallas route fell back to XLA.

    Populated at *trace* time: a jit cache hit re-runs no dispatch and
    leaves the log untouched.  This is the last-decision-per-kind view the
    benchmark rows read; the full bounded history (every decision, in
    order, across engines/backends) is :func:`dispatch_records`.
    """
    out: Dict[str, Dict[str, Any]] = {}
    for rec in _DISPATCH_RECORDS.records():
        fields = dict(rec)
        out[fields.pop("kind")] = fields
    return out


def dispatch_records() -> list:
    """The full bounded dispatch stream, oldest first: each record carries
    ``kind`` plus the fields of :func:`dispatch_log`.  Survives the
    last-write-wins collapse — two engines tracing concurrently, or one
    benchmark tracing per-backend rows back to back, each keep their
    entries (up to ``DISPATCH_LOG_CAP``)."""
    return _DISPATCH_RECORDS.records()


def _log(kind: str, **fields: Any) -> None:
    _DISPATCH_RECORDS.append({"kind": kind, **fields})
    tel.instant("attn.dispatch", proc="dispatch", kind=kind, **fields)
    tel.counter(f"attn.dispatch.{kind}.{fields.get('backend', '?')}",
                proc="dispatch")
    if "fallback" in fields:
        tel.counter("attn.dispatch.fallback", proc="dispatch")


def _requested_backend(backend: Optional[str]) -> Optional[str]:
    """Explicit request for this call: env var wins over the argument;
    ``None`` / ``""`` / ``"auto"`` mean "no request" (XLA status quo)."""
    env = os.environ.get(ATTN_BACKEND_ENV, "").strip()
    if env and env.lower() != "auto":
        return env
    if backend is None or backend in ("", "auto"):
        return None
    return backend


def _get_kernel(kind: str):
    """Registry entry for one dispatch kind, importing ``repro.kernels``
    lazily (ops.py imports this module for its oracles — the registry can
    only be consulted after both are loaded)."""
    from repro.core.portable import registry
    import repro.kernels  # noqa: F401  (side effect: registers kernels)
    return registry.get(ATTN_KERNELS[kind])


def resolve_attention_backend(kind: str,
                              backend: Optional[str] = None) -> str:
    """Resolve the attention backend for one dispatch kind.

    Precedence: ``REPRO_ATTN_BACKEND`` env var > explicit ``backend``
    argument > ``"xla"`` (the status-quo plain-XLA path).  A requested
    backend that exists but is unavailable on this host (e.g. ``"pallas"``
    off-TPU) raises ``BackendUnavailableError`` with the probe's reason —
    it never silently becomes ``"xla"``; an *unknown* name raises
    ``KeyError`` so config typos surface immediately.
    """
    if kind not in ATTN_KERNELS:
        raise KeyError(f"unknown attention dispatch kind {kind!r}; "
                       f"have {sorted(ATTN_KERNELS)}")
    req = _requested_backend(backend)
    if req is None or req == "xla":
        return "xla"
    kernel = _get_kernel(kind)
    if req not in kernel.backends:
        raise KeyError(
            f"unknown attention backend {req!r} for {ATTN_KERNELS[kind]!r}; "
            f"have {sorted(kernel.backends)}")
    kernel._require_available(req)      # BackendUnavailableError if not
    return req


def _tuned_params(kernel, *args, backend: str, **kwargs):
    """(params, provenance) for this exact call from the tuning cache."""
    from repro.core import tuning
    hit = tuning.cached_entry(kernel, *args, backend=backend, **kwargs)
    if hit is None:
        return {}, "miss-default"
    return tuning.params_from_cache(hit["params"]), \
        hit.get("search", "exhaustive")


def attend(q, k, v, q_pos, k_pos, *, n_kv_heads: int, causal: bool,
           window: int = 0, bf16_intermediates: bool = False,
           backend: Optional[str] = None,
           k_index_aligned: bool = True) -> jnp.ndarray:
    """Position-masked GQA attention, dispatched through the kernel registry.

    Same contract as :func:`attend_xla` (which also remains the default
    path).  ``backend`` requests a registry backend by name (``"pallas"``,
    ``"pallas_interpret"``, ``"xla"``; env var ``REPRO_ATTN_BACKEND``
    overrides).  Single-query causal calls route to ``attention.decode``,
    prefill-shaped calls to ``attention.flash``; calls the kernels cannot
    express (block misalignment, ring-wrapped prefill caches flagged via
    ``k_index_aligned=False``) fall back to XLA and record why in the
    dispatch log.  Tuned block sizes are injected from the tuning cache
    (miss -> declared defaults).
    """
    s, t = q.shape[1], k.shape[1]
    kind = "decode" if (causal and s == 1) else "prefill"
    resolved = resolve_attention_backend(kind, backend)

    if resolved != "xla":
        kernel = _get_kernel(kind)
        if kind == "decode":
            params, prov = _tuned_params(kernel, q, k, v, q_pos, k_pos,
                                         backend=resolved, window=window)
            from repro.kernels.flash_attention.kernel import decode_block
            bkv = params.get("bkv") or decode_block(
                t, k.shape[2], k.shape[3], k.dtype.itemsize)
            if bkv is not None and t % min(bkv, t) == 0:
                _log(kind, backend=resolved, kernel=kernel.name,
                     tuning=prov, params=params)
                return kernel(q, k, v, q_pos, k_pos, backend=resolved,
                              window=window, **params)
            _log(kind, backend="xla", kernel=kernel.name, tuning="n/a",
                 params={}, fallback=f"cache_len {t} not divisible by "
                                     f"a decode block size")
        else:
            qk = jnp.moveaxis(q, 2, 1)           # (B,H,S,Dh) kernel layout
            kk = jnp.moveaxis(k, 2, 1)           # (B,Kv,T,Dh)
            vk = jnp.moveaxis(v, 2, 1)
            params, prov = _tuned_params(kernel, qk, kk, vk, q_pos, k_pos,
                                         backend=resolved, causal=causal,
                                         window=window)
            bq = min(params.get("bq", 256), s)
            bk = min(params.get("bk", 256), t)
            aligned = s % bq == 0 and t % bk == 0
            if not (causal and not k_index_aligned) and aligned:
                _log(kind, backend=resolved, kernel=kernel.name,
                     tuning=prov, params=params)
                out = kernel(qk, kk, vk, q_pos, k_pos, backend=resolved,
                             causal=causal, window=window, **params)
                return jnp.moveaxis(out, 1, 2)
            reason = (f"S={s}/T={t} not divisible by blocks {bq}/{bk}"
                      if not aligned else
                      "causal prefill against a wrapped/unaligned KV ring")
            _log(kind, backend="xla", kernel=kernel.name, tuning="n/a",
                 params={}, fallback=reason)
    else:
        _log(kind, backend="xla", kernel=ATTN_KERNELS[kind], tuning="n/a",
             params={})

    return attend_xla(q, k, v, q_pos, k_pos, n_kv_heads=n_kv_heads,
                      causal=causal, window=window,
                      bf16_intermediates=bf16_intermediates)


def attention_apply(p: Params, x: jnp.ndarray, *, n_heads: int,
                    n_kv_heads: int, head_dim: int, positions: jnp.ndarray,
                    causal: bool = True, window: int = 0,
                    use_rope: bool = True, rope_theta: float = 1e4,
                    cache: Optional[Dict[str, jnp.ndarray]] = None,
                    memory_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                    memory_pos: Optional[jnp.ndarray] = None,
                    bf16_intermediates: bool = False,
                    backend: Optional[str] = None,
                    ) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """One attention sublayer.

    * training / prefill: cache=None, full-sequence self attention.
    * decode: cache holds K/V/pos ring buffer; x is (B, 1, D).
    * cross attention: memory_kv=(k, v) precomputed from encoder output
      (memory_pos gives their positions; causal must be False).
    ``backend`` selects the registry attention backend (see ``attend``).
    Returns (output, updated_cache).
    """
    b, s, d = x.shape
    q = (x @ p["wq"]).reshape(b, s, n_heads, head_dim)
    if use_rope:
        q = apply_rope(q, positions, rope_theta)

    k_index_aligned = True
    if memory_kv is not None:
        k, v = memory_kv
        k_pos = memory_pos
        new_cache = cache
        k_index_aligned = False      # encoder memory: arbitrary positions
    else:
        k = (x @ p["wk"]).reshape(b, s, n_kv_heads, head_dim)
        v = (x @ p["wv"]).reshape(b, s, n_kv_heads, head_dim)
        if use_rope:
            k = apply_rope(k, positions, rope_theta)
        if cache is None:
            k_pos = positions
            new_cache = None
        else:
            cache_len = cache["k"].shape[1]
            # ring-buffer slot for each new token; pad tokens (position -1,
            # masked prefill) are routed out of bounds and dropped — slot
            # -1 % cache_len would collide with a real token's slot on
            # sliding-window ring buffers shorter than the padded length
            slots = jnp.where(positions >= 0, positions % cache_len,
                              cache_len)                 # (B, S)
            bidx = jnp.arange(b, dtype=jnp.int32)[:, None]
            ck = cache["k"].at[bidx, slots].set(k, mode="drop")
            cv = cache["v"].at[bidx, slots].set(v, mode="drop")
            cpos = cache["pos"].at[bidx, slots].set(positions, mode="drop")
            new_cache = {"k": ck, "v": cv, "pos": cpos}
            k, v, k_pos = ck, cv, cpos
            # a multi-token prefill against a ring shorter than the padded
            # length can wrap: slot index no longer tracks position, so the
            # flash prefill kernel's index-based block skip is unsound
            k_index_aligned = s == 1 or cache_len >= s

    out = attend(q, k, v, positions, k_pos, n_kv_heads=n_kv_heads,
                 causal=causal, window=window,
                 bf16_intermediates=bf16_intermediates, backend=backend,
                 k_index_aligned=k_index_aligned)
    return out.reshape(b, s, n_heads * head_dim) @ p["wo"], new_cache


def cross_kv(p: Params, memory: jnp.ndarray, n_kv_heads: int,
             head_dim: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Precompute cross-attention K/V from encoder memory (B, T, D)."""
    b, t, _ = memory.shape
    k = (memory @ p["wk"]).reshape(b, t, n_kv_heads, head_dim)
    v = (memory @ p["wv"]).reshape(b, t, n_kv_heads, head_dim)
    return k, v
