"""jit'd wrappers + registry entries for flash/decode attention.

Registers two kernel families the serving hot path dispatches through
(``models/attention.attend``):

  * ``attention.flash``  — prefill/train tiling, kernel layout
    q (B,H,S,Dh) / k,v (B,Kv,T,Dh), optional (B,S)/(B,T) position arrays
    for left-padded serving prefill;
  * ``attention.decode`` — single-query ring-buffer decode, *model-native*
    layout q (B,1,H,Dh) / k,v (B,T,Kv,Dh) / q_pos (B,1) / k_pos (B,T), so
    the ``xla`` oracle is literally the plain-XLA ``attend`` path serving
    has always run (bitwise, no layout moves).

The compiled ``pallas`` backend declares ``available=on_tpu`` and always
compiles (``interpret=False``); interpret mode is reached only through the
``pallas_interpret`` backend, so a Pallas timing on the chip is never an
interpreter timing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.portable import on_tpu, register_kernel
from repro.kernels.flash_attention import kernel as K
from repro.kernels.flash_attention.ref import decode_ref, flash_ref


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_pallas(q, k, v, q_pos=None, k_pos=None, *, causal=True, window=0,
                 bq=K.DEFAULT_BQ, bk=K.DEFAULT_BK, interpret=False):
    if q_pos is not None:
        b, h, s, _ = q.shape
        t = k.shape[2]
        q_pos = q_pos.astype(jnp.int32).reshape(b, s, 1)
        k_pos = k_pos.astype(jnp.int32).reshape(b, 1, t)
    return K.flash_attention(q, k, v, q_pos, k_pos, causal=causal,
                             window=window, bq=bq, bk=bk,
                             interpret=interpret)


flash_xla = jax.jit(flash_ref, static_argnames=("causal", "window"))


@functools.partial(jax.jit, static_argnames=("window", "bkv", "interpret"))
def decode_pallas(q, k, v, q_pos, k_pos, *, window=0, bkv=K.DEFAULT_BKV,
                  interpret=False):
    b, s, h, dh = q.shape          # s == 1 (single decode query)
    kv = k.shape[2]
    g = h // kv
    qk = q.reshape(b, h, dh).reshape(b, kv, g, dh)     # kv-major head order
    kk = jnp.moveaxis(k, 1, 2)                         # (B,Kv,T,Dh)
    vk = jnp.moveaxis(v, 1, 2)
    out = K.decode_attention(
        qk, kk, vk, q_pos.astype(jnp.int32),
        k_pos.astype(jnp.int32)[:, None, :], window=window, bkv=bkv,
        interpret=interpret)
    return out.reshape(b, h, dh).reshape(b, s, h, dh)


decode_xla = jax.jit(decode_ref, static_argnames=("window",))


def _flops_model(q, k, v, *pos, causal=True, **kw):
    b, h, s, dh = q.shape
    t = k.shape[2]
    pairs = s * t * (0.5 if causal and s == t else 1.0)
    return 4.0 * b * h * pairs * dh      # QK^T + PV


def _decode_flops_model(q, k, v, *pos, **kw):
    b, s, h, dh = q.shape                # model layout, s == 1
    return 4.0 * b * h * s * k.shape[1] * dh


_k = register_kernel("attention.flash", flops_model=_flops_model,
                     doc="flash attention (causal/windowed GQA), "
                         "online-softmax Pallas kernel")
_k.add_backend("xla", flash_xla)
_k.add_backend("pallas", flash_pallas, available=on_tpu)
_k.add_backend("pallas_interpret",
               functools.partial(flash_pallas, interpret=True))
# q/k block sizes of the online-softmax loop — must divide S and T
_k.declare_tunables(
    ("pallas", "pallas_interpret"),
    bq=(64, 128, 256, 512),
    bk=(64, 128, 256, 512),
    constraint=lambda p, q, k, v, *a, **kw:
        q.shape[2] % p["bq"] == 0 and k.shape[2] % p["bk"] == 0)
# the online-softmax output block is revisited across the k-axis grid —
# a declared rescale-and-accumulate output, not a write race
_k.declare_grid_contract(("pallas", "pallas_interpret"),
                         accumulator_outputs=(0,))


_kd = register_kernel("attention.decode", flops_model=_decode_flops_model,
                      doc="single-query GQA decode against a ring-buffer "
                          "KV cache (position-masked, leftpad -1 aware)")
_kd.add_backend("xla", decode_xla)
_kd.add_backend("pallas", decode_pallas, available=on_tpu)
_kd.add_backend("pallas_interpret",
                functools.partial(decode_pallas, interpret=True))
# cache-axis block size of the online-softmax loop — must divide cache_len
_kd.declare_tunables(
    ("pallas", "pallas_interpret"),
    bkv=(64, 128, 256, 512),
    constraint=lambda p, q, k, v, *a, **kw:
        k.shape[1] % p["bkv"] == 0 or k.shape[1] <= p["bkv"])
# same online-softmax accumulator shape along the cache-axis grid
_kd.declare_grid_contract(("pallas", "pallas_interpret"),
                          accumulator_outputs=(0,))
# single-query decode re-reads the whole KV cache per token (AI ~1):
# memory-bound on every modeled chip ridge
_kd.declare_roofline_contract(("xla", "pallas", "pallas_interpret"),
                              bound="memory")
