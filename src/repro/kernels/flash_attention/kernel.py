"""Flash attention (causal/windowed GQA) as Pallas-TPU kernels.

Two kernel shapes share the online-softmax machinery:

* ``flash_attention`` — prefill/train tiling, grid (B, H, n_q, n_k) with the
  k axis innermost and sequential on TPU, so the running state (m, l, acc)
  lives in VMEM scratch persisting across k steps; the output BlockSpec maps
  every k step of one (b, h, qi) cell to the same block and is written on
  the last step.  GQA is expressed in the k/v index maps (h -> h // group).
  Masking is index-based by default; passing ``q_pos``/``k_pos`` switches to
  *position-based* masking (``k_pos == -1`` marks empty/pad slots — the
  serving engine's left-padded prefill), under the contract that positions
  are index-aligned up to a non-negative per-row left-pad offset
  (``pos[i] <= i``, real tokens contiguous).  The causal block-skip
  predicate stays sound under that contract; the window block-skip is only
  applied in index mode (a left-pad offset shifts which low blocks a window
  reaches, so position mode visits them all and lets the mask decide).

* ``decode_attention`` — single-query serving decode against a ring-buffer
  KV cache, grid (B, Kv, n_t) with the cache axis innermost/sequential.
  The cache carries the absolute position of every slot (-1 = empty), so
  wraparound needs no special handling: masking is purely position-based
  (``kp >= 0 & kp <= qp`` + optional sliding window) and slot order never
  matters.  Every slot block is visited (ring order is arbitrary).  At
  least one cache slot must be valid per row (the decode path always writes
  the current token's K/V before attending) — an all-masked row returns 0
  where the XLA oracle returns a uniform average of v, both garbage by
  contract.

BlockSpec dims are (bq x dh) / (bk x dh) MXU-aligned tiles; softmax state
accumulates in fp32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BQ = 256
DEFAULT_BK = 256
DEFAULT_BKV = 256


def _flash_body(*refs, bq: int, bk: int, n_k: int, causal: bool, window: int,
                scale: float, has_pos: bool):
    if has_pos:
        q_ref, k_ref, v_ref, qp_ref, kp_ref, o_ref, m_scr, l_scr, acc_scr \
            = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
        qp_ref = kp_ref = None
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = qi * bq
    k_lo = ki * bk
    # static-shape predicate: does this k block intersect the mask at all?
    # In position mode the causal skip stays sound (pos[i] <= i with a
    # shared per-row offset for q, and cache slots holding pos in {-1, s}),
    # but the window skip is index-distance based and a left-pad offset
    # shrinks the position distance — so it only applies in index mode.
    run = True
    if causal:
        run = k_lo <= q_lo + bq - 1
    if window and not has_pos:
        run = run & (k_lo + bk - 1 >= q_lo - (window - 1))

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)

        if has_pos:
            qp = qp_ref[0]                           # (bq, 1) int32
            kp = kp_ref[0]                           # (1, bk) int32
            mask = kp >= 0                           # empty/pad slots
        else:
            qp = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kp = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = mask & (kp <= qp)
        if window:
            mask = mask & ((qp - kp) < window)
        logits = jnp.where(jnp.broadcast_to(mask, (bq, bk)), logits, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, q_pos=None, k_pos=None, *, causal: bool = True,
                    window: int = 0, bq: int = DEFAULT_BQ,
                    bk: int = DEFAULT_BK, interpret: bool = False):
    """q (B,H,S,Dh), k/v (B,Kv,T,Dh) -> (B,H,S,Dh).

    q_pos (B,S,1) / k_pos (B,1,T) int32 absolute positions (pass both or
    neither); -1 marks empty/pad slots.  Without them masking is
    index-based (token i at position i).
    """
    b, h, s, dh = q.shape
    kv, t = k.shape[1], k.shape[2]
    g = h // kv
    bq = min(bq, s)
    bk = min(bk, t)
    if s % bq or t % bk:
        raise ValueError(f"S={s}/T={t} must divide block sizes {bq}/{bk}")
    if (q_pos is None) != (k_pos is None):
        raise ValueError("pass both q_pos and k_pos, or neither")
    has_pos = q_pos is not None
    n_q, n_k = s // bq, t // bk
    scale = 1.0 / math.sqrt(dh)

    body = functools.partial(_flash_body, bq=bq, bk=bk, n_k=n_k,
                             causal=causal, window=window, scale=scale,
                             has_pos=has_pos)
    in_specs = [
        pl.BlockSpec((1, 1, bq, dh), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        pl.BlockSpec((1, 1, bk, dh),
                     lambda b_, h_, qi, ki: (b_, h_ // g, ki, 0)),
        pl.BlockSpec((1, 1, bk, dh),
                     lambda b_, h_, qi, ki: (b_, h_ // g, ki, 0)),
    ]
    args = [q, k, v]
    if has_pos:
        in_specs += [
            pl.BlockSpec((1, bq, 1), lambda b_, h_, qi, ki: (b_, qi, 0)),
            pl.BlockSpec((1, 1, bk), lambda b_, h_, qi, ki: (b_, 0, ki)),
        ]
        args += [q_pos, k_pos]
    return pl.pallas_call(
        body,
        grid=(b, h, n_q, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, bq, dh),
                               lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max m
            pltpu.VMEM((bq, 1), jnp.float32),    # running denom l
            pltpu.VMEM((bq, dh), jnp.float32),   # running accumulator
        ],
        interpret=interpret,
    )(*args)


# --------------------------------------------------------------------------
# decode: single query against the ring-buffer KV cache
# --------------------------------------------------------------------------
def _decode_body(q_ref, k_ref, v_ref, qp_ref, kp_ref, o_ref, m_scr, l_scr,
                 acc_scr, *, n_t: int, window: int, scale: float):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)              # (G, dh)
    k = k_ref[0, 0].astype(jnp.float32)              # (bkv, dh)
    v = v_ref[0, 0].astype(jnp.float32)
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (G, bkv)

    qp = qp_ref[pl.program_id(0)]                    # scalar int32 (SMEM)
    kp = kp_ref[0]                                   # (1, bkv) int32
    mask = (kp >= 0) & (kp <= qp)                    # empty slots + causal
    if window:
        mask = mask & ((qp - kp) < window)
    logits = jnp.where(jnp.broadcast_to(mask, logits.shape), logits, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ti == n_t - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def decode_attention(q, k, v, q_pos, k_pos, *, window: int = 0,
                     bkv: int = DEFAULT_BKV, interpret: bool = False):
    """q (B,Kv,G,Dh), k/v (B,Kv,T,Dh), q_pos (B,1), k_pos (B,1,T) int32
    -> (B,Kv,G,Dh).

    One query token per row against a position-annotated KV cache: slot
    order is arbitrary (ring buffers arrive as stored), ``k_pos == -1``
    marks empty slots, and ``window > 0`` additionally restricts to
    ``q_pos - k_pos < window``.  ``bkv`` tiles the cache axis.
    """
    b, kv, g, dh = q.shape
    t = k.shape[2]
    bkv = min(bkv, t)
    if t % bkv:
        raise ValueError(f"cache length T={t} must divide block size {bkv}")
    n_t = t // bkv
    scale = 1.0 / math.sqrt(dh)

    body = functools.partial(_decode_body, n_t=n_t, window=window,
                             scale=scale)
    return pl.pallas_call(
        body,
        grid=(b, kv, n_t),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh), lambda b_, k_, ti: (b_, k_, 0, 0)),
            pl.BlockSpec((1, 1, bkv, dh), lambda b_, k_, ti: (b_, k_, ti, 0)),
            pl.BlockSpec((1, 1, bkv, dh), lambda b_, k_, ti: (b_, k_, ti, 0)),
            # every row's query position, whole in SMEM: a (1, 1) block of
            # a (B, 1) array breaks the TPU (8, 128) tiling rule for B > 1
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, bkv), lambda b_, k_, ti: (b_, 0, ti)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh),
                               lambda b_, k_, ti: (b_, k_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),     # running max m
            pltpu.VMEM((g, 1), jnp.float32),     # running denom l
            pltpu.VMEM((g, dh), jnp.float32),    # running accumulator
        ],
        interpret=interpret,
    )(q, k, v, q_pos.reshape(b), k_pos)
