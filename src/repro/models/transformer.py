"""Model assembly: decoder-only / encoder-decoder LMs over a *layer plan*.

Layers are executed through a plan of segments:

    [("eager", idx), ("scan", [lo, hi)), ...]

Homogeneous runs of layers are stacked (leading dim = run length) and driven
by `lax.scan` — HLO stays O(1) in depth (95-layer models compile in seconds)
and the stacked layout is the canonical pipeline-parallel unit.  Layers that
differ structurally (deepseek-moe's dense first layer, hymba's three
global-attention layers whose KV cache is full-length instead of
sliding-window) run eagerly with their own parameters.

Everything is pure-functional: `init_params` -> pytree, `forward` /
`decode_step` are jit-able functions of (params, batch).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (Params, apply_mlp, apply_norm, cast_tree,
                                 dense_init, embed_init, mlp_init, norm_init)


# --------------------------------------------------------------------------
# sharding hints (kept abstract so models never import mesh machinery)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ShardingHints:
    """Optional with_sharding_constraint points; no-op by default."""

    activation: Callable[[jnp.ndarray], jnp.ndarray] = lambda x: x
    logits: Callable[[jnp.ndarray], jnp.ndarray] = lambda x: x
    # ZeRO-1 lever: constrain a (bf16) copy of the params to TP-only sharding
    # (data/pod axes stripped) so the FSDP gather happens once per step
    params_compute: Callable[[Any], Any] = lambda tree: tree
    # MoE expert-parallel guidance: constrain (G,E,C,D) expert buffers /
    # (G,gs,E,C) dispatch tensors so GSPMD lowers to all-to-all instead of
    # replicating (kind: "gecd" | "gtec")
    moe_constraint: Callable[[jnp.ndarray, str], jnp.ndarray] = \
        lambda x, kind: x


NO_HINTS = ShardingHints()


# --------------------------------------------------------------------------
# layer plan
# --------------------------------------------------------------------------
def eager_layer_ids(cfg: ModelConfig) -> Tuple[int, ...]:
    ids = set()
    if cfg.is_moe and cfg.dense_prefix_layers:
        ids.update(range(cfg.dense_prefix_layers))
    ids.update(cfg.global_layers)
    return tuple(sorted(ids))


def layer_plan(cfg: ModelConfig) -> List[Tuple[str, Any]]:
    eager = eager_layer_ids(cfg)
    plan: List[Tuple[str, Any]] = []
    lo = 0
    for e in eager:
        if e > lo:
            plan.append(("scan", (lo, e)))
        plan.append(("eager", e))
        lo = e + 1
    if lo < cfg.n_layers:
        plan.append(("scan", (lo, cfg.n_layers)))
    return plan


def layer_kind(cfg: ModelConfig, idx: int) -> Dict[str, Any]:
    """Structural description of layer `idx`."""
    is_global = idx in cfg.global_layers
    use_moe = cfg.is_moe and idx >= cfg.dense_prefix_layers
    window = 0 if (is_global or not cfg.window) else cfg.window
    return {"moe": use_moe, "window": window,
            "cross": cfg.is_encoder_decoder, "rwkv": cfg.rwkv,
            "ssm": cfg.ssm_state > 0}


# --------------------------------------------------------------------------
# single decoder layer
# --------------------------------------------------------------------------
def layer_init(key, cfg: ModelConfig, idx: int, *, encoder: bool = False
               ) -> Params:
    kind = layer_kind(cfg, idx)
    d, dt = cfg.d_model, cfg.pdtype()
    ks = jax.random.split(key, 10)
    if kind["rwkv"] and not encoder:
        n_heads = d // 64
        return rwkv_mod.rwkv_layer_init(ks[0], d, cfg.d_ff, n_heads, dt,
                                        cfg.n_layers)
    p: Params = {
        "ln1": norm_init(d, cfg.norm, dt),
        "attn": attn.attention_init(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                    cfg.head_dim, dt, cfg.n_layers),
        "ln2": norm_init(d, cfg.norm, dt),
    }
    if kind["moe"] and not encoder:
        p["moe"] = moe_mod.moe_init(ks[1], d, cfg.d_ff, cfg.n_experts,
                                    cfg.n_shared_experts, cfg.mlp, dt,
                                    cfg.n_layers)
    else:
        ff = cfg.dense_ff() if not encoder else cfg.d_ff
        p["mlp"] = mlp_init(ks[1], d, ff, cfg.mlp, dt, cfg.n_layers)
    if kind["ssm"] and not encoder:
        p["ssm"] = ssm_mod.ssm_init(ks[2], d, cfg.n_heads * cfg.head_dim,
                                    cfg.ssm_state, dt, cfg.n_layers)
        p["ln_attn_br"] = norm_init(d, cfg.norm, dt)
        p["ln_ssm_br"] = norm_init(d, cfg.norm, dt)
    if kind["cross"] and not encoder:
        p["ln_cross"] = norm_init(d, cfg.norm, dt)
        p["cross"] = attn.attention_init(ks[3], d, cfg.n_heads,
                                         cfg.n_kv_heads, cfg.head_dim, dt,
                                         cfg.n_layers)
    return p


def layer_apply(p: Params, x, cfg: ModelConfig, kind: Dict[str, Any], *,
                positions, cache=None, memory=None, memory_pos=None,
                hints: ShardingHints = NO_HINTS, encoder: bool = False):
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind["rwkv"] and not encoder:
        st = cache or {}
        n_heads = cfg.d_model // 64
        h, (wkv, tm_last) = rwkv_mod.time_mix_apply(
            p["tm"], apply_norm(p["ln_tm"], x, cfg.norm, bf16_mul=cfg.norm_bf16_mul), n_heads,
            state=st.get("wkv"), last_x=st.get("tm_last"),
            use_chunked=x.shape[1] > 1)
        x = hints.activation(x + h)
        h2, cm_last = rwkv_mod.channel_mix_apply(
            p["cm"], apply_norm(p["ln_cm"], x, cfg.norm, bf16_mul=cfg.norm_bf16_mul),
            last_x=st.get("cm_last"))
        x = hints.activation(x + h2)
        new_cache = {"wkv": wkv, "tm_last": tm_last, "cm_last": cm_last} \
            if cache is not None else None
        return x, new_cache, aux

    cache = cache or {}
    h = apply_norm(p["ln1"], x, cfg.norm, bf16_mul=cfg.norm_bf16_mul)
    attn_kwargs = dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                       head_dim=cfg.head_dim, positions=positions,
                       use_rope=cfg.use_rope, rope_theta=cfg.rope_theta,
                       causal=not encoder, window=kind["window"],
                       bf16_intermediates=cfg.attn_bf16_intermediates,
                       backend=cfg.attn_backend)
    a_out, new_kv = attn.attention_apply(p["attn"], h,
                                         cache=cache.get("self"),
                                         **attn_kwargs)
    new_cache: Dict[str, Any] = {}
    if cache.get("self") is not None:
        new_cache["self"] = new_kv

    if kind["ssm"] and not encoder:
        s_out, (ssm_state, conv_state) = ssm_mod.ssm_apply(
            p["ssm"], h, state=cache.get("ssm"), conv_state=cache.get("conv"))
        # hymba fusion: mean of the two normalized branch outputs
        a_out = 0.5 * (apply_norm(p["ln_attn_br"], a_out, cfg.norm, bf16_mul=cfg.norm_bf16_mul)
                       + apply_norm(p["ln_ssm_br"], s_out, cfg.norm, bf16_mul=cfg.norm_bf16_mul))
        if "ssm" in cache or cache.get("self") is not None:
            new_cache["ssm"] = ssm_state
            new_cache["conv"] = conv_state
    x = hints.activation(x + a_out)

    if kind["cross"] and not encoder and memory is not None:
        h = apply_norm(p["ln_cross"], x, cfg.norm, bf16_mul=cfg.norm_bf16_mul)
        # project cross K/V from raw encoder memory (cheap: memory is the
        # short stub-frontend sequence; a K/V cache here is a noted opt.)
        mk, mv = attn.cross_kv(p["cross"], memory, cfg.n_kv_heads,
                               cfg.head_dim)
        c_out, _ = attn.attention_apply(
            p["cross"], h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, positions=positions, causal=False,
            use_rope=False, memory_kv=(mk, mv), memory_pos=memory_pos,
            backend=cfg.attn_backend)
        x = hints.activation(x + c_out)

    h = apply_norm(p["ln2"], x, cfg.norm, bf16_mul=cfg.norm_bf16_mul)
    if kind["moe"] and not encoder:
        m_out, aux = moe_mod.moe_apply(
            p["moe"], h, n_experts=cfg.n_experts, top_k=cfg.top_k,
            mlp_kind=cfg.mlp, capacity_factor=cfg.moe_capacity_factor,
            stopgrad_dispatch=cfg.moe_stopgrad_dispatch,
            constraint=hints.moe_constraint)
    else:
        m_out = apply_mlp(p["mlp"], h, cfg.mlp)
    x = hints.activation(x + m_out)
    return x, (new_cache if new_cache else None), aux


# --------------------------------------------------------------------------
# full model
# --------------------------------------------------------------------------
def init_params(cfg: ModelConfig, key) -> Params:
    dt = cfg.pdtype()
    keys = jax.random.split(key, cfg.n_layers + cfg.n_encoder_layers + 4)
    # tables padded to cfg.padded_vocab: keeps the vocab dim shardable on
    # every mesh (padded logit columns are masked to -inf in forward)
    params: Params = {"embed": embed_init(keys[0], cfg.padded_vocab,
                                          cfg.d_model, dt),
                      "final_norm": norm_init(cfg.d_model, cfg.norm, dt)}
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(keys[1], cfg.d_model,
                                       cfg.padded_vocab, dt)
    # rwkv needs explicit pre-norms stored with the block
    def one_layer(i):
        p = layer_init(keys[2 + i], cfg, i)
        if cfg.rwkv:
            p["ln_tm"] = norm_init(cfg.d_model, cfg.norm, dt)
            p["ln_cm"] = norm_init(cfg.d_model, cfg.norm, dt)
        return p

    plan = layer_plan(cfg)
    params["eager"] = {str(i): one_layer(i)
                       for kind, i in plan if kind == "eager"}
    params["segments"] = []
    for kind, rng_ in plan:
        if kind != "scan":
            continue
        lo, hi = rng_
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                               *[one_layer(i) for i in range(lo, hi)])
        params["segments"].append(stacked)

    if cfg.is_encoder_decoder:
        ek = jax.random.split(keys[-1], cfg.n_encoder_layers)
        params["encoder"] = {
            "layers": jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[layer_init(ek[i], cfg, i, encoder=True)
                  for i in range(cfg.n_encoder_layers)]),
            "final_norm": norm_init(cfg.d_model, cfg.norm, dt),
        }
    return params


def leftpad_positions(lengths: jnp.ndarray, seq_len: int) -> jnp.ndarray:
    """Positions for left-padded prompts: (B,) true lengths -> (B, S).

    Pad tokens get position -1, which the position-based attention mask
    treats as "empty" (`k_pos >= 0` fails): pad keys are never attended, pad
    queries produce garbage that callers must ignore, and the KV-cache write
    for a pad is dropped entirely (attention.attention_apply routes
    position < 0 out of bounds with scatter mode="drop", so pads cannot
    clobber a real slot even on sliding-window ring buffers).  Real tokens
    get positions 0..L-1 so downstream decode continues at position L.
    """
    idx = jnp.arange(seq_len, dtype=jnp.int32)[None, :]
    pos = idx - (seq_len - lengths.astype(jnp.int32))[:, None]
    return jnp.where(pos >= 0, pos, -1)


def _sinusoidal(positions, d):
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _run_layers(params: Params, x, cfg: ModelConfig, *, positions,
                caches=None, memory=None, memory_pos=None,
                hints: ShardingHints = NO_HINTS, remat: bool = False):
    """Execute the layer plan. caches: {"eager": {id: c}, "segments": [c]}."""
    plan = layer_plan(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = {"eager": {}, "segments": []} if caches is not None else None
    seg_i = 0
    for kind_tag, arg in plan:
        if kind_tag == "eager":
            idx = arg
            kind = layer_kind(cfg, idx)
            c = caches["eager"].get(str(idx)) if caches is not None else None
            fn = partial(layer_apply, cfg=cfg, kind=kind, hints=hints)
            if remat:
                fn = jax.checkpoint(fn, static_argnums=())
            lp = cast_tree(params["eager"][str(idx)], cfg.cdtype())
            x, nc, aux = fn(lp, x, positions=positions, cache=c,
                            memory=memory, memory_pos=memory_pos)
            aux_total += aux
            if new_caches is not None:
                new_caches["eager"][str(idx)] = nc
        else:
            lo, hi = arg
            kind = layer_kind(cfg, lo)  # homogeneous within a segment
            seg_params = params["segments"][seg_i]
            seg_cache = caches["segments"][seg_i] if caches is not None \
                else None

            # Caches ride the scan CARRY and are updated in place with
            # dynamic_update_index — XLA aliases while-loop carries, so the
            # decode path pays 1x cache memory instead of the 2x an
            # xs->ys stacked cache would cost.
            def body(carry, xs):
                h, aux_acc, cbuf = carry
                lp, idx = xs
                lc = None if cbuf is None else jax.tree.map(
                    lambda b: jax.lax.dynamic_index_in_dim(
                        b, idx, 0, keepdims=False), cbuf)

                def inner(lp_, h_, lc_):
                    return layer_apply(cast_tree(lp_, cfg.cdtype()), h_,
                                       cfg=cfg, kind=kind,
                                       positions=positions, cache=lc_,
                                       memory=memory, memory_pos=memory_pos,
                                       hints=hints)
                if remat:
                    inner = jax.checkpoint(inner)
                h, nc, aux = inner(lp, h, lc)
                if cbuf is not None:
                    cbuf = jax.tree.map(
                        lambda b, n: jax.lax.dynamic_update_index_in_dim(
                            b, n.astype(b.dtype), idx, 0), cbuf, nc)
                return (h, aux_acc + aux, cbuf), None

            n_seg = hi - lo
            (x, aux_total, seg_new), _ = jax.lax.scan(
                body, (x, aux_total, seg_cache),
                (seg_params, jnp.arange(n_seg, dtype=jnp.int32)))
            if new_caches is not None:
                new_caches["segments"].append(seg_new)
            seg_i += 1
    return x, new_caches, aux_total


def encode(params: Params, cfg: ModelConfig, frames: jnp.ndarray,
           hints: ShardingHints = NO_HINTS):
    """Whisper-style encoder over stub frame embeddings (B, T, D)."""
    b, t, _ = frames.shape
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    x = frames.astype(cfg.cdtype()) \
        + _sinusoidal(pos, cfg.d_model).astype(cfg.cdtype())
    enc = params["encoder"]
    kind = {"moe": False, "window": 0, "cross": False, "rwkv": False,
            "ssm": False}

    def body(h, lp):
        h, _, _ = layer_apply(cast_tree(lp, cfg.cdtype()), h, cfg=cfg,
                              kind=kind, positions=pos, hints=hints,
                              encoder=True)
        return h, None

    x, _ = jax.lax.scan(body, x, enc["layers"])
    return apply_norm(enc["final_norm"], x, cfg.norm, bf16_mul=cfg.norm_bf16_mul), pos


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray, *,
            positions=None, caches=None, frames=None, patches=None,
            memory=None, hints: ShardingHints = NO_HINTS,
            remat: bool = False, last_only: bool = False, lengths=None,
            attn_backend: Optional[str] = None):
    """Full forward. tokens (B, S) -> logits (B, S, V), caches', aux.

    frames: (B, T, D) stub audio frontend output (enc-dec archs).
    patches: (B, P, D) stub vision frontend output (vlm archs; added to the
    first P token positions — early fusion).
    memory: precomputed encoder output (decode steps skip re-encoding).
    last_only: project logits for the final position only (prefill serving —
    avoids materializing the (B, S, V) tensor).
    lengths: (B,) true prompt lengths for left-padded batched prefill; pads
    are masked out of attention via position -1 (see leftpad_positions).
    Ignored when explicit positions are given.
    attn_backend: per-call override of cfg.attn_backend (registry attention
    backend; see models/attention.resolve_attention_backend).
    """
    if attn_backend is not None:
        cfg = dataclasses.replace(cfg, attn_backend=attn_backend)
    cdt = cfg.cdtype()
    b, s = tokens.shape
    if positions is None:
        if lengths is not None:
            positions = leftpad_positions(lengths, s)
        else:
            positions = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = params["embed"].astype(cdt)[tokens]
    if patches is not None:
        p_len = patches.shape[1]
        x = x.at[:, :p_len].add(patches.astype(cdt))
    if not cfg.use_rope and not cfg.rwkv:
        x = x + _sinusoidal(positions, cfg.d_model).astype(cdt)
    x = hints.activation(x)

    memory_pos = None
    if cfg.is_encoder_decoder:
        if memory is None:
            if frames is None:
                raise ValueError("enc-dec model requires `frames` or `memory`")
            memory, memory_pos = encode(params, cfg, frames, hints)
        else:
            t = memory.shape[1]
            memory_pos = jnp.broadcast_to(
                jnp.arange(t, dtype=jnp.int32)[None], (memory.shape[0], t))
    else:
        memory = None

    x, new_caches, aux = _run_layers(
        params, x, cfg, positions=positions, caches=caches,
        memory=memory, memory_pos=memory_pos, hints=hints, remat=remat)

    x = apply_norm(params["final_norm"], x, cfg.norm, bf16_mul=cfg.norm_bf16_mul)
    if last_only:
        x = x[:, -1:]
    unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = hints.logits(x @ unembed.astype(cdt))
    if cfg.padded_vocab != cfg.vocab_size:
        valid = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(valid, logits, jnp.asarray(-1e9, logits.dtype))
    return logits, new_caches, aux


def init_caches(cfg: ModelConfig, batch: int, seq_len: int) -> Params:
    """Decode caches per the layer plan (ring buffers for SWA layers)."""
    cdt = cfg.cdtype()
    plan = layer_plan(cfg)

    def one(idx):
        kind = layer_kind(cfg, idx)
        if kind["rwkv"]:
            h = cfg.d_model // 64
            return {"wkv": jnp.zeros((batch, h, 64, 64), jnp.float32),
                    "tm_last": jnp.zeros((batch, 1, cfg.d_model), cdt),
                    "cm_last": jnp.zeros((batch, 1, cfg.d_model), cdt)}
        cache_len = min(kind["window"], seq_len) if kind["window"] \
            else seq_len
        c = {"self": attn.init_cache(batch, cache_len, cfg.n_kv_heads,
                                     cfg.head_dim, cdt)}
        if kind["ssm"]:
            di = cfg.n_heads * cfg.head_dim
            c["ssm"] = jnp.zeros((batch, di, cfg.ssm_state), jnp.float32)
            c["conv"] = jnp.zeros((batch, ssm_mod.CONV_WIDTH - 1, di), cdt)
        return c

    caches = {"eager": {}, "segments": []}
    for tag, arg in plan:
        if tag == "eager":
            caches["eager"][str(arg)] = one(arg)
        else:
            lo, hi = arg
            caches["segments"].append(
                jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[one(i) for i in range(lo, hi)]))
    return caches
