"""Seeded weights and a plain float32 reference of a dense GQA decoder
(granite-3.0 layout: RMSNorm, rotary q/k, grouped-query attention, SwiGLU,
output head tied to the embedding).

The weights are made here, from the seed, in the type they are served in
(bfloat16); the program is handed them, and the reference makes them again
layer by layer from the same seed.  It takes nothing the program has made.

The configuration file keeps the published values; where the program
departs from them, its ``departures`` entry gives the value as run, and the
reference follows it (:func:`as_run`), so that both compute the same
function: no embedding, residual or logit multipliers, attention scaled by
``1/sqrt(head_dim)``, and the program's RMSNorm epsilon.  ``PERF.md`` lists
these departures.

``mode="f32"`` is the reference: float32 throughout, every matrix product at
``Precision.HIGHEST``.  ``mode="fp8"`` is the control: the same forward with
both operands of every matrix product rounded to float8_e4m3fn under a
per-tensor scale, the step below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0

#: layer leaves in generation order: name -> (shape, std) from the widths
LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w_gate", "w_up",
                "w_down")


def widths(cfg: Dict) -> Dict[str, int]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"],
            "dh": cfg.get("head_dim", d // h), "f": cfg["intermediate_size"],
            "v": cfg["vocab_size"], "layers": cfg["num_hidden_layers"]}


#: what this forward computes for each scalar of the configuration: the
#: value as run has to be this (the head size gives the attention scale)
IDENTITY = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
            "logits_scaling": 1.0}


def as_run(cfg: Dict) -> Dict[str, float]:
    """The multipliers and the norm epsilon as run: the published value, or
    its ``departures`` entry.  Raises where a value as run is not one this
    forward computes."""
    dep = cfg.get("departures", {})
    keys = ("attention_multiplier", "rms_norm_eps") + tuple(IDENTITY)
    out = {k: float(dep[k]["as_run"] if k in dep else cfg[k]) for k in keys}
    want = dict(IDENTITY, attention_multiplier=widths(cfg)["dh"] ** -0.5)
    for k, v in want.items():
        if abs(out[k] - v) > 1e-9 * abs(v):
            raise ValueError(f"{k} as run is {out[k]}; this forward computes "
                             f"{v}")
    return out


def _leaf_spec(w: Dict[str, int], name: str) -> Tuple[tuple, float]:
    d, hd, kvd, f = w["d"], w["h"] * w["dh"], w["kv"] * w["dh"], w["f"]
    out_scale = 1.0 / np.sqrt(2 * w["layers"])
    return {"ln1": ((d,), 0.0), "ln2": ((d,), 0.0),
            "wq": ((d, hd), d ** -0.5), "wk": ((d, kvd), d ** -0.5),
            "wv": ((d, kvd), d ** -0.5),
            "wo": ((hd, d), hd ** -0.5 * out_scale),
            "w_gate": ((d, f), d ** -0.5), "w_up": ((d, f), d ** -0.5),
            "w_down": ((f, d), f ** -0.5 * out_scale)}[name]


def _normal(key, shape, std):
    """std * N(0, 1) in bfloat16; a norm scale (std 0) is 1 + 0.1 N(0, 1)."""
    z = jax.random.normal(key, shape, jnp.bfloat16)
    if std == 0.0:
        return jnp.bfloat16(1) + jnp.bfloat16(0.1) * z
    return z * jnp.bfloat16(std)


def layer_weights(key, w: Dict[str, int], layer) -> Dict[str, jnp.ndarray]:
    """Layer ``layer``'s weights in bfloat16 (``layer`` may be traced)."""
    lk = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    return {name: _normal(jax.random.fold_in(lk, i), *_leaf_spec(w, name))
            for i, name in enumerate(LAYER_LEAVES)}


def embedding(key, w: Dict[str, int]) -> jnp.ndarray:
    """(vocab, hidden) embedding, also the output head, in bfloat16."""
    return _normal(jax.random.fold_in(key, 0), (w["v"], w["d"]), 0.02)


def final_norm(key, w: Dict[str, int]) -> jnp.ndarray:
    return _normal(jax.random.fold_in(key, 2), (w["d"],), 0.0)


def stacked_layers(key, w: Dict[str, int]) -> Dict[str, jnp.ndarray]:
    """Every layer's weights stacked on a leading axis, as
    :func:`layer_weights` makes them one by one."""
    return jax.vmap(lambda l: layer_weights(key, w, l))(
        jnp.arange(w["layers"], dtype=jnp.int32))


# --------------------------------------------------------------------------
# the forward pass
# --------------------------------------------------------------------------
def _q8(x):
    """Round to float8_e4m3fn under a per-tensor scale, back to float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _mm(mode: str, spec: str, a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if mode == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, theta):
    """Rotary embedding, rotating the two halves of each head (positions
    0..S-1); x (S, heads, dh)."""
    s, _, dh = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


@functools.partial(jax.jit, static_argnames=("w", "mode", "eps", "theta"))
def _layer(x, p, *, w, mode, eps, theta):
    """One decoder layer over one whole sequence x (S, d), causal."""
    s = x.shape[0]
    h, kv, dh = w["h"], w["kv"], w["dh"]
    a = _rmsnorm(x, p["ln1"], eps)
    q = _rope(_mm(mode, "sd,de->se", a, p["wq"]).reshape(s, h, dh), theta)
    k = _rope(_mm(mode, "sd,de->se", a, p["wk"]).reshape(s, kv, dh), theta)
    v = _mm(mode, "sd,de->se", a, p["wv"]).reshape(s, kv, dh)
    g = h // kv
    qg = q.reshape(s, kv, g, dh)
    scores = _mm(mode, "skgd,tkd->kgst", qg, k) / np.sqrt(dh)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _mm(mode, "kgst,tkd->skgd", probs, v).reshape(s, h * dh)
    x = x + _mm(mode, "se,ed->sd", o, p["wo"])
    b = _rmsnorm(x, p["ln2"], eps)
    gate = _mm(mode, "sd,df->sf", b, p["w_gate"])
    up = _mm(mode, "sd,df->sf", b, p["w_up"])
    return x + _mm(mode, "sf,fd->sd", jax.nn.silu(gate) * up, p["w_down"])


@functools.partial(jax.jit, static_argnames=("w", "mode", "eps"))
def _head(x, norm, emb, *, w, mode, eps):
    return _mm(mode, "sd,vd->sv", _rmsnorm(x, norm, eps), emb)


class _Frozen(dict):
    """A hashable widths dict, for jit's static arguments."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


_make_layer = jax.jit(layer_weights, static_argnums=1)
_make_embedding = jax.jit(embedding, static_argnums=1)
_make_final_norm = jax.jit(final_norm, static_argnums=1)


def logits(key, cfg: Dict, tokens: np.ndarray, mode: str = "f32"):
    """(S, vocab) float32 logits of one sequence, every position, with the
    weights made again from ``key`` one layer at a time."""
    w = _Frozen(widths(cfg))
    eps, theta = as_run(cfg)["rms_norm_eps"], float(cfg["rope_theta"])
    emb = _make_embedding(key, w)
    x = emb[jnp.asarray(tokens)].astype(jnp.float32)
    for layer in range(w["layers"]):
        p = _make_layer(key, w, jnp.int32(layer))
        x = _layer(x, p, w=w, mode=mode, eps=eps, theta=theta)
        del p
    return _head(x, _make_final_norm(key, w), emb, w=w, mode=mode, eps=eps)


def rows(key, cfg: Dict, prompt: np.ndarray, generated: List[int],
         length: int, mode: str = "f32"):
    """Logits at the positions that chose each served token: the sequence
    is the prompt and the served tokens but the last, padded at the end
    with token 0 to ``length`` (one compiled shape for every request;
    causal attention keeps the padding out of earlier positions)."""
    gen = np.asarray(generated, np.int64)
    seq = np.concatenate([np.asarray(prompt, np.int64), gen[:-1]])
    if len(seq) > length:
        raise ValueError(f"sequence of {len(seq)} tokens > {length}")
    tokens = np.zeros(length, np.int32)
    tokens[:len(seq)] = seq
    pos = np.arange(len(prompt) - 1, len(seq))
    return logits(key, cfg, tokens, mode)[jnp.asarray(pos)]


def gaps(ref_rows, picks) -> np.ndarray:
    """How far each picked token's reference logit lies below the
    reference's best at its position."""
    picks = jnp.asarray(np.asarray(picks, np.int64))[:, None]
    return np.asarray(jnp.max(ref_rows, -1)
                      - jnp.take_along_axis(ref_rows, picks, -1)[:, 0])


def served_gaps(key, cfg: Dict, prompt, generated, length: int):
    """The reference's gap of every token the program served."""
    return gaps(rows(key, cfg, prompt, generated, length), generated)


def control_gaps(key, cfg: Dict, prompt, generated, length: int):
    """The control, read without decoding: at each position of the same
    prompt and served tokens, the reference's gap of the token that the
    fp8 forward puts first.  Returns (program's gaps, control's gaps)."""
    ref = rows(key, cfg, prompt, generated, length)
    low = rows(key, cfg, prompt, generated, length, "fp8")
    return gaps(ref, generated), gaps(ref, jnp.argmax(low, -1))
