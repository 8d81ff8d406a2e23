"""Operations a dense GQA decoder must do to serve tokens, from its widths
and the real lengths of the requests alone.

A multiply-add counts as two operations.  Padding, inactive decode slots,
the padded rows of the vocabulary and positions of the cache that hold no
token do not count: they are work the implementation chose, not work the
request needs.
"""

from typing import Dict


def widths(cfg: Dict) -> Dict[str, int]:
    """The sizes the counts need, from a configuration file's keys."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"],
            "dh": cfg.get("head_dim", d // h), "f": cfg["intermediate_size"],
            "v": cfg["vocab_size"], "layers": cfg["num_hidden_layers"]}


def layer_matmul_params(w: Dict[str, int]) -> int:
    """Weights of one layer's matrix multiplications: q, k, v, o and the
    three SwiGLU projections."""
    q_o = 2 * w["d"] * w["h"] * w["dh"]
    k_v = 2 * w["d"] * w["kv"] * w["dh"]
    return q_o + k_v + 3 * w["d"] * w["f"]


def params(w: Dict[str, int], tied: bool = True) -> int:
    """Every parameter: layers (with their two norm scales), the embedding
    (shared with the output head when ``tied``) and the final norm."""
    per_layer = layer_matmul_params(w) + 2 * w["d"]
    embed = w["v"] * w["d"] * (1 if tied else 2)
    return w["layers"] * per_layer + embed + w["d"]


def attention_flops(w: Dict[str, int], keys: int) -> float:
    """Q.K^T and P.V of one query against ``keys`` positions, all layers."""
    return 4.0 * w["layers"] * w["h"] * w["dh"] * keys


def prefill_flops(w: Dict[str, int], prompt_len: int) -> float:
    """One prefill of a prompt: every layer over every prompt token, causal
    attention over the real length, logits for the last position only."""
    L = prompt_len
    matmul = 2.0 * w["layers"] * layer_matmul_params(w) * L
    causal_keys = L * (L + 1) / 2
    logits = 2.0 * w["d"] * w["v"]
    return matmul + attention_flops(w, causal_keys) + logits


def decode_flops(w: Dict[str, int], keys: int) -> float:
    """One decoded token whose query attends over ``keys`` positions."""
    matmul = 2.0 * w["layers"] * layer_matmul_params(w)
    logits = 2.0 * w["d"] * w["v"]
    return matmul + attention_flops(w, keys) + logits
