"""Operations and bytes the attention kernels must do, from real lengths.

Prefill (``attention.flash``): per layer, causal Q.K^T and P.V over the
prompt's real length, reading q, k and v once and writing the output once.
Decode (``attention.decode``): per layer and active slot, k and v of the
live positions, the query and the output, once each.  Elements are of
``itemsize`` bytes (bfloat16: 2).
"""

from typing import Dict


def prefill(w: Dict[str, int], prompt_len: int, itemsize: int = 2):
    """(flops, bytes) of one prompt's prefill attention, all layers."""
    L = prompt_len
    flops = 4.0 * w["h"] * w["dh"] * L * (L + 1) / 2
    q_o = 2 * L * w["h"] * w["dh"]
    k_v = 2 * L * w["kv"] * w["dh"]
    return w["layers"] * flops, w["layers"] * float((q_o + k_v) * itemsize)


def decode(w: Dict[str, int], keys: int, itemsize: int = 2):
    """(flops, bytes) of one decoded token attending over ``keys`` live
    positions, all layers."""
    flops = 4.0 * w["h"] * w["dh"] * keys
    k_v = 2 * keys * w["kv"] * w["dh"]
    q_o = 2 * w["h"] * w["dh"]
    return w["layers"] * flops, w["layers"] * float((k_v + q_o) * itemsize)


def least_seconds(flops: float, nbytes: float, peaks: Dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
