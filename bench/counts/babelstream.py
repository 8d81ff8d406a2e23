"""Bytes a BabelStream kernel must move, from its size alone.

Copied from the paper's Eq. 2 (``repro.core.metrics.babelstream_bytes``):
copy, mul and dot read or write two arrays of ``n`` elements, add and triad
three.  A kernel that moves more still needs only these bytes, so extra
traffic shows as a lower share of the roofline, not as a larger count.
"""

#: arrays of ``n`` elements each kernel reads or writes once
ARRAYS = {"copy": 2, "mul": 2, "add": 3, "triad": 3, "dot": 2}


def bytes_required(op: str, n: int, itemsize: int) -> float:
    return float(ARRAYS[op] * n * itemsize)


def roofline_share(r, op: str):
    """Percent of the HBM roofline reached by ``op`` in a traced run: Eq. 2
    bytes over the chip's HBM bandwidth, over the device seconds a call of
    the benchmark's ``jit_bench_<op>`` module took; None where the trace
    holds no such module."""
    mod = r["trace"]["modules"].get(f"jit_bench_{op}")
    if not mod or not mod["count"]:
        return None
    rec = r["records"]
    least = bytes_required(op, rec["n"], rec["itemsize"]) \
        / r["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least * mod["count"] / mod["seconds"]
