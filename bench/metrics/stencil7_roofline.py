"""Share of the HBM roofline reached by the seven-point stencil call.

Least time = paper Eq. 1 bytes of the call's shape (``counts/stencil7``)
over the chip's HBM bandwidth; the stencil is memory-bound (13 operations
per 8 bytes per cell in float32, far below the v5e ridge of 240).  Time =
device seconds of the benchmark's ``jit_bench_call`` module per call, in the
trace.  Re-reads of the input by the kernel show here as a lower share.
"""

import harness


def read(r):
    mod = r["trace"]["modules"].get("jit_bench_call")
    if not mod or not mod["count"]:
        return None
    counts = harness.load_module("counts", "stencil7")
    rec = r["records"]
    least = counts.bytes_required(rec["shape"], rec["itemsize"]) \
        / r["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least * mod["count"] / mod["seconds"]
