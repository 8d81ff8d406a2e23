"""Share of its roofline reached by the decode attention kernel.

Least time = for each decoded token in the traced window, the k and v
bytes of its live positions plus its query and output, over HBM bandwidth
(memory-bound: about one operation per byte; ``counts/attention``).  Time =
device seconds of the ``decode_pallas`` custom calls in the window.  A
kernel that reads every position of the cache, live or not, and every slot,
active or not, shows here as a low share.
"""

import harness


def read(r):
    op = r["trace"]["ops"].get("decode_pallas")
    if not op or not op["seconds"]:
        return None
    a = harness.load_module("counts", "attention")
    w = harness.load_module("counts", "dense_gqa").widths(r["config"])
    keys = harness.load_module("metrics", "decode_mfu").decode_tokens(r)
    least = sum(a.least_seconds(*a.decode(w, k), r["peaks"]) for k in keys)
    return 100.0 * least / op["seconds"]
