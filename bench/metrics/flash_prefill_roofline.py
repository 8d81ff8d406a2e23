"""Share of its roofline reached by the prefill attention kernel.

Least time = for each prefill in the traced window, the larger of its
attention operations over the bf16 peak and its q, k, v and output bytes
over HBM bandwidth, at the prompt's real length (``counts/attention``).
Time = device seconds of the ``flash_pallas`` custom calls in the window.
"""

import harness


def read(r):
    op = r["trace"]["ops"].get("flash_pallas")
    if not op or not op["seconds"]:
        return None
    a = harness.load_module("counts", "attention")
    w = harness.load_module("counts", "dense_gqa").widths(r["config"])
    end = r["trace"]["window_s"]
    least = sum(a.least_seconds(*a.prefill(w, q["prompt_len"]), r["peaks"])
                for q in r["records"]["requests"]
                if q["tokens"] and 0.0 <= q["tokens"][0] <= end)
    return 100.0 * least / op["seconds"]
