"""Share of the chip's bf16 peak reached by the decode steps.

Operations = what the decoded tokens in the traced window required
(``counts/dense_gqa.decode_flops``: every matmul for each active slot's
token plus attention over its live positions; inactive slots and empty
cache positions do not count).  Time = device seconds of the program's
``jit_decode_fn`` modules in the window.
"""

import harness


def decode_tokens(r):
    """(keys attended) of every decoded token in the traced window: token
    i >= 1 of a request attends over prompt_len + i positions."""
    end = r["trace"]["window_s"]
    for q in r["records"]["requests"]:
        for i, t in enumerate(q["tokens"][1:], start=1):
            if 0.0 <= t <= end:
                yield q["prompt_len"] + i


def read(r):
    mod = r["trace"]["modules"].get("jit_decode_fn")
    if not mod or not mod["seconds"]:
        return None
    g = harness.load_module("counts", "dense_gqa")
    w = g.widths(r["config"])
    flops = sum(g.decode_flops(w, k) for k in decode_tokens(r))
    return 100.0 * flops / mod["seconds"] / r["peaks"]["bf16_flops_per_s"]
