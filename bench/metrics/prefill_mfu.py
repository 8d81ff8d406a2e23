"""Share of the chip's bf16 peak reached by the prefill programs.

Operations = what the prefills in the traced window required
(``counts/dense_gqa.prefill_flops`` at each prompt's real length: padding to
the bucket does not count).  Time = device seconds of the program's
``jit_prefill_fn`` modules in the window.  A request's prefill is in the
window when its first token is.
"""

import harness


def read(r):
    mod = r["trace"]["modules"].get("jit_prefill_fn")
    if not mod or not mod["seconds"]:
        return None
    g = harness.load_module("counts", "dense_gqa")
    w = g.widths(r["config"])
    end = r["trace"]["window_s"]
    flops = sum(g.prefill_flops(w, q["prompt_len"])
                for q in r["records"]["requests"]
                if q["tokens"] and 0.0 <= q["tokens"][0] <= end)
    return 100.0 * flops / mod["seconds"] / r["peaks"]["bf16_flops_per_s"]
