"""Closed loop of blocking calls: one caller that waits for each result
before it makes the next call, on the same inputs, until the window ends.

This is how a science user runs a kernel in a time-stepping loop: the next
step needs the result of this one, so every call ends in
``block_until_ready`` and the time per call counts everything between two
results.
"""

import time
from typing import Any, Callable, Dict


def run(call: Callable[[], Any], seconds: float, params: Dict,
        annotate=None) -> Dict[str, Any]:
    """Call ``call()`` back to back for ``seconds``; every call blocks.

    Returns the number of calls, the host time of the first call's start
    and the last call's end, each call's seconds, and the last output.  ``annotate``, if
    given, wraps each call in a named host span for the trace.
    """
    if params.get("blocking") is not True:
        raise ValueError("kernel_loop runs blocking calls only")
    calls, out, ends = 0, None, []
    t0 = time.perf_counter()
    t = t0
    while t - t0 < seconds:
        if annotate is None:
            out = call()
            out.block_until_ready()
        else:
            with annotate("bench.call"):
                out = call()
                out.block_until_ready()
        calls += 1
        t = time.perf_counter()
        ends.append(t)
    return {"calls": calls, "t0": t0, "t_end": t, "output": out,
            "call_s": [b - a for a, b in zip([t0] + ends, ends)]}
