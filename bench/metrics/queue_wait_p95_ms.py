"""95th percentile, over every request that arrived in the window, of the
wait from when the request was due to when the engine gave it a slot
(``Request.t_admitted``, the program's own host timestamp)."""

import harness


def read(r):
    waits = [q["admitted"] - q["due"] for q in r["records"]["requests"]
             if q["window"] and q["admitted"] == q["admitted"]]
    return 1e3 * harness.percentile(waits, 95) if waits else None
