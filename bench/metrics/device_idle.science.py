"""Share of the window in which no operation ran on the device (science
cells): 100 * (1 - busy / window), busy being the union of the ``XLA Ops``
intervals in the trace."""


def read(r):
    t = r["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
