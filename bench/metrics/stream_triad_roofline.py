"""Share of the HBM roofline reached by BabelStream triad: Eq. 2 bytes
(``counts/babelstream``) over the chip's HBM bandwidth, over the device
seconds of the benchmark's ``jit_bench_triad`` module per call, in the
trace."""

import harness


def read(r):
    return harness.load_module("counts", "babelstream").roofline_share(
        r, "triad")
