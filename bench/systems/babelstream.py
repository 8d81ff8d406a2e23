"""One BabelStream iteration as upstream runs it: copy, mul, add, triad and
dot, each reading what the one before wrote, through the program's
portable registry.

Each kernel is called inside a jit of the benchmark's own, named
``bench_<op>``, so that its XLA module is ``jit_bench_<op>`` in the trace
whatever the program names its wrappers, and no kernel can be fused with
the next unnoticed.  A call dispatches the five without waiting on the
host and blocks once, at its end, on every output.  Every call starts from
the same seeded inputs: upstream's carry scales ``a`` by 0.96 an iteration
and would reach zero long before the window ends.
"""

from typing import Any, Dict, NamedTuple

import jax

import harness


class Iteration(NamedTuple):
    """What one iteration writes, in order: c after copy, b, c after add,
    a, and the sum."""
    c: Any
    b: Any
    c2: Any
    a: Any
    dot: Any

    def block_until_ready(self) -> "Iteration":
        jax.block_until_ready(tuple(self))
        return self


def timed_chain(cfg: Dict):
    import repro.kernels  # noqa: F401  (registers every backend)
    from repro.core.portable import get_kernel
    k = {op: get_kernel(f"babelstream.{op}")
         for op in ("copy", "mul", "add", "triad", "dot")}
    backend, s = cfg["backend"], float(cfg["scalar"])

    def bench_copy(a):
        return k["copy"](a, backend=backend)

    def bench_mul(c):
        return k["mul"](c, scalar=s, backend=backend)

    def bench_add(a, b):
        return k["add"](a, b, backend=backend)

    def bench_triad(b, c):
        return k["triad"](b, c, scalar=s, backend=backend)

    def bench_dot(a, b):
        return k["dot"](a, b, backend=backend)

    copy, mul, add, triad, dot = map(jax.jit, (
        bench_copy, bench_mul, bench_add, bench_triad, bench_dot))

    def iteration(a, b, c) -> Iteration:
        c = copy(a)
        b = mul(c)
        c2 = add(a, b)
        a2 = triad(b, c2)
        return Iteration(c, b, c2, a2, dot(a2, b))
    return iteration


def run(ctx: "harness.Run") -> Dict[str, Any]:
    cfg = ctx.config
    ref = harness.load_module("refs", cfg["reference"])
    loop = harness.load_module("generators", ctx.traffic["generator"])
    inputs = ref.make_inputs(cfg, harness.seed_key(ctx.seed))
    call = timed_chain(cfg)
    for _ in range(2):                 # compile (or load), then one warm call
        call(*inputs).block_until_ready()

    annotate = jax.profiler.TraceAnnotation if ctx.trace else None
    t0 = ctx.start_window()
    got = loop.run(lambda: call(*inputs), ctx.seconds, ctx.traffic,
                   annotate=annotate)
    ctx.end_window(got["t_end"])
    ctx.finish()
    ctx.read_memory()
    window = got["t_end"] - t0
    ms = [1e3 * c for c in got["call_s"]]
    harness.log(f"window {window:.6f} s, {got['calls']} calls, compiles in "
                f"window {ctx.compiles_in_window}; call ms p1 "
                f"{harness.percentile(ms, 1)!r} p50 "
                f"{harness.percentile(ms, 50)!r} p99 "
                f"{harness.percentile(ms, 99)!r} max {max(ms)!r}")
    readings = ref.compare(cfg, inputs, got["output"])
    limits = ctx.cell["limits"]
    return {
        "end_to_end": {"call_ms": window * 1000.0 / got["calls"]},
        "attempted": got["calls"], "failed": 0,
        "checks": {k: harness.check(v, limits[k]) for k, v in readings.items()},
        "records": {"calls": got["calls"], "n": int(cfg["n"]),
                    "itemsize": jax.numpy.dtype(cfg["dtype"]).itemsize},
    }


def calibrate(ctx: "harness.Run", seeds) -> None:
    """For each seed, the program's reading and the control's, on the
    cell's own size (no window)."""
    cfg = ctx.config
    ref = harness.load_module("refs", cfg["reference"])
    call = timed_chain(cfg)
    for seed in seeds:
        inputs = ref.make_inputs(cfg, harness.seed_key(seed))
        prog = ref.compare(cfg, inputs, call(*inputs))
        ctrl = ref.compare(cfg, inputs, ref.control(cfg, inputs))
        print(f"calibrate seed {seed} program {prog} control {ctrl}",
              flush=True)
