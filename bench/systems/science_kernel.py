"""A science kernel called through the program's portable registry.

The configuration names the kernel, the registry backend, the input shape
and dtype, and the reference module that makes the inputs from the seed and
judges the output.  The timed path is ``get_kernel(kernel)(*inputs,
backend=backend, **coefficients)`` inside a jit of the benchmark's own,
named ``bench_call``, so that its XLA module is ``jit_bench_call`` in the
trace whatever the program names its wrappers.
"""

from typing import Any, Dict

import jax

import harness


def timed_call(cfg: Dict):
    import repro.kernels  # noqa: F401  (registers every backend)
    from repro.core.portable import get_kernel
    kernel = get_kernel(cfg["kernel"])
    backend, coeffs = cfg["backend"], dict(cfg.get("coefficients", {}))

    def bench_call(*inputs):
        return kernel(*inputs, backend=backend, **coeffs)
    return jax.jit(bench_call)


def run(ctx: "harness.Run") -> Dict[str, Any]:
    cfg = ctx.config
    ref = harness.load_module("refs", cfg["reference"])
    loop = harness.load_module("generators", ctx.traffic["generator"])
    inputs = ref.make_inputs(cfg, harness.seed_key(ctx.seed))
    call = timed_call(cfg)
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
        "records": {"calls": got["calls"], "shape": cfg["shape"],
                    "itemsize": jax.numpy.dtype(cfg["dtype"]).itemsize},
    }


def calibrate(ctx: "harness.Run", seeds) -> None:
    """For each seed, the program's reading and the control's, on the
    cell's own input size (no window)."""
    cfg = ctx.config
    ref = harness.load_module("refs", cfg["reference"])
    call = timed_call(cfg)
    for seed in seeds:
        inputs = ref.make_inputs(cfg, harness.seed_key(seed))
        prog = ref.compare(cfg, inputs, call(*inputs))
        ctrl = ref.compare(cfg, inputs, ref.control(cfg, inputs))
        print(f"calibrate seed {seed} program {prog} control {ctrl}",
              flush=True)
