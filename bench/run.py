"""The chip benchmark: one run of one cell.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json`` (see ``harness.cell``).  The run sets up the
system from the seed, warms up the cell's shapes, measures for ``--seconds``,
checks what the timed path produced against the plain reference, and prints
one JSON line last on standard output.  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` records a profiler trace of the window and
reports its per-layer metrics instead.  Without a TPU, or with fewer chips
than the cell asks for, it exits nonzero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_jax():
    """The persistent compilation cache at its fixed path in the checkout;
    every program is cached, however quickly it compiled."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(harness.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    sys.path.insert(0, str(harness.ROOT / "src"))


def per_layer(ctx, out, peaks):
    """The per-layer metrics of a ``--trace 1`` run, each from its reader;
    a reader that finds nothing to read leaves its metric out."""
    import trace_reduce
    summary = trace_reduce.reduce(ctx.trace_path)
    reading = {"trace": summary, "records": out["records"], "peaks": peaks,
               "config": ctx.config}
    metrics = {}
    for m in ctx.parts["per_layer"]:
        value = harness.load_module("metrics", m["name"]).read(reading)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    harness.log(f"trace {ctx.trace_path}: window {summary['window_s']} s, "
                f"busy {summary['busy_s']} s, modules "
                f"{ {k: v['seconds'] for k, v in summary['modules'].items()} }")
    breakdown = {"device_ops": summary["device_ops"],
                 "idle_gaps": summary["idle_gaps"]}
    return metrics, summary, breakdown


def main(argv=None, *, device=None) -> int:
    """One run; ``device`` stands in for the chip look (tests only)."""
    args = parse(argv)
    parts = harness.cell(args.workload)
    setup_jax()
    if device is None:
        device = harness.device_summary(parts["entry"]["chips"])
    ctx = harness.Run(workload=args.workload, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      parts=parts, t_start=T_START, device=device)
    system = harness.load_module("systems", parts["config"]["system"])
    out = system.run(ctx)

    dev = dict(device, memory_peak_bytes=ctx.memory_peak)
    breakdown = None
    if ctx.trace:
        metrics, summary, breakdown = per_layer(
            ctx, out, harness.peaks(device["kind"]))
        dev.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
    else:
        values = dict(out["end_to_end"], setup_s=ctx.setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in parts["end_to_end"]}
    checks = out["checks"]
    correct = all(c["ok"] for c in checks.values())
    for line in harness.check_lines(checks):
        harness.log(line)
    print(harness.result_line(correct=correct, attempted=out["attempted"],
                              failed=out["failed"], metrics=metrics,
                              device=dev, checks=checks,
                              breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
