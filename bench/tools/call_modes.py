"""Per process, what one call of a science cell costs when it blocks, when
calls are pipelined, and when the host spins on ``is_ready``: the probe
for a per-process slow mode of ``call_ms``.  A slow mode that shows in the
pipelined cost lies on the device; one that shows only in the blocking cost
lies in the host's round trip.

    python bench/tools/call_modes.py --workload stencil7-l512-loop --seed 1

Run it in a dozen processes, one after another; not part of a benchmark run.
"""

import argparse
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import harness  # noqa: E402
import run  # noqa: E402


def per_call_ms(call, inputs, mode: str, seconds: float = 3.0,
                depth: int = 16) -> float:
    n, t0 = 0, time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        out = call(*inputs)
        if mode == "blocking":
            out.block_until_ready()
        elif mode == "spin":
            while not out.is_ready():
                pass
        elif n % depth == depth - 1:          # pipelined: a few in flight
            out.block_until_ready()
        n += 1
    out.block_until_ready()
    return (time.perf_counter() - t0) / n * 1e3


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    parts = harness.cell(args.workload)
    run.setup_jax()
    harness.device_summary(parts["entry"]["chips"])
    cfg = parts["config"]
    ref = harness.load_module("refs", cfg["reference"])
    system = harness.load_module("systems", cfg["system"])
    inputs = ref.make_inputs(cfg, harness.seed_key(args.seed))
    call = system.timed_call(cfg)
    for _ in range(3):
        call(*inputs).block_until_ready()
    modes = ("blocking", "pipelined", "spin") * 2
    ms = {m: [] for m in modes}
    for m in modes:
        ms[m].append(per_call_ms(call, inputs, m))
    print(f"modes seed {args.seed} " + " ".join(
        f"{m}_ms {v!r}" for m, v in ms.items()), flush=True)


if __name__ == "__main__":
    main()
