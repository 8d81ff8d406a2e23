"""Readings for a cell's correctness limits: the program's on many seeds
and the control's on the same ones, in one process.

    python bench/tools/calibrate.py --workload <name> --seeds 1,2,3 [--seconds s]

Each system's ``calibrate`` prints one ``calibrate seed ...`` line per seed.
Not part of a benchmark run: the limits in ``bench/workloads/<cell>.json``
are set from what it prints (see PERF.md).
"""

import argparse
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import harness  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    parts = harness.cell(args.workload)
    run.setup_jax()
    device = harness.device_summary(parts["entry"]["chips"])
    ctx = harness.Run(workload=args.workload, seed=0, seconds=args.seconds,
                      trace=False, parts=parts, t_start=time.perf_counter(),
                      device=device)
    system = harness.load_module("systems", parts["config"]["system"])
    system.calibrate(ctx, [int(s) for s in args.seeds.split(",")])


if __name__ == "__main__":
    main()
