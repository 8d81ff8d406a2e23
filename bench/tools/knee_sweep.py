"""Find a serving cell's knee: the highest arrival rate the system sustains
without a growing backlog.  One set-up, then one window per seed and rate,
each after the cell's pre-roll.

    python bench/tools/knee_sweep.py --workload <name> --rates 1,2,3 \
        --seconds 30 --seeds 7,8

Per seed and rate it prints the end-to-end metrics and the queue wait of the first
and the last quarter of the arrivals: a backlog that grows through the
window shows as a last-quarter wait far above the first.  The cell's
``rate_rps`` is then set by hand to about 0.8 of the knee (see PERF.md).
"""

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import harness  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seeds", default="7")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    parts = harness.cell(args.workload)
    preroll = float(parts["cell"]["preroll_s"])
    run.setup_jax()
    device = harness.device_summary(parts["entry"]["chips"])
    ctx = harness.Run(workload=args.workload, seed=seeds[0],
                      seconds=args.seconds, trace=False, parts=parts,
                      t_start=time.perf_counter(), device=device)
    sysmod = harness.load_module("systems", parts["config"]["system"])
    engine = sysmod.setup(ctx, seeds[0])
    print(f"setup {time.perf_counter() - ctx.t_start:.3f} s", flush=True)
    for seed in seeds:
        for rate in (float(r) for r in args.rates.split(",")):
            reqs = sysmod.requests(sysmod.schedule(ctx, seed, rate,
                                                   args.seconds, preroll))
            steps0 = engine.stats["decode_steps"]
            t = time.perf_counter()
            out = sysmod.serve(engine, reqs, args.seconds,
                               on_start=time.perf_counter,
                               on_end=lambda t: None, preroll=preroll)
            arrived = sysmod.in_window(reqs)
            waits = [r.t_admitted - r.arrival_time for r in arrived]
            q = max(1, len(arrived) // 4)
            row = dict(sysmod.summarize(reqs, out["window_start"],
                                        args.seconds),
                       seed=seed, rate=rate, arrivals=len(arrived),
                       unfinished=sysmod.unfinished(arrived),
                       wait_first_q_ms=1e3 * sum(waits[:q]) / q,
                       wait_last_q_ms=1e3 * sum(waits[-q:]) / q,
                       decode_steps=engine.stats["decode_steps"] - steps0,
                       drained=out["drained"],
                       run_s=time.perf_counter() - t)
            print("sweep " + json.dumps(row), flush=True)

if __name__ == "__main__":
    main()
