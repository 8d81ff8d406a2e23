"""CLI: ``python -m repro.core.analysis`` — audit the whole registry.

Walks the derived (kernel, backend) matrix, runs the seven static passes
(four correctness + the PR-9 traffic/roofline/drift performance passes),
writes a ``repro.analysis/v2`` JSON report, and exits nonzero iff any
non-waived finding survives.  The sharded backends only *trace* on a
multi-device topology, so when the parent process is pinned to one device
the CLI re-execs itself under ``JAX_PLATFORMS=cpu`` and
``--xla_force_host_platform_device_count=8`` (appended to — never
clobbering — the user's XLA_FLAGS).  The audit only traces, so the child
never needs a chip the parent may hold.  ``--smoke`` skips the re-exec and
the per-tunable-point sweep: the seconds-scale drift-lane subset.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ARTIFACT = "ANALYSIS_report.json"
DEFAULT_DEVICES = 8
_CHILD_ENV = "REPRO_ANALYSIS_CHILD"


def _print_summary(report) -> None:
    s = report["summary"]
    print(f"static analysis: {s['cells']} cells, {s['audited']} audited, "
          f"{s['findings']} finding(s), {s['waived']} waived, "
          f"{s['skips']} skip(s) "
          f"[device_count={report['device_count']}"
          f"{', smoke' if report['smoke'] else ''}]")
    drift = report.get("drift", {})
    if drift:
        cal = drift.get("calibration")
        cal_s = f"{cal:.1f}x" if cal is not None else "n/a"
        print(f"  perf model: chip={report.get('chip')}, "
              f"{len(report.get('cost', {}))} cells costed, drift joins "
              f"{drift.get('joined', 0)}/{drift.get('measurements', 0)} "
              f"(calibration {cal_s}, band {drift.get('band')}x)")
    for f in report["findings"]:
        print(f"  FINDING {f['kernel']}[{f['backend']}] {f['pass_name']}/"
              f"{f['code']}: {f['message']}")
    for f in report["waived"]:
        print(f"  waived  {f['kernel']}[{f['backend']}] {f['pass_name']}/"
              f"{f['code']}: {f['waive_reason']}")
    for s_ in report["skips"]:
        print(f"  skip    {s_['kernel']}[{s_['backend']}] "
              f"{s_['pass_name']}: {s_['reason']}")


def _audit_here(args) -> int:
    from repro.core import analysis
    report = analysis.audit_registry(smoke=args.smoke,
                                     tuning_cache=args.tuning_cache,
                                     telemetry_trace=args.telemetry,
                                     drift_band=args.drift_band)
    analysis.write_report(report, args.json)
    _print_summary(report)
    return 1 if report["summary"]["findings"] else 0


def _reexec(args, devices: int) -> int:
    from repro.launch.hostsim import merged_xla_flags
    env = dict(os.environ, JAX_PLATFORMS="cpu")   # tracing only: no chip
    env["XLA_FLAGS"] = merged_xla_flags(devices, env)
    env[_CHILD_ENV] = "1"
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "repro.core.analysis",
           "--json", os.path.abspath(args.json), "--devices", str(devices)]
    if args.smoke:
        cmd.append("--smoke")
    if args.tuning_cache:
        cmd += ["--tuning-cache", os.path.abspath(args.tuning_cache)]
    if args.telemetry:
        cmd += ["--telemetry", os.path.abspath(args.telemetry)]
    if args.drift_band is not None:
        cmd += ["--drift-band", str(args.drift_band)]
    return subprocess.call(cmd, env=env)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        prog="python -m repro.core.analysis",
        description="static kernel auditor over the live registry")
    ap.add_argument("--smoke", action="store_true",
                    help="smoke-kernel subset, default params only, no "
                         "multi-device re-exec (PR-time drift check)")
    ap.add_argument("--json", default=ARTIFACT,
                    help=f"report path (default {ARTIFACT})")
    ap.add_argument("--devices", type=int, default=DEFAULT_DEVICES,
                    help="forced host-device count for the sharded cells")
    ap.add_argument("--tuning-cache", default=None,
                    help="tuning cache JSON joined by the drift gate "
                         "(default: the process cache, $REPRO_TUNING_CACHE "
                         "or ~/.cache/repro/tuning.json)")
    ap.add_argument("--telemetry", default=None,
                    help="telemetry JSONL trace whose "
                         "registry.time_backend.result events feed the "
                         "drift gate")
    ap.add_argument("--drift-band", type=float, default=None,
                    help="drift tolerance band (x the calibrated median; "
                         "default 8.0)")
    args = ap.parse_args(argv)

    if not args.smoke and not os.environ.get(_CHILD_ENV):
        import jax
        if jax.device_count() < 2:
            # jax reads XLA_FLAGS once at backend init — too late for this
            # process, so the full audit forks a multi-device child
            raise SystemExit(_reexec(args, args.devices))
    raise SystemExit(_audit_here(args))


if __name__ == "__main__":
    main()
