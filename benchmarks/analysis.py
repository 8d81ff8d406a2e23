"""Static-auditor lane for the benchmark orchestrator (BENCH_analysis.json).

Not a timing benchmark: this module runs ``python -m repro.core.analysis``
— the registry-wide static kernel auditor — as a child process with
``JAX_PLATFORMS=cpu`` (it only traces, and the orchestrator process already
holds any chip; the CLI re-execs itself under forced host devices for the
sharded cells) and republishes its ``repro.analysis/v2``
report as the orchestrator artifact.  The CSV row carries the audit
wall-clock and the finding/waiver/skip counts as the derived column, so a
drift in either shows up in the same place every other lane drifts.  Since
v2 the auditor's findings include the performance passes — traffic
inflation over a declared limit, a roofline-bound flip against a declared
contract, and measured-vs-predicted drift beyond the band — so this lane
gates on those exactly like the correctness passes.

    PYTHONPATH=src python -m benchmarks.run [--smoke] --only analysis

A non-waived finding fails the module (nonzero orchestrator exit), the
same contract as a conformance failure: the registry must stay audit-clean.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from benchmarks.common import emit, header

ARTIFACT = "BENCH_analysis.json"


def run(smoke: bool = False, json_path: str = ARTIFACT) -> dict:
    cmd = [sys.executable, "-m", "repro.core.analysis",
           "--json", os.path.abspath(json_path)]
    if smoke:
        cmd.append("--smoke")
    env = dict(os.environ, JAX_PLATFORMS="cpu")   # tracing only: no chip
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")

    t0 = time.perf_counter()
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True)
    dt = time.perf_counter() - t0
    sys.stderr.write(proc.stderr)

    if not os.path.exists(json_path):
        raise RuntimeError(
            f"auditor wrote no report (exit {proc.returncode}):\n"
            f"{proc.stdout}")
    with open(json_path) as f:
        report = json.load(f)
    s = report["summary"]
    emit("analysis.audit", dt,
         f"cells={s['cells']} findings={s['findings']} "
         f"waived={s['waived']} skips={s['skips']} "
         f"costed={len(report.get('cost', {}))} "
         f"drift_joined={s.get('drift_joined', 0)}")
    if proc.returncode or s["findings"]:
        raise RuntimeError(
            f"static audit found {s['findings']} non-waived finding(s) "
            f"(exit {proc.returncode}):\n{proc.stdout}")
    return report


if __name__ == "__main__":
    header()
    run(smoke="--smoke" in sys.argv[1:])
