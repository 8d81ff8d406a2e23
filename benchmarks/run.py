"""Benchmark orchestrator: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only <name>] [--smoke]

Emits ``name,us_per_call,derived`` CSV (scaffold contract).  Mapping:
    stencil          -> paper Fig. 3 (Eq. 1 bandwidth)
    babelstream      -> paper Fig. 4 (Eq. 2 bandwidth)
    minibude         -> paper Figs. 6-7 (Eq. 3 GFLOP/s)
    hartree_fock     -> paper Table 4 (wall-clock)
    portability      -> paper Table 5 (Eq. 4 Phi-bar, tuned via the
                        registry sweep; writes BENCH_portability.json)
    scaling          -> weak/strong device-count scaling of the sharded
                        backends, xla_shard vs shard_pallas per kernel
                        (simulated host devices; writes BENCH_scaling.json)
    roofline_kernels -> paper Fig. 2 + Tables 2-3 (AI / bound placement)
    lm_step          -> framework-level LM step timings
    analysis         -> registry-wide static kernel auditor (jaxpr/grid/
                        collective/recompile passes; writes
                        BENCH_analysis.json, fails on non-waived findings)

``--smoke`` shrinks every module that supports it (a ``smoke=`` parameter
on its ``run()``) to seconds-scale shapes with ``iters=1`` — the PR-time
drift lane is ``python -m benchmarks.run --smoke --only portability``.

A failing module never aborts the run mid-CSV: its traceback is buffered
and printed to stderr *after* the CSV block, and the exit code is nonzero
only once every requested module has run.
"""

from __future__ import annotations

import argparse
import inspect
import sys
import traceback

from benchmarks.common import header
from repro.launch.compile_cache import configure_compile_cache

MODULES = ["stencil", "babelstream", "minibude", "hartree_fock",
           "portability", "scaling", "roofline_kernels", "lm_step",
           "analysis"]


def _run_module(name: str, smoke: bool) -> None:
    mod = __import__(f"benchmarks.{name}", fromlist=["run"])
    params = inspect.signature(mod.run).parameters
    if smoke and "smoke" in params:
        mod.run(smoke=True)
    else:
        mod.run()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, metavar="MODULE",
                    help=f"run a single module (one of {MODULES})")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, iters=1 — PR-time drift check")
    args = ap.parse_args(argv)
    if args.only is not None and args.only not in MODULES:
        print(f"unknown benchmark module {args.only!r}; "
              f"known modules: {MODULES}", file=sys.stderr)
        raise SystemExit(2)
    mods = [args.only] if args.only else MODULES

    configure_compile_cache()
    header()
    failures = []
    for name in mods:
        try:
            _run_module(name, args.smoke)
        except Exception:
            failures.append((name, traceback.format_exc()))
    if failures:
        for name, tb in failures:
            print(f"\n--- benchmark module {name!r} failed ---\n{tb}",
                  file=sys.stderr)
        print(f"benchmark modules failed: {[n for n, _ in failures]}",
              file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
