"""Registry-wide static kernel auditor.

The conformance suite (PR 5) is dynamic: it executes one case per cell and
cannot see a write race in a Pallas grid, an accidental f64 promotion, an
extra collective inside a ``check_vma=False`` shard_map body, or a Python
scalar baked into an ``lru_cache``'d compiled closure.  This package is the
ahead-of-execution layer: every (kernel, backend) cell of the live registry
is traced to a closed jaxpr on its conformance-case inputs —
``jax.make_jaxpr`` abstract-evaluates, so compiled ``pallas`` backends
audit off-TPU and sharded backends audit under forced host devices — and
four passes run without executing anything:

  1. **dtypes** (`analysis.dtypes`) — float64-promotion lint under a forced
     x64 trace + accumulation-dtype downgrade check for psum/dot_general;
  2. **grid** (`analysis.grid`) — Pallas BlockSpec coverage proof: every
     output block written exactly once (holes / write races / OOB tiles),
     swept over every constraint-valid tunable point in the full audit;
  3. **collectives** (`analysis.collectives_audit`) — ppermute/psum/
     all_gather census vs each backend's declared communication contract
     (slab stencil: 2 ppermutes, pencil: 4; overlap variants additionally
     prove an interior compute independent of the halo traffic; any
     undeclared all_gather is a finding);
  4. **recompile** (`analysis.recompile`) — AST scan for lru_cache'd
     trace-producing builders keyed on runtime Python scalars.

PR 9 added the static *performance* auditor (`analysis.cost`) on the same
traces — the execution-free twin of `benchmarks/portability.py`:

  5. **traffic** — HBM byte/FLOP census with loop/grid multiplicities and
     BlockSpec-enumerated halo re-reads + accumulator revisits; traffic
     beyond the declared inflation limit over the compulsory boundary
     bytes is a finding;
  6. **roofline** — arithmetic intensity × the detected ChipSpec →
     predicted ms, memory/compute/collective bound verdict, statically
     attainable Eq.-4 fraction; a flip vs `declare_roofline_contract` is
     a finding;
  7. **drift** — predictions joined against measured time (PR-2 tuning
     cache + PR-8 telemetry), self-calibrated by the median
     measured/predicted ratio; a cell beyond the tolerance band is the
     "left N× on the table" finding.

The audited matrix derives from ``conformance.conformance_pairs()`` — never
a hand-written list.  ``python -m repro.core.analysis`` walks it (re-execing
under 8 forced host devices when needed) and writes a ``repro.analysis/v2``
JSON report; ``tests/test_static_analysis.py`` parametrizes the same matrix.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from repro.core.analysis import collectives_audit, cost, dtypes, grid, recompile
from repro.core.analysis import jaxpr_utils as JU
from repro.core.analysis.report import (PASSES, SCHEMA, CellResult, Finding,
                                        SkipRecord, assemble_report)

__all__ = [
    "PASSES",
    "SCHEMA",
    "SMOKE_KERNELS",
    "Finding",
    "SkipRecord",
    "CellResult",
    "audit_cell",
    "audit_pairs",
    "audit_registry",
    "write_report",
]

#: tier-1 smoke subset: one kernel per audited shape of trouble (a pallas
#: sequential accumulator, a halo-exchange stencil, the f64-lint regression
#: site, and the revisited online-softmax decode output).  The smoke matrix
#: is still *derived*: conformance_pairs() filtered to these kernels.
SMOKE_KERNELS = ("stencil7", "babelstream.dot", "minibude.fasten",
                 "attention.decode")

#: bound on the constraint-valid tunable points swept per cell by the full
#: audit; anything dropped is recorded as a skip, never silently truncated
MAX_TUNABLE_POINTS = 32


def audit_pairs(smoke: bool = False) -> List[Tuple[str, str]]:
    """The audited (kernel, backend) matrix — conformance_pairs(), whole or
    filtered to the smoke kernels.  Derived from the live registry."""
    from repro.core import conformance
    pairs = conformance.conformance_pairs()
    if smoke:
        pairs = [(k, b) for k, b in pairs if k in SMOKE_KERNELS]
    return pairs


def _short(exc: BaseException) -> str:
    msg = str(exc).split("\n")[0]
    return f"{type(exc).__name__}: {msg[:200]}"


def _variant_tag(kwargs: Dict[str, Any]) -> str:
    return ",".join(f"{k}={v}" for k, v in sorted(kwargs.items()))


def _recompile_findings(kernel: str, backend: str, fn: Any) -> List[Finding]:
    module = recompile.module_of(fn)
    if module is None or not module.startswith("repro"):
        return []
    findings = []
    for items in recompile.scan_module(module):
        h = dict(items)
        waived = h["waiver"] is not None
        findings.append(Finding(
            kernel=kernel, backend=backend, pass_name="recompile",
            code="scalar-cache-key",
            message=(f"{h['module']}:{h['line']} calls lru_cache'd "
                     f"trace-producing builder {h['builder']!r} with "
                     f"runtime scalar(s): {', '.join(h['scalars'])} — one "
                     f"compiled program per distinct value"),
            waived=waived, waive_reason=h["waiver"],
            detail={"module": h["module"], "line": h["line"],
                    "builder": h["builder"], "scalars": list(h["scalars"])}))
    return findings


def audit_cell(kernel: str, backend: str, *,
               smoke: bool = False) -> CellResult:
    """Run the four static passes on one registry cell.

    Never executes the kernel.  Cells this host cannot even *trace* (a
    sharded backend on a 1-device process) come back with per-pass
    ``SkipRecord``s carrying the reason — the CLI re-execs under forced
    host devices so the full report has none of those.
    """
    from repro.core import conformance
    from repro.core.portable import registry

    k = registry.get(kernel)
    b = k.backend(backend)
    res = CellResult(kernel=kernel, backend=backend)
    passes_run: List[str] = []

    # pass 4 is source-level: it runs even for cells that cannot trace
    res.findings.extend(_recompile_findings(kernel, backend, b.fn))
    passes_run.append("recompile")

    case = conformance.CASES.get(kernel)
    if case is None:
        for p in ("dtypes", "grid", "collectives"):
            res.skips.append(SkipRecord(
                kernel, backend, p,
                "no conformance case (conformance itself fails this cell)"))
        res.passes_run = tuple(passes_run)
        return res
    args, kwargs = case()

    variants = collectives_audit.normalize_contract(
        k.comm_contract(backend), args)
    declared = backend in k.comm_contracts

    traces: Dict[Tuple[Tuple[str, Any], ...], Any] = {}

    def trace_with(extra: Dict[str, Any]):
        key = tuple(sorted({**kwargs, **extra}.items(),
                           key=lambda kv: kv[0]))
        if key not in traces:
            traces[key] = JU.trace(b.fn, args, {**kwargs, **extra})
        return traces[key]

    # --- pass 3: collectives, one trace per contract variant ------------
    coll_ok = True
    for vkw, expected in variants:
        try:
            closed = trace_with(vkw)
        except Exception as exc:
            res.skips.append(SkipRecord(kernel, backend, "collectives",
                                        f"variant {_variant_tag(vkw)} "
                                        f"untraceable: {_short(exc)}"))
            coll_ok = False
            continue
        res.findings.extend(collectives_audit.check_counts(
            kernel, backend, closed, expected, declared,
            variant=_variant_tag(vkw)))
    if coll_ok:
        passes_run.append("collectives")

    # --- passes 1 + 2 run on the default-variant trace -------------------
    default_kw = variants[0][0]
    try:
        closed = trace_with(default_kw)
    except Exception as exc:
        for p in ("dtypes", "grid"):
            res.skips.append(SkipRecord(kernel, backend, p, _short(exc)))
        res.passes_run = tuple(passes_run)
        return res

    res.findings.extend(dtypes.run_accum_check(
        kernel, backend, closed, k.accum_dtype))
    try:
        res.findings.extend(dtypes.run_f64_lint(
            kernel, backend, b.fn, args, {**kwargs, **default_kw}))
        passes_run.append("dtypes")
    except Exception as exc:
        res.skips.append(SkipRecord(kernel, backend, "dtypes",
                                    f"x64 trace failed: {_short(exc)}"))

    accum = k.grid_contract(backend).get("accumulator_outputs", ())
    gfindings, ncalls = grid.run(kernel, backend, closed, accum,
                                 variant=_variant_tag(default_kw))
    res.findings.extend(gfindings)
    passes_run.append("grid")

    # --- passes 5 + 6: traffic census + roofline verdict -----------------
    from repro.core.roofline import detect_chip
    chip = detect_chip()
    try:
        tr = cost.census(closed)
        v = cost.verdict(tr, chip)
        res.findings.extend(cost.traffic_findings(
            kernel, backend, k, tr, variant=_variant_tag(default_kw)))
        res.findings.extend(cost.roofline_findings(kernel, backend, k, tr, v))
        res.cost = {"chip": chip.name, "traffic": tr.to_json(),
                    "verdict": v.to_json(), "points": [],
                    "best_predicted": None}
        passes_run.extend(["traffic", "roofline"])
    except Exception as exc:
        for p in ("traffic", "roofline"):
            res.skips.append(SkipRecord(kernel, backend, p, _short(exc)))

    # full audit: cross-check the declared TunableSpace constraint — every
    # constraint-valid point must still satisfy the coverage proof AND get
    # its own traffic census (a block size that re-streams whole operands
    # is a per-point defect the default point can't show)
    space = k.tunable_space(backend)
    if not smoke and ncalls and space is not None:
        try:
            points = space.valid_points(*args, **kwargs)
        except Exception as exc:
            points = []
            res.skips.append(SkipRecord(
                kernel, backend, "grid",
                f"constraint not evaluable here: {_short(exc)}"))
        if len(points) > MAX_TUNABLE_POINTS:
            res.skips.append(SkipRecord(
                kernel, backend, "grid",
                f"tunable sweep capped at {MAX_TUNABLE_POINTS} of "
                f"{len(points)} valid points"))
            points = points[:MAX_TUNABLE_POINTS]
        for pt in points:
            try:
                pclosed = trace_with({**default_kw, **pt})
            except Exception as exc:
                res.findings.append(Finding(
                    kernel=kernel, backend=backend, pass_name="grid",
                    code="constraint-admits-untraceable-point",
                    message=(f"constraint-valid point {pt} does not even "
                             f"trace: {_short(exc)}"),
                    detail={"point": {n: repr(v) for n, v in pt.items()}}))
                continue
            pfind, _ = grid.run(kernel, backend, pclosed, accum,
                                variant=_variant_tag(pt))
            res.findings.extend(pfind)
            if res.cost is None:
                continue
            try:
                ptr = cost.census(pclosed)
                pv = cost.verdict(ptr, chip)
            except Exception as exc:
                res.skips.append(SkipRecord(
                    kernel, backend, "traffic",
                    f"point {_variant_tag(pt)} not costable: {_short(exc)}"))
                continue
            res.findings.extend(cost.traffic_findings(
                kernel, backend, k, ptr, variant=_variant_tag(pt)))
            res.cost["points"].append({
                "params": {n: repr(v) for n, v in pt.items()},
                "flops": ptr.flops, "hbm_bytes": ptr.hbm_bytes,
                "inflation": ptr.inflation,
                "predicted_ms": pv.predicted_s * 1e3, "bound": pv.bound})

    if res.cost is not None and res.cost["points"]:
        best = min(res.cost["points"], key=lambda p: p["predicted_ms"])
        res.cost["best_predicted"] = best["params"]

    res.passes_run = tuple(passes_run)
    return res


def audit_registry(*, smoke: bool = False, tuning_cache: Any = None,
                   telemetry_trace: Optional[str] = None,
                   drift_band: Optional[float] = None) -> Dict[str, Any]:
    """Audit the whole derived matrix and assemble the v2 report.

    The per-cell passes (1–6) run first; the registry-level drift gate
    (pass 7) then joins the tuning cache (``tuning_cache`` path, default
    the process cache) and optional ``telemetry_trace`` JSONL against the
    static predictions for the same matrix.
    """
    import jax

    from repro.core.roofline import detect_chip

    pairs = audit_pairs(smoke)
    cells = [audit_cell(k, b, smoke=smoke) for k, b in pairs]
    drift = cost.drift_gate(cache_path=tuning_cache,
                            trace_path=telemetry_trace,
                            pairs=set(pairs), band=drift_band)
    return assemble_report(cells, device_count=jax.device_count(),
                           smoke=smoke, chip=detect_chip().name, drift=drift)


def write_report(report: Dict[str, Any], path: str) -> None:
    import json
    import os
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
