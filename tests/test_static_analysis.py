"""Static kernel auditor: registry matrix + planted-defect fixtures.

Two kinds of coverage:

  * the *clean* direction — the live registry's cells audit without
    findings.  Tier-1 parametrizes ``analysis.audit_pairs(smoke=True)``
    (derived, never hand-written); the ``slow`` lane runs the CLI end to
    end, which re-execs under 8 forced host devices so the sharded cells
    trace for real and the report must come back with zero skips;
  * the *dirty* direction — a planted bad kernel per pass proves each
    analysis actually fires: an undeclared Pallas write race, a coverage
    hole, an out-of-bounds index map, a weak-scalar f64 promotion, a bf16
    accumulation downgrade, an undeclared all_gather, and a scalar-keyed
    ``lru_cache`` builder (with and without its waiver comment).  A
    detector nobody has seen fail is just a comment.
"""

import functools
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core import analysis, conformance
from repro.core.analysis import (collectives_audit, dtypes, grid,
                                 jaxpr_utils as JU, recompile)
from repro.core.portable import (Backend, BackendUnavailableError,
                                 PortableKernel, registry)

SMOKE_PAIRS = analysis.audit_pairs(smoke=True)


# ---------------------------------------------------------------------------
# clean direction: the live registry audits without findings
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "kernel,backend", SMOKE_PAIRS,
    ids=[f"{k}-{b}" for k, b in SMOKE_PAIRS])
def test_registry_cell_audits_clean(kernel, backend):
    """Every smoke cell: no non-waived findings; cells this 1-device host
    cannot trace surface as explicit SkipRecords, never silent passes."""
    res = analysis.audit_cell(kernel, backend, smoke=True)
    assert res.errors == [], [f.to_json() for f in res.errors]
    assert "recompile" in res.passes_run
    for s in res.skips:
        assert s.reason  # a skip always says why


def test_audit_matrix_derives_from_live_registry():
    """Registering a backend adds its audit cell with no suite edit."""
    k = registry.get("stencil7")
    assert ("stencil7", "tmp_audit_backend") not in analysis.audit_pairs()
    k.add_backend("tmp_audit_backend", k.backends["xla"].fn)
    try:
        assert ("stencil7", "tmp_audit_backend") in analysis.audit_pairs()
        res = analysis.audit_cell("stencil7", "tmp_audit_backend",
                                  smoke=True)
        assert res.errors == []
    finally:
        del k.backends["tmp_audit_backend"]


@pytest.mark.slow
def test_full_audit_cli_is_clean():
    """End to end: the CLI re-execs under forced host devices, audits the
    whole matrix, and reports zero findings and zero skips."""
    out = os.path.abspath("ANALYSIS_report_test.json")
    env = dict(os.environ)
    # importing repro.launch.dryrun (test_dryrun_integration) plants a
    # 512-device XLA_FLAGS in this process's environ; the child must see
    # the documented lane (re-exec to 8 forced devices), not that leak
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_ANALYSIS_CHILD", None)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "repro.core.analysis", "--json", out],
            env=env, capture_output=True, text=True, timeout=1200)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        with open(out) as f:
            report = json.load(f)
    finally:
        if os.path.exists(out):
            os.remove(out)
    assert report["schema"] == analysis.SCHEMA
    assert report["summary"]["findings"] == 0
    assert report["summary"]["skips"] == 0
    assert report["summary"]["audited"] == report["summary"]["cells"]
    assert report["device_count"] >= 2


# ---------------------------------------------------------------------------
# planted fixtures: each pass proven to fire
# ---------------------------------------------------------------------------
def _racy_sum(x):
    """Planted grid defect: every grid step writes output block (0,) but
    the output is NOT a declared accumulator — a write race."""
    def body(x_ref, o_ref):
        o_ref[...] = x_ref[...]
    return pl.pallas_call(
        body, grid=(4,),
        in_specs=[pl.BlockSpec((32,), lambda i: (i,))],
        out_specs=pl.BlockSpec((32,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((32,), jnp.float32),
        interpret=True)(x)


def _holey_copy(x):
    """Planted grid defect: 4 output blocks, 2 grid steps — blocks 2, 3
    are never written."""
    def body(x_ref, o_ref):
        o_ref[...] = x_ref[...]
    return pl.pallas_call(
        body, grid=(2,),
        in_specs=[pl.BlockSpec((32,), lambda i: (i,))],
        out_specs=pl.BlockSpec((32,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((128,), jnp.float32),
        interpret=True)(x)


def _oob_copy(x):
    """Planted grid defect: index map addresses block i+1 of a 4-block
    space at grid step 3 — out of bounds (and block 0 is a hole)."""
    def body(x_ref, o_ref):
        o_ref[...] = x_ref[...]
    return pl.pallas_call(
        body, grid=(4,),
        in_specs=[pl.BlockSpec((32,), lambda i: (i,))],
        out_specs=pl.BlockSpec((32,), lambda i: (i + 1,)),
        out_shape=jax.ShapeDtypeStruct((128,), jnp.float32),
        interpret=True)(x)


def _grid_findings(fn, accumulator_outputs=()):
    closed = JU.trace(fn, (jnp.ones((128,), jnp.float32),), {})
    findings, ncalls = grid.run("planted", "pallas_interpret", closed,
                                accumulator_outputs)
    assert ncalls == 1
    return {f.code for f in findings}, findings


def test_planted_write_race_fires():
    codes, findings = _grid_findings(_racy_sum)
    assert codes == {"write-race"}
    assert findings[0].detail["revisited"] == [[0]]


def test_declared_accumulator_legalizes_revisit():
    """The same planted kernel with its output declared as an accumulator
    audits clean — the dot-partial pattern."""
    codes, _ = _grid_findings(_racy_sum, accumulator_outputs=(0,))
    assert codes == set()


def test_planted_coverage_hole_fires():
    codes, findings = _grid_findings(_holey_copy)
    assert codes == {"coverage-hole"}
    hole = findings[0]
    assert hole.detail["holes"] == [[2], [3]]


def test_planted_oob_tile_fires():
    codes, _ = _grid_findings(_oob_copy)
    assert "out-of-bounds-tile" in codes


def test_planted_f64_promotion_fires():
    """The minibude bug class, distilled: jnp.where over two weak Python
    scalars anchors to float64 under x64."""
    def bad(x):
        return jnp.where(x > 0, 2.0, 4.0) * x

    findings = dtypes.run_f64_lint(
        "planted", "xla", bad, (jnp.ones((8,), jnp.float32),), {})
    assert any(f.code == "f64-promotion" for f in findings)

    def good(x):
        c = x.dtype.type
        return jnp.where(x > 0, c(2.0), c(4.0)) * x

    assert dtypes.run_f64_lint(
        "planted", "xla", good, (jnp.ones((8,), jnp.float32),), {}) == []


def test_planted_accum_downgrade_fires():
    def bf16_dot(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())))

    a = jnp.ones((8, 8), jnp.bfloat16)
    closed = JU.trace(bf16_dot, (a, a), {})
    findings = dtypes.run_accum_check("planted", "xla", closed, "float32")
    assert [f.code for f in findings] == ["accum-downgrade"]
    assert findings[0].detail["dtype"] == "bfloat16"

    def f32_dot(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    closed = JU.trace(f32_dot, (a, a), {})
    assert dtypes.run_accum_check("planted", "xla", closed, "float32") == []


def test_planted_undeclared_all_gather_fires():
    """A sharded body that quietly re-materializes the global array.
    check_vma=False mirrors how such a defect ships: replication checking
    would have rejected the spec combination outright."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))

    def gathers(x):
        def body(lx):
            return jnp.sum(jax.lax.all_gather(lx, "x"))
        return jax.shard_map(body, mesh=mesh, in_specs=(P("x"),),
                             out_specs=P(), check_vma=False)(x)

    closed = JU.trace(gathers, (jnp.ones((8,), jnp.float32),), {})
    (_, expected), = collectives_audit.normalize_contract(None, ())
    findings = collectives_audit.check_counts(
        "planted", "xla_shard", closed, expected, declared=False)
    assert "undeclared-all-gather" in {f.code for f in findings}


def test_comm_contract_mismatch_fires():
    """A declared contract that disagrees with the trace is a mismatch —
    distinct from the undeclared case."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))

    def summed(x):
        return jax.shard_map(lambda lx: jax.lax.psum(lx, "x"), mesh=mesh,
                             in_specs=(P("x"),), out_specs=P(),
                             check_vma=False)(x)

    closed = JU.trace(summed, (jnp.ones((8,), jnp.float32),), {})
    findings = collectives_audit.check_counts(
        "planted", "xla_shard", closed, {"ppermute": 0, "psum": 0},
        declared=True)
    assert {f.code for f in findings} == {"comm-contract-mismatch"}
    # and the correct declaration audits clean
    assert collectives_audit.check_counts(
        "planted", "xla_shard", closed, {"ppermute": 0, "psum": 1},
        declared=True) == []


_HAZARD_SRC = textwrap.dedent("""
    import functools
    import jax

    @functools.lru_cache(maxsize=None)
    def _build(n, scalar):
        return jax.jit(lambda x: x * scalar + n)

    def entry(x, scalar=0.5):
        return _build(x.shape[0], float(scalar))(x)
""")

_WAIVED_SRC = _HAZARD_SRC.replace(
    "    return jax.jit",
    "    # audit: compile-time-constant(scalar) — baked by design\n"
    "    return jax.jit")


def test_planted_scalar_cache_key_fires():
    hazards = recompile.scan_source(_HAZARD_SRC, "planted_mod")
    assert len(hazards) == 1
    h = hazards[0]
    assert h["builder"] == "_build" and h["waiver"] is None
    # both the float(...) wrapper and the float-default parameter are named
    assert any("float(scalar)" in s for s in h["scalars"])


def test_waiver_comment_downgrades_hazard():
    hazards = recompile.scan_source(_WAIVED_SRC, "planted_mod")
    assert len(hazards) == 1
    assert "compile-time-constant(scalar)" in hazards[0]["waiver"]


def test_shape_keyed_builder_is_not_a_hazard():
    src = _HAZARD_SRC.replace("float(scalar))", "2 * n)")
    assert recompile.scan_source(src, "planted_mod") == []


def test_planted_cell_end_to_end():
    """Full plumbing: a temporarily registered kernel with a racy Pallas
    backend comes back from audit_cell with exactly the planted finding."""
    name = "planted.racy"
    k = PortableKernel(name=name, doc="planted auditor fixture")
    k.add_backend("xla", lambda x: jnp.broadcast_to(x[:32], (32,)))
    k.add_backend("pallas_interpret", _racy_sum)
    registry._kernels[name] = k
    conformance.CASES[name] = lambda: (
        (jnp.ones((128,), jnp.float32),), {})
    try:
        res = analysis.audit_cell(name, "pallas_interpret", smoke=True)
        assert "write-race" in {f.code for f in res.errors}
        # ...and the declared-accumulator escape hatch clears it
        k.declare_grid_contract("pallas_interpret",
                                accumulator_outputs=(0,))
        res = analysis.audit_cell(name, "pallas_interpret", smoke=True)
        assert "write-race" not in {f.code for f in res.errors}
    finally:
        del registry._kernels[name]
        del conformance.CASES[name]


# ---------------------------------------------------------------------------
# satellites: tolerance routing + availability reasons
# ---------------------------------------------------------------------------
def test_validate_routes_through_conformance_tolerance():
    name = "planted.tol"
    k = PortableKernel(name=name)
    k.add_backend("xla", lambda x: x)
    k.add_backend("off_by_eps", lambda x: x + 1e-6)
    registry._kernels[name] = k
    conformance.ORACLE_TOL[name] = (0.0, 1e-3)
    try:
        x = jnp.ones((4,), jnp.float32)
        # default tolerance comes from the conformance table: 1e-6 < 1e-3
        k.validate(x, backend="off_by_eps")
        # a bitwise cell validates at rtol=atol=0 and must reject the drift
        conformance.ORACLE_TOL[name] = "bitwise"
        with pytest.raises(AssertionError):
            k.validate(x, backend="off_by_eps")
        # explicit tolerances still override per call
        k.validate(x, backend="off_by_eps", rtol=0.0, atol=1e-3)
    finally:
        del registry._kernels[name]
        del conformance.ORACLE_TOL[name]


def test_unavailable_reason_from_false_predicate():
    def never(): return False
    b = Backend(name="b", fn=lambda: None, available=never)
    assert b.is_available() is False
    assert "returned False" in b.unavailable_reason
    assert "never" in b.unavailable_reason


def test_unavailable_reason_from_raising_probe():
    def boom(): raise RuntimeError("no TPU runtime linked")
    b = Backend(name="b", fn=lambda: None, available=boom)
    assert b.is_available() is False
    assert "RuntimeError" in b.unavailable_reason
    assert "no TPU runtime linked" in b.unavailable_reason


def test_unavailable_reason_resets_when_available():
    flag = {"ok": False}
    b = Backend(name="b", fn=lambda: None, available=lambda: flag["ok"])
    assert not b.is_available() and b.unavailable_reason
    flag["ok"] = True
    assert b.is_available() and b.unavailable_reason is None


def test_require_available_surfaces_reason():
    k = PortableKernel(name="planted.unavail")
    k.add_backend("xla", lambda x: x)
    k.add_backend("tpu_only", lambda x: x, available=lambda: False)
    with pytest.raises(BackendUnavailableError, match="returned False"):
        k._require_available("tpu_only")


def test_conformance_skip_carries_reason():
    """The conformance suite's skip message now carries the probe's own
    words, not a bare False."""
    name = "planted.skip"
    k = PortableKernel(name=name)
    k.add_backend("xla", lambda x: x)
    k.add_backend("elsewhere", lambda x: x,
                  available=lambda: (_ for _ in ()).throw(
                      RuntimeError("requires libfoo")))
    registry._kernels[name] = k
    conformance.CASES[name] = lambda: ((jnp.ones((4,), jnp.float32),), {})
    conformance.ORACLE_TOL[name] = (0.0, 0.0)
    try:
        with pytest.raises(BackendUnavailableError, match="requires libfoo"):
            conformance.check_backend(name, "elsewhere")
    finally:
        del registry._kernels[name]
        del conformance.CASES[name]
        del conformance.ORACLE_TOL[name]


# ---------------------------------------------------------------------------
# report schema
# ---------------------------------------------------------------------------
def test_report_schema_and_waiver_visibility():
    """Smoke report: schema v2, matrix == derived smoke matrix, and the
    three intentional registry waivers stay visible (never silent)."""
    report = analysis.audit_registry(smoke=True)
    assert report["schema"] == "repro.analysis/v2"
    assert report["passes"] == list(analysis.PASSES)
    assert sorted(map(tuple, report["matrix"])) == sorted(SMOKE_PAIRS)
    assert report["summary"]["findings"] == 0
    waived_codes = {w["code"] for w in report["waived"]}
    assert waived_codes <= {"scalar-cache-key"}
    for w in report["waived"]:
        assert "compile-time-constant" in w["waive_reason"]
