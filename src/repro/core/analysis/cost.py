"""Passes 5–7 — the static performance auditor (traffic / roofline / drift).

PR 7 proved every (kernel, backend) registry cell *correct* without
executing it; this module proves every cell *fast enough* without executing
it.  Three execution-free passes over the same closed-jaxpr traces:

  5. **traffic** — a census of HBM bytes read/written and FLOPs, walked
     from the jaxpr with loop/grid multiplicities (``scan`` bodies count
     ``length`` times, ``pallas_call`` bodies once per grid step, the most
     expensive ``cond`` branch wins).  Pallas BlockSpecs are costed by the
     same index-map enumeration as the grid pass, so halo *re-reads* and
     accumulator *revisits* are counted as real traffic, not wished away.
     An operand left in HBM (``pl.ANY``) is costed by the kernel's own
     ``dma_start`` copies at the body's multiplicity; a copy under
     ``pl.when`` counts on every step, an upper bound like ``cond``.
     The jaxpr boundary (invars + consts + outvars) is the minimum-traffic
     floor; ``inflation = traffic / floor`` is the "how many times over the
     compulsory bytes does this kernel move" number, and a cell whose
     inflation exceeds its declared (or the default) limit is a finding.
  6. **roofline** — arithmetic intensity × the detected ``ChipSpec`` →
     three-term predicted seconds, a ``bound`` verdict
     (memory | compute | collective), and the statically attainable
     fraction of peak compute — the paper's Eq.-4 e_i upper bound computed
     without running anything.  Kernels may pin their expected bound via
     ``declare_roofline_contract``; a verdict flip is a finding.
  7. **drift** — join the predictions against *measured* time from the
     PR-2 tuning cache and PR-8 ``registry.time_backend`` telemetry.  The
     absolute scale of a static model is host-dependent, so the gate
     self-calibrates: the median measured/predicted ratio across all joined
     cells is the host factor, and a cell whose own ratio exceeds
     ``band ×`` the median is the "your kernel left N× on the table" lint.

The same cost model is the prior for ``tuning.tune(search="model")``:
:func:`rank_points` orders a tunable grid by predicted cost and
:func:`prune_dominated` drops points that are strictly worse on traffic
AND parallelism before anything is timed.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import math
import re
import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.analysis import jaxpr_utils as JU
from repro.core.analysis.grid import MAX_GRID_POINTS
from repro.core.analysis.report import Finding
from repro.core.roofline import ChipSpec, detect_chip

__all__ = [
    "Traffic",
    "Verdict",
    "census",
    "verdict",
    "traffic_findings",
    "roofline_findings",
    "drift_gate",
    "collect_measurements",
    "parse_shape_signature",
    "rank_points",
    "prune_dominated",
    "DEFAULT_INFLATION_LIMIT",
    "DEFAULT_DRIFT_BAND",
    "MIN_DRIFT_JOINS",
    "DRIFT_WAIVERS",
]

#: traffic over the compulsory floor tolerated without a declared limit —
#: generous enough for halo re-reads and online-softmax revisits, tight
#: enough that a block mapping re-streaming whole operands per grid step
#: (the planted fixture, a real O(grid) blowup) still fires
DEFAULT_INFLATION_LIMIT = 8.0

#: drift findings fire when a cell's measured/predicted ratio exceeds
#: ``band ×`` the registry-wide median ratio (the host calibration factor)
DEFAULT_DRIFT_BAND = 8.0

#: the calibration median is meaningless over fewer joins than this — the
#: gate reports the joins but emits no findings below it
MIN_DRIFT_JOINS = 3

#: (kernel, backend) cells whose drift is understood and accepted; the
#: finding still appears in the report's ``waived`` list
DRIFT_WAIVERS: Dict[Tuple[str, str], str] = {}


def _short(exc: BaseException) -> str:
    msg = str(exc).split("\n")[0]
    return f"{type(exc).__name__}: {msg[:200]}"


def _prod(xs) -> float:
    out = 1.0
    for x in xs:
        out *= float(x)
    return out


def _aval_bytes(aval: Any) -> float:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0.0
    try:
        return _prod(shape) * np.dtype(dtype).itemsize
    except TypeError:
        return 0.0


def _out_elems(eqn: Any) -> float:
    for v in eqn.outvars:
        shape = getattr(v.aval, "shape", None)
        if shape is not None:
            return _prod(shape)
    return 1.0


# FLOP weights per output element.  Deliberately conventional (everything
# elementwise is 1 FLOP/element, a dot_general is 2·M·N·K): the model is
# used for *relative* verdicts and priors, not absolute TFLOP/s claims.
_EW_PRIMS = frozenset((
    "add", "sub", "mul", "div", "rem", "pow", "atan2", "max", "min",
    "nextafter", "and", "or", "xor", "not", "neg", "abs", "sign", "floor",
    "ceil", "round", "exp", "exp2", "log", "log1p", "expm1", "tanh", "sin",
    "cos", "tan", "asin", "acos", "atan", "sinh", "cosh", "asinh", "acosh",
    "atanh", "sqrt", "rsqrt", "cbrt", "logistic", "erf", "erfc", "erf_inv",
    "square", "integer_pow", "is_finite", "eq", "ne", "lt", "le", "gt",
    "ge", "select_n", "clamp",
))
_REDUCE_PRIMS = frozenset((
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "argmax", "argmin", "cumsum", "cumprod", "cummax",
    "cummin", "cumlogsumexp",
))
_CONTAINER_PRIMS = frozenset((
    "pjit", "closed_call", "core_call", "remat", "checkpoint", "remat2",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "custom_jvp_call_jaxpr",
))


@dataclasses.dataclass
class Traffic:
    """The census: one traced cell's modeled work and data movement.

    All byte/FLOP totals are *program-wide* (shard_map bodies are counted
    once per shard); :func:`verdict` divides the compute and memory terms
    by ``shards`` when predicting wall-clock.
    """

    flops: float = 0.0
    hbm_read_bytes: float = 0.0
    hbm_write_bytes: float = 0.0
    hbm_min_bytes: float = 0.0       # compulsory floor: invars+consts+outvars
    collective_bytes: float = 0.0
    collective_count: float = 0.0
    reread_bytes: float = 0.0        # pallas input blocks read more than once
    revisit_bytes: float = 0.0       # pallas accumulator blocks re-written
    pallas_calls: int = 0
    grid_steps: float = 0.0          # total pallas grid steps (× loop mult)
    approx_grids: int = 0            # grids costed without enumeration
    unknown_trip_loops: int = 0      # while-loops counted as one trip
    shards: int = 1

    @property
    def hbm_bytes(self) -> float:
        return self.hbm_read_bytes + self.hbm_write_bytes

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / max(self.hbm_bytes, 1.0)

    @property
    def inflation(self) -> float:
        return self.hbm_bytes / max(self.hbm_min_bytes, 1.0)

    def merge(self, other: "Traffic") -> None:
        self.flops += other.flops
        self.hbm_read_bytes += other.hbm_read_bytes
        self.hbm_write_bytes += other.hbm_write_bytes
        self.collective_bytes += other.collective_bytes
        self.collective_count += other.collective_count
        self.reread_bytes += other.reread_bytes
        self.revisit_bytes += other.revisit_bytes
        self.pallas_calls += other.pallas_calls
        self.grid_steps += other.grid_steps
        self.approx_grids += other.approx_grids
        self.unknown_trip_loops += other.unknown_trip_loops
        self.shards = max(self.shards, other.shards)

    def to_json(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["hbm_bytes"] = self.hbm_bytes
        d["arithmetic_intensity"] = self.arithmetic_intensity
        d["inflation"] = self.inflation
        return d


def _clipped_block_bytes(bi: Tuple[int, ...], block: Tuple[int, ...],
                         shape: Tuple[int, ...], itemsize: int) -> float:
    elems = 1.0
    for i, b, s in zip(bi, block, shape):
        extent = min(b, s - i * b)
        if extent <= 0:
            return 0.0  # out-of-bounds tile: the grid pass owns that finding
        elems *= extent
    return elems * itemsize


def _pallas_traffic(gm: Any, mult: float,
                    t: Traffic) -> Tuple[float, float]:
    """Blockwise HBM traffic of one pallas_call; returns the grid-step count
    (the body multiplicity for the FLOP and DMA walk) and the bytes of the
    inputs left in HBM, which the body's DMAs move."""
    grid = tuple(int(g) for g in (getattr(gm, "grid", ()) or ()))
    steps = _prod(grid) if grid else 1.0
    mappings = [bm for bm in gm.block_mappings if bm is not None]
    out_ids = {id(bm) for _, bm in JU.output_block_mappings(gm)}
    enumerable = 0 < steps <= MAX_GRID_POINTS
    if not enumerable:
        t.approx_grids += 1
    hbm_in = 0.0
    for bm in mappings:
        if JU.in_hbm(bm.block_aval):
            if id(bm) not in out_ids:
                hbm_in += _aval_bytes(bm.array_aval) * mult
            continue
        block = JU.blocked_dims(bm)
        if block is None:
            continue  # element / bounded-slice indexing: not modeled
        shape = tuple(int(s) for s in bm.array_aval.shape)
        itemsize = int(np.dtype(bm.array_aval.dtype).itemsize)
        full_block = _prod(block) * itemsize
        arr_bytes = _prod(shape) * itemsize
        total = distinct = None
        if enumerable:
            try:
                visits: Dict[Tuple[int, ...], int] = {}
                for idx in JU.grid_points(grid):
                    bi = JU.eval_index_map(bm.index_map_jaxpr, idx)
                    visits[bi] = visits.get(bi, 0) + 1
                total, distinct = 0.0, 0.0
                for bi, cnt in visits.items():
                    cb = _clipped_block_bytes(bi, block, shape, itemsize)
                    total += cnt * cb
                    distinct += cb
            except Exception:
                total = None  # index map needs inputs we don't have
        if total is None:
            total = steps * full_block
            distinct = min(total, arr_bytes)
        extra = max(0.0, total - distinct)
        if id(bm) in out_ids:
            # every visit writes the block; a revisit additionally reads
            # the previous partial back (accumulator read-modify-write)
            t.hbm_write_bytes += total * mult
            t.hbm_read_bytes += extra * mult
            t.revisit_bytes += extra * mult
        else:
            t.hbm_read_bytes += total * mult
            t.reread_bytes += extra * mult
    return max(steps, 1.0), hbm_in


def _walk(jaxpr: Any, mult: float, t: Traffic) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "scan":
            length = float(eqn.params.get("length", 1) or 1)
            body = eqn.params.get("jaxpr")
            if body is not None:
                _walk(getattr(body, "jaxpr", body), mult * length, t)
        elif name == "while":
            t.unknown_trip_loops += 1
            body = eqn.params.get("body_jaxpr")
            if body is not None:
                _walk(getattr(body, "jaxpr", body), mult, t)
        elif name == "cond":
            best: Optional[Traffic] = None
            for br in eqn.params.get("branches", ()):
                tb = Traffic()
                _walk(getattr(br, "jaxpr", br), mult, tb)
                if best is None or (tb.flops + tb.hbm_bytes
                                    > best.flops + best.hbm_bytes):
                    best = tb
            if best is not None:
                t.merge(best)
        elif name == "shard_map":
            mesh = eqn.params.get("mesh")
            size = int(getattr(mesh, "size", 1) or 1)
            t.shards = max(t.shards, size)
            body = eqn.params.get("jaxpr")
            if body is not None:
                _walk(getattr(body, "jaxpr", body), mult * size, t)
        elif name == "pallas_call":
            t.pallas_calls += 1
            gm = eqn.params.get("grid_mapping")
            steps, hbm_in = 1.0, 0.0
            if gm is not None:
                steps, hbm_in = _pallas_traffic(gm, mult, t)
                t.grid_steps += steps * mult
            body = eqn.params.get("jaxpr")
            if body is not None:
                read0 = t.hbm_read_bytes
                _walk(getattr(body, "jaxpr", body), mult * steps, t)
                # DMA'd bytes beyond one pass over the HBM inputs: re-reads
                t.reread_bytes += max(0.0, t.hbm_read_bytes - read0 - hbm_in)
        elif name == "dma_start":
            nbytes, src_hbm, dst_hbm = JU.dma_copy(eqn)
            if src_hbm:
                t.hbm_read_bytes += nbytes * mult
            if dst_hbm:
                t.hbm_write_bytes += nbytes * mult
        elif name in JU.PSUM_PRIMITIVES or name in ("ppermute", "all_to_all",
                                                    "reduce_scatter"):
            payload = sum(_aval_bytes(v.aval) for v in eqn.invars
                          if hasattr(v, "aval"))
            t.collective_bytes += payload * mult
            t.collective_count += mult
            if name in JU.PSUM_PRIMITIVES:
                t.flops += (payload / max(1, _itemsize_of(eqn))) * mult
        elif name == "all_gather":
            payload = sum(_aval_bytes(v.aval) for v in eqn.outvars)
            t.collective_bytes += payload * mult
            t.collective_count += mult
        elif name == "dot_general":
            (lc, _rc), _batch = eqn.params["dimension_numbers"]
            lhs_shape = getattr(eqn.invars[0].aval, "shape", ())
            kdim = _prod(lhs_shape[i] for i in lc) if lc else 1.0
            t.flops += 2.0 * _out_elems(eqn) * kdim * mult
        elif name in _REDUCE_PRIMS:
            ins = [v for v in eqn.invars if hasattr(v, "aval")]
            elems = _prod(getattr(ins[0].aval, "shape", ())) if ins else 1.0
            t.flops += elems * mult
        elif name in _EW_PRIMS:
            t.flops += _out_elems(eqn) * mult
        else:
            # unknown containers (linear_call, ffi wrappers, ...): descend
            # into any sub-jaxpr so nested work is never silently dropped
            for inner in JU._iter_subjaxprs(eqn.params):
                _walk(inner, mult, t)


def _itemsize_of(eqn: Any) -> int:
    for v in eqn.invars:
        dt = getattr(getattr(v, "aval", None), "dtype", None)
        if dt is not None:
            return int(np.dtype(dt).itemsize)
    return 1


def census(closed: Any) -> Traffic:
    """Walk one closed jaxpr into a :class:`Traffic` record.  Pure trace
    math — nothing executes."""
    t = Traffic()
    jx = closed.jaxpr
    _walk(jx, 1.0, t)
    boundary_read = sum(_aval_bytes(v.aval) for v in jx.invars)
    for c in closed.consts:
        try:
            boundary_read += float(np.asarray(c).nbytes)
        except Exception:
            pass
    boundary_write = sum(_aval_bytes(v.aval) for v in jx.outvars)
    t.hbm_min_bytes = boundary_read + boundary_write
    # The boundary is the floor for *every* backend; the blockwise pallas
    # traffic replaces it only where it exceeds it (a fused XLA cell has no
    # per-block visibility, so its census IS the floor — inflation 1.0).
    t.hbm_read_bytes = max(t.hbm_read_bytes, boundary_read)
    t.hbm_write_bytes = max(t.hbm_write_bytes, boundary_write)
    return t


# --------------------------------------------------------------------------
# pass 6: roofline verdict
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Verdict:
    """Three-term static roofline of one cell on one chip."""

    chip: str
    compute_s: float
    memory_s: float
    collective_s: float
    predicted_s: float
    bound: str                      # "compute" | "memory" | "collective"
    attainable_frac: float          # statically attainable fraction of peak

    def to_json(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["predicted_ms"] = self.predicted_s * 1e3
        return d


def verdict(t: Traffic, chip: Optional[ChipSpec] = None) -> Verdict:
    """Eq.-4's e_i computed statically: the max of the three roofline terms
    is the predicted step time, its argmax the bound, and the compute term's
    share of it the attainable fraction of peak FLOP/s."""
    chip = chip if chip is not None else detect_chip()
    shards = max(1, t.shards)
    compute_s = t.flops / (chip.peak_flops * shards)
    memory_s = t.hbm_bytes / (chip.hbm_bw * shards)
    collective_s = t.collective_bytes / (chip.ici_bw * shards)
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    predicted_s = max(terms.values())
    bound = max(terms, key=terms.get)
    attainable = compute_s / predicted_s if predicted_s > 0 else 1.0
    return Verdict(chip=chip.name, compute_s=compute_s, memory_s=memory_s,
                   collective_s=collective_s, predicted_s=predicted_s,
                   bound=bound, attainable_frac=attainable)


def traffic_findings(kernel: str, backend: str, k: Any, t: Traffic,
                     variant: str = "") -> List[Finding]:
    """Pass 5 check: modeled traffic vs the compulsory floor."""
    contract = k.roofline_contract(backend) if hasattr(
        k, "roofline_contract") else {}
    limit = float(contract.get("traffic_inflation_limit",
                               DEFAULT_INFLATION_LIMIT))
    tag = f" [{variant}]" if variant else ""
    if t.inflation <= limit:
        return []
    return [Finding(
        kernel=kernel, backend=backend, pass_name="traffic",
        code="traffic-inflation",
        message=(f"modeled HBM traffic{tag} is {t.inflation:.1f}× the "
                 f"compulsory {t.hbm_min_bytes:.0f} bytes "
                 f"(re-reads {t.reread_bytes:.0f}, revisits "
                 f"{t.revisit_bytes:.0f}); limit {limit:g}× — "
                 f"declare_roofline_contract to raise it if intended"),
        detail={"inflation": t.inflation, "limit": limit,
                "hbm_bytes": t.hbm_bytes, "floor_bytes": t.hbm_min_bytes,
                "reread_bytes": t.reread_bytes,
                "revisit_bytes": t.revisit_bytes, "variant": variant})]


def roofline_findings(kernel: str, backend: str, k: Any, t: Traffic,
                      v: Verdict) -> List[Finding]:
    """Pass 6 check: verdict vs the declared bound (when one is pinned)."""
    contract = k.roofline_contract(backend) if hasattr(
        k, "roofline_contract") else {}
    declared = contract.get("bound")
    if not declared or v.bound == declared:
        return []
    return [Finding(
        kernel=kernel, backend=backend, pass_name="roofline",
        code="bound-mismatch",
        message=(f"declared {declared}-bound but the {v.chip} roofline says "
                 f"{v.bound}-bound (AI {t.arithmetic_intensity:.2f} "
                 f"FLOP/byte, predicted {v.predicted_s * 1e3:.3f} ms)"),
        detail={"declared": declared, "verdict": v.bound,
                "arithmetic_intensity": t.arithmetic_intensity,
                "predicted_ms": v.predicted_s * 1e3, "chip": v.chip})]


# --------------------------------------------------------------------------
# pass 7: drift gate (predictions vs measured time)
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Measurement:
    """One measured (kernel, backend, shape, params) → seconds sample."""

    kernel: str
    backend: str
    shape: str                      # tuning.shape_signature string
    params: Dict[str, Any]
    seconds: float
    source: str                     # "cache" | "telemetry"
    devices: int = 1
    platform: str = ""


_ARRAY_SIG = re.compile(r"^([A-Za-z_][A-Za-z_0-9]*)\[([0-9,]*)\]$")


def _np_dtype(name: str) -> Any:
    try:
        return np.dtype(name)
    except TypeError:
        import jax.numpy as jnp
        special = getattr(jnp, name, None)
        if special is not None:
            return np.dtype(special)
        raise


def parse_shape_signature(
        sig: str) -> Optional[Tuple[Tuple[Any, ...], Dict[str, Any]]]:
    """Invert ``tuning.shape_signature``: ``f32[8,64];0.5;k=int32[2]`` →
    (positional arg structs/literals, kwargs).  Array parts come back as
    ``jax.ShapeDtypeStruct`` (traceable without materializing), scalar parts
    via ``ast.literal_eval``.  Returns ``None`` when any part is neither —
    that measurement simply can't be re-traced and is skipped."""
    import jax
    args: List[Any] = []
    kwargs: Dict[str, Any] = {}
    if sig == "":
        return tuple(args), kwargs
    for part in sig.split(";"):
        name = None
        if "=" in part and not part.startswith("="):
            maybe, rest = part.split("=", 1)
            if maybe.isidentifier():
                name, part = maybe, rest
        m = _ARRAY_SIG.match(part)
        if m:
            try:
                dtype = _np_dtype(m.group(1))
            except TypeError:
                return None
            dims = tuple(int(d) for d in m.group(2).split(",") if d)
            val: Any = jax.ShapeDtypeStruct(dims, dtype)
        else:
            try:
                val = ast.literal_eval(part)
            except (ValueError, SyntaxError):
                return None
        if name is None:
            args.append(val)
        else:
            kwargs[name] = val
    return tuple(args), kwargs


def _cache_measurements(cache_path: Any,
                        pairs: Optional[set]) -> List[Measurement]:
    from pathlib import Path

    from repro.core import tuning
    path = Path(cache_path) if cache_path is not None \
        else tuning.default_cache_path()
    entries = tuning.TuningCache._read_entries(path)
    out = []
    for key_str, entry in entries.items():
        parts = key_str.split("|")
        if len(parts) != 7:
            continue
        kernel, backend, shape, _dtype, platform, code, dev = parts
        if pairs is not None and (kernel, backend) not in pairs:
            continue
        try:
            devices = int(dev.lstrip("d"))
            seconds = float(entry.get("seconds", 0.0))
        except (TypeError, ValueError):
            continue
        if not (seconds > 0.0 and math.isfinite(seconds)):
            continue
        out.append(Measurement(
            kernel=kernel, backend=backend, shape=shape,
            params=tuning.params_from_cache(entry.get("params", {}) or {}),
            seconds=seconds, source="cache", devices=devices,
            platform=platform))
    return out


def _telemetry_measurements(trace_path: str,
                            pairs: Optional[set]) -> List[Measurement]:
    from repro.core import tuning
    from repro.core.telemetry import export
    try:
        doc = export.read_events(trace_path)
    except (OSError, ValueError):
        return []
    out = []
    for ev in doc.get("events", ()):
        if ev.get("name") != "registry.time_backend.result":
            continue
        attrs = ev.get("attrs", {}) or {}
        kernel, backend = attrs.get("kernel"), attrs.get("backend")
        shape, seconds = attrs.get("shape"), attrs.get("seconds")
        if not kernel or not backend or shape is None or seconds is None:
            continue
        if pairs is not None and (kernel, backend) not in pairs:
            continue
        try:
            seconds = float(seconds)
            params = json.loads(attrs.get("params_json", "{}"))
        except (TypeError, ValueError):
            continue
        if not (seconds > 0.0 and math.isfinite(seconds)):
            continue
        out.append(Measurement(
            kernel=kernel, backend=backend, shape=str(shape),
            params=tuning.params_from_cache(params or {}), seconds=seconds,
            source="telemetry", devices=int(attrs.get("devices", 1) or 1),
            platform=str(attrs.get("platform", ""))))
    return out


def collect_measurements(cache_path: Any = None,
                         trace_path: Optional[str] = None,
                         pairs: Optional[set] = None) -> List[Measurement]:
    """Measured samples joinable to static predictions, deduped on
    (kernel, backend, shape, params) keeping the best (smallest) seconds.
    Only measurements from *this* platform at a traceable device count are
    kept — a TPU-measured entry must not calibrate a CPU prediction."""
    import jax
    platform = jax.devices()[0].platform
    devices = jax.device_count()
    ms = _cache_measurements(cache_path, pairs)
    if trace_path:
        ms += _telemetry_measurements(trace_path, pairs)
    best: Dict[Tuple[str, str, str, str], Measurement] = {}
    for m in ms:
        if m.platform and m.platform != platform:
            continue
        if m.devices > devices:
            continue
        key = (m.kernel, m.backend, m.shape,
               json.dumps(m.params, sort_keys=True, default=repr))
        if key not in best or m.seconds < best[key].seconds:
            best[key] = m
    return [best[k] for k in sorted(best)]


def predict_seconds(m: Measurement,
                    chip: Optional[ChipSpec] = None) -> Optional[float]:
    """Static predicted seconds for one measurement's exact problem, or
    ``None`` when the cell can't be re-traced here (unknown kernel, stale
    code, sharded cell on a small host, unparsable signature)."""
    from repro.core import tuning
    from repro.core.portable import registry
    try:
        k = registry.get(m.kernel)
        b = k.backends[m.backend]
    except KeyError:
        return None
    parsed = parse_shape_signature(m.shape)
    if parsed is None:
        return None
    args, sig_kwargs = parsed
    try:
        closed = JU.trace(b.fn, args, {**sig_kwargs, **m.params})
    except Exception:
        return None
    v = verdict(census(closed), chip)
    return v.predicted_s if v.predicted_s > 0 else None


def drift_gate(*, cache_path: Any = None, trace_path: Optional[str] = None,
               pairs: Optional[set] = None,
               band: Optional[float] = None,
               chip: Optional[ChipSpec] = None,
               ) -> Tuple[List[Finding], Dict[str, Any]]:
    """Pass 7: join measurements to predictions and flag outliers.

    The static model's absolute scale is host-dependent (a CPU lane runs
    everything ~1000× slower than the chip peaks predict), so the gate is
    *relative*: the median measured/predicted ratio is the host calibration
    factor, and only a cell whose own ratio exceeds ``band ×`` that median
    fires.  Fewer than :data:`MIN_DRIFT_JOINS` joins → records only, no
    findings (an empty cache keeps the CLI deterministic)."""
    band = float(band) if band is not None else DEFAULT_DRIFT_BAND
    chip = chip if chip is not None else detect_chip()
    measurements = collect_measurements(cache_path, trace_path, pairs)
    joined: List[Tuple[Measurement, float, float]] = []
    records: List[Dict[str, Any]] = []
    for m in measurements:
        p = predict_seconds(m, chip)
        rec = {"kernel": m.kernel, "backend": m.backend, "shape": m.shape,
               "params": {k: repr(v) for k, v in m.params.items()},
               "seconds": m.seconds, "source": m.source,
               "predicted_s": p}
        if p is not None:
            rec["ratio"] = m.seconds / p
            joined.append((m, p, m.seconds / p))
        records.append(rec)
    summary: Dict[str, Any] = {
        "band": band, "chip": chip.name,
        "measurements": len(measurements), "joined": len(joined),
        "min_joins": MIN_DRIFT_JOINS, "calibration": None,
        "records": records,
    }
    if len(joined) < MIN_DRIFT_JOINS:
        return [], summary
    med = statistics.median(r for _, _, r in joined)
    summary["calibration"] = med
    findings: List[Finding] = []
    for m, p, r in joined:
        rel = r / med if med > 0 else float("inf")
        for rec in records:
            if (rec["kernel"], rec["backend"], rec["shape"]) == \
                    (m.kernel, m.backend, m.shape):
                rec["relative"] = rel
        if rel <= band:
            continue
        reason = DRIFT_WAIVERS.get((m.kernel, m.backend))
        findings.append(Finding(
            kernel=m.kernel, backend=m.backend, pass_name="drift",
            code="perf-drift",
            message=(f"measured {m.seconds * 1e3:.3f} ms vs calibrated "
                     f"prediction {p * med * 1e3:.3f} ms — {rel:.1f}× left "
                     f"on the table (band {band:g}×, host calibration "
                     f"{med:.1f}×, source {m.source})"),
            waived=reason is not None, waive_reason=reason,
            detail={"seconds": m.seconds, "predicted_s": p,
                    "calibrated_predicted_s": p * med, "ratio": r,
                    "relative": rel, "band": band, "shape": m.shape,
                    "params": {k: repr(v) for k, v in m.params.items()},
                    "source": m.source}))
    return findings, summary


# --------------------------------------------------------------------------
# the model as a tuning prior
# --------------------------------------------------------------------------
def rank_points(kernel: Any, backend: str, points: Sequence[Dict[str, Any]],
                args: tuple, kwargs: dict,
                chip: Optional[ChipSpec] = None) -> List[Dict[str, Any]]:
    """Cost every tunable point statically and return them sorted by
    predicted seconds (ties keep declaration order — the same determinism
    rule as the exhaustive sweep).  Untraceable points sort last."""
    chip = chip if chip is not None else detect_chip()
    b = kernel.backend(backend)
    costed: List[Dict[str, Any]] = []
    for i, pt in enumerate(points):
        rec: Dict[str, Any] = {"params": dict(pt), "order": i}
        try:
            closed = JU.trace(b.fn, args, {**kwargs, **pt})
            t = census(closed)
            v = verdict(t, chip)
            rec.update(predicted_s=v.predicted_s, bound=v.bound,
                       hbm_bytes=t.hbm_bytes, flops=t.flops,
                       parallelism=max(t.grid_steps, 1.0) * t.shards)
        except Exception as exc:
            rec.update(predicted_s=float("inf"), error=_short(exc),
                       hbm_bytes=float("inf"), parallelism=0.0)
        costed.append(rec)
    return sorted(costed, key=lambda r: (r["predicted_s"], r["order"]))


def prune_dominated(ranked: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Drop points strictly worse on traffic AND parallelism than some other
    point — they cannot win on either roofline term, so timing them buys
    nothing.  Points that failed to trace are dropped outright."""
    live = [r for r in ranked if "error" not in r]
    keep = []
    for r in live:
        dominated = any(
            o is not r
            and o["hbm_bytes"] < r["hbm_bytes"]
            and o["parallelism"] > r["parallelism"]
            for o in live)
        if not dominated:
            keep.append(r)
    return keep
