"""PR 9 — the static performance auditor's cost model.

Covers the jaxpr traffic census (multiplicity-aware walk, Pallas blockwise
re-reads, compulsory-floor semantics), the roofline verdict and chip
detection, shape-signature round-tripping, the three performance passes on
planted fixtures (inflated traffic, wrong declared bound, drift beyond the
band), and the model-guided tuning search (ranking, dominance pruning,
partial-search cache provenance)."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels  # noqa: F401  (registers every backend)
from repro.core import conformance, tuning
from repro.core.analysis import cost
from repro.core.analysis import jaxpr_utils as JU
from repro.core.portable import registry
from repro.core.roofline import (AMD_MI300A, CPU_HOST, NVIDIA_H100, TPU_V5E,
                                 detect_chip)


def _trace(fn, *args, **kwargs):
    return JU.trace(fn, args, kwargs)


# ---------------------------------------------------------------------------
# traffic census
# ---------------------------------------------------------------------------
def test_census_elementwise_floor():
    x = jax.ShapeDtypeStruct((128,), jnp.float32)
    t = cost.census(_trace(lambda a: a + 1.0, x))
    assert t.flops == 128
    # boundary floor: one f32[128] in, one out
    assert t.hbm_min_bytes == 2 * 128 * 4
    assert t.hbm_bytes == t.hbm_min_bytes
    assert t.inflation == 1.0
    assert t.arithmetic_intensity == pytest.approx(128 / (2 * 128 * 4))


def test_census_dot_general_flops():
    a = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((32, 16), jnp.float32)
    t = cost.census(_trace(jnp.dot, a, b))
    assert t.flops == 2 * 64 * 16 * 32


def test_census_scan_multiplicity():
    x = jax.ShapeDtypeStruct((256,), jnp.float32)

    def ten_adds(a):
        def body(c, _):
            return c + 1.0, None
        c, _ = jax.lax.scan(body, a, None, length=10)
        return c

    t = cost.census(_trace(ten_adds, x))
    assert t.flops == 10 * 256
    # the scan revisits the same carry: the boundary floor stays 2 arrays
    assert t.hbm_min_bytes == 2 * 256 * 4


def test_census_pallas_counts_halo_rereads():
    """stencil7's row tiles copy an 8-row halo group above and below each
    tile with every plane: the census must count the kernel's own DMAs and
    see traffic above the compulsory floor."""
    k = registry.get("stencil7")
    args, kwargs = conformance.CASES["stencil7"]()
    t = cost.census(_trace(k.backends["pallas_interpret"].fn, *args,
                           **kwargs, by=8))
    assert t.pallas_calls >= 1
    assert t.grid_steps >= 1
    assert t.reread_bytes > 0
    assert t.hbm_bytes > t.hbm_min_bytes
    assert t.inflation > 1.0


def test_census_collective_bytes():
    """psum under shard_map counts its payload, scaled by the mesh size."""
    from jax.sharding import Mesh, PartitionSpec as P

    ndev = jax.device_count()
    mesh = Mesh(np.array(jax.devices()), ("x",))

    def summed(a):
        return jax.shard_map(
            lambda blk: jax.lax.psum(jnp.sum(blk), "x"),
            mesh=mesh, in_specs=P("x"), out_specs=P(), check_vma=False)(a)

    x = jax.ShapeDtypeStruct((8 * ndev,), jnp.float32)
    t = cost.census(_trace(summed, x))
    assert t.shards == ndev
    assert t.collective_count == ndev      # one psum per shard program
    assert t.collective_bytes == 4.0 * ndev  # f32 scalar payload per shard


# ---------------------------------------------------------------------------
# roofline verdict + chips
# ---------------------------------------------------------------------------
def test_verdict_memory_vs_compute_bound():
    lo = cost.Traffic(flops=100.0, hbm_read_bytes=1e6, hbm_write_bytes=1e6,
                      hbm_min_bytes=2e6)
    v = cost.verdict(lo, CPU_HOST)
    assert v.bound == "memory"
    assert v.predicted_s == pytest.approx(2e6 / CPU_HOST.hbm_bw)
    assert 0.0 < v.attainable_frac < 1.0

    hi = cost.Traffic(flops=1e12, hbm_read_bytes=8.0, hbm_write_bytes=8.0,
                      hbm_min_bytes=16.0)
    v = cost.verdict(hi, CPU_HOST)
    assert v.bound == "compute"
    assert v.attainable_frac == pytest.approx(1.0)


def test_verdict_collective_bound_and_shards():
    t = cost.Traffic(flops=1.0, hbm_read_bytes=8.0, hbm_write_bytes=8.0,
                     hbm_min_bytes=16.0, collective_bytes=1e9, shards=4)
    v = cost.verdict(t, CPU_HOST)
    assert v.bound == "collective"
    # all three terms scale by the shard count
    assert v.collective_s == pytest.approx(1e9 / (CPU_HOST.ici_bw * 4))


def test_detect_chip_mapping():
    assert detect_chip("tpu", "TPU v5 lite") is TPU_V5E
    # an unknown TPU kind raises instead of borrowing v5e peaks
    for kind in ("TPU v4", "TPU v6 lite", None):
        with pytest.raises(ValueError, match="no peak table"):
            detect_chip("tpu", kind)
    assert detect_chip("gpu") is NVIDIA_H100
    assert detect_chip("cuda", "NVIDIA H100 80GB HBM3") is NVIDIA_H100
    assert detect_chip("gpu", "AMD Instinct MI300A") is AMD_MI300A
    assert detect_chip("rocm") is AMD_MI300A
    assert detect_chip("cpu") is CPU_HOST
    # the CI-lane spec keeps its ridge in the same decade as the real chips
    assert 10 < CPU_HOST.ridge < NVIDIA_H100.ridge


# ---------------------------------------------------------------------------
# shape-signature round trip
# ---------------------------------------------------------------------------
def test_parse_shape_signature_roundtrip():
    x = jnp.ones((8, 64), jnp.float32)
    k = jnp.zeros((2,), jnp.int32)
    sig = tuning.shape_signature(x, 0.5, k=k)
    parsed = cost.parse_shape_signature(sig)
    assert parsed is not None
    args, kwargs = parsed
    assert args[0].shape == (8, 64) and args[0].dtype == np.float32
    assert args[1] == 0.5
    assert kwargs["k"].shape == (2,) and kwargs["k"].dtype == np.int32


def test_parse_shape_signature_edges():
    assert cost.parse_shape_signature("") == ((), {})
    assert cost.parse_shape_signature("not a signature !") is None
    # scalar-only and kwarg-only forms
    args, kwargs = cost.parse_shape_signature("3;flag=True")
    assert args == (3,) and kwargs == {"flag": True}


# ---------------------------------------------------------------------------
# planted fixtures: each performance pass fires
# ---------------------------------------------------------------------------
class _FakeKernel:
    def __init__(self, contract):
        self._contract = contract

    def roofline_contract(self, backend):
        return dict(self._contract)


def test_planted_inflated_traffic_fires():
    """Real traced Pallas traffic against a deliberately tight limit."""
    k = registry.get("stencil7")
    args, kwargs = conformance.CASES["stencil7"]()
    t = cost.census(_trace(k.backends["pallas_interpret"].fn, *args,
                           **kwargs))
    tight = _FakeKernel({"traffic_inflation_limit": t.inflation * 0.5})
    fs = cost.traffic_findings("stencil7", "pallas_interpret", tight, t)
    assert len(fs) == 1
    assert fs[0].code == "traffic-inflation"
    assert fs[0].detail["inflation"] == pytest.approx(t.inflation)
    # raising the declared limit absorbs it
    loose = _FakeKernel({"traffic_inflation_limit": t.inflation * 2})
    assert cost.traffic_findings("stencil7", "pallas_interpret", loose,
                                 t) == []


def test_planted_wrong_bound_fires():
    x = jax.ShapeDtypeStruct((1 << 16,), jnp.float32)
    t = cost.census(_trace(lambda a: a * 2.0, x))    # AI 0.125: memory
    v = cost.verdict(t, CPU_HOST)
    assert v.bound == "memory"
    wrong = _FakeKernel({"bound": "compute"})
    fs = cost.roofline_findings("babelstream.mul", "xla", wrong, t, v)
    assert len(fs) == 1 and fs[0].code == "bound-mismatch"
    right = _FakeKernel({"bound": "memory"})
    assert cost.roofline_findings("babelstream.mul", "xla", right, t,
                                  v) == []
    undeclared = _FakeKernel({})
    assert cost.roofline_findings("babelstream.mul", "xla", undeclared, t,
                                  v) == []


def _write_drift_cache(seconds_by_key, tmp_path):
    """Write a synthetic repro.tuning/v2 cache joinable by the drift gate."""
    platform = jax.devices()[0].platform
    entries = {}
    for (k, b, s), sec in seconds_by_key.items():
        key = tuning.TuningKey(kernel=k, backend=b, shape=s, dtype="float32",
                               platform=platform, code="x", devices=1)
        entries[key.as_str()] = {"params": {}, "seconds": sec,
                                 "search": "exhaustive"}
    path = tmp_path / "drift_cache.json"
    path.write_text(json.dumps({"schema": tuning.CACHE_SCHEMA,
                                "entries": entries}))
    return path


def test_planted_drift_beyond_band_fires(tmp_path):
    """Three well-calibrated joins + one 1000x outlier: exactly the outlier
    fires, and the summary carries the host calibration median."""
    probes = [
        ("babelstream.copy", "xla", tuning.shape_signature(
            jnp.ones((1 << 14,), jnp.float32))),
        ("babelstream.mul", "xla", tuning.shape_signature(
            jnp.ones((1 << 14,), jnp.float32))),
        ("babelstream.add", "xla", tuning.shape_signature(
            jnp.ones((1 << 14,), jnp.float32),
            jnp.ones((1 << 14,), jnp.float32))),
        ("babelstream.triad", "xla", tuning.shape_signature(
            jnp.ones((1 << 14,), jnp.float32),
            jnp.ones((1 << 14,), jnp.float32))),
    ]
    chip = detect_chip()
    preds = {}
    for k, b, s in probes:
        p = cost.predict_seconds(
            cost.Measurement(kernel=k, backend=b, shape=s, params={},
                             seconds=1.0, source="cache"), chip)
        assert p is not None and p > 0
        preds[(k, b, s)] = p
    seconds = {key: 100.0 * p for key, p in preds.items()}
    outlier = probes[-1]
    seconds[outlier] *= 1000.0
    path = _write_drift_cache(seconds, tmp_path)

    findings, summary = cost.drift_gate(cache_path=path, band=8.0, chip=chip)
    assert summary["joined"] == 4
    assert summary["calibration"] == pytest.approx(100.0, rel=0.01)
    assert len(findings) == 1
    f = findings[0]
    assert (f.kernel, f.backend) == outlier[:2]
    assert f.code == "perf-drift" and not f.waived
    assert f.detail["relative"] > 8.0


def test_drift_gate_too_few_joins_is_silent(tmp_path):
    sig = tuning.shape_signature(jnp.ones((1 << 14,), jnp.float32))
    path = _write_drift_cache(
                      {("babelstream.copy", "xla", sig): 1.0}, tmp_path)
    findings, summary = cost.drift_gate(cache_path=path, band=8.0)
    assert findings == []
    assert summary["joined"] < cost.MIN_DRIFT_JOINS
    assert summary["calibration"] is None


# ---------------------------------------------------------------------------
# the model as a tuning prior
# ---------------------------------------------------------------------------
def test_rank_points_orders_by_prediction():
    k = registry.get("stencil7")
    args, kwargs = conformance.CASES["stencil7"]()
    points = k.tunable_space("pallas_interpret").valid_points(*args,
                                                              **kwargs)
    assert len(points) >= 2
    ranked = cost.rank_points(k, "pallas_interpret", points, args, kwargs)
    assert len(ranked) == len(points)
    preds = [r["predicted_s"] for r in ranked]
    assert preds == sorted(preds)
    assert all("bound" in r for r in ranked)


def test_prune_dominated():
    ranked = [
        {"params": {"a": 1}, "predicted_s": 1.0, "hbm_bytes": 100.0,
         "parallelism": 4.0, "order": 0},
        # strictly worse on both axes than the first: pruned
        {"params": {"a": 2}, "predicted_s": 2.0, "hbm_bytes": 200.0,
         "parallelism": 2.0, "order": 1},
        # worse traffic but better parallelism: kept
        {"params": {"a": 3}, "predicted_s": 3.0, "hbm_bytes": 300.0,
         "parallelism": 8.0, "order": 2},
        # untraceable: dropped outright
        {"params": {"a": 4}, "predicted_s": float("inf"), "error": "boom",
         "hbm_bytes": float("inf"), "parallelism": 0.0, "order": 3},
    ]
    keep = cost.prune_dominated(ranked)
    kept = [r["params"]["a"] for r in keep]
    assert kept == [1, 3]


def test_model_search_provenance_and_no_exhaustive_serving(tmp_path):
    """tune(search='model') caches provenance 'model'; the entry is never
    served to an exhaustive caller; the exhaustive result replaces it."""
    k = registry.get("stencil7")
    args, kwargs = conformance.CASES["stencil7"]()
    cache = tuning.TuningCache(path=str(tmp_path / "model.json"))

    tr = tuning.tune(k, *args, backend="pallas_interpret", cache=cache,
                     iters=1, warmup=0, search="model", **kwargs)
    assert tr.skipped is None and not tr.cached
    assert tr.search == "model"
    key = tuning.make_key(k, *args, backend="pallas_interpret", **kwargs)
    entry = cache.get(key)
    assert entry is not None and entry["search"] == "model"

    # a model hit serves a second model request...
    again = tuning.tune(k, *args, backend="pallas_interpret", cache=cache,
                        iters=1, warmup=0, search="model", **kwargs)
    assert again.cached
    # ...but never an exhaustive one — that re-sweeps and overwrites
    full = tuning.tune(k, *args, backend="pallas_interpret", cache=cache,
                       iters=1, warmup=0, search="exhaustive", **kwargs)
    assert not full.cached
    assert cache.get(key)["search"] == "exhaustive"


def test_model_search_times_at_most_top_k(tmp_path):
    k = registry.get("stencil7")
    args, kwargs = conformance.CASES["stencil7"]()
    cache = tuning.TuningCache(path=str(tmp_path / "budget.json"))
    tr = tuning.tune(k, *args, backend="pallas_interpret", cache=cache,
                     iters=1, warmup=0, search="model", budget=2, **kwargs)
    assert tr.skipped is None
    assert len(tr.swept) <= 2
