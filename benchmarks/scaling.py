"""Weak/strong device-count scaling of the science kernels (BENCH_scaling.json).

The paper's Eq.-4 methodology compares compiler backends on one device; this
module extends the axis to *device count* via the sharded backends the
distributed subsystem registers — the oracle-arithmetic ``xla_shard``
decompositions (``repro.distributed.domain``) AND the composite
``shard_pallas`` backends (``repro.distributed.shard_pallas``: the unchanged
Pallas kernels inside ``shard_map``, interpret mode off-TPU), so the curves
compare the two portability stories shard-for-shard:

  * **strong scaling** — fixed global problem, growing shard count:
      efficiency(S) = t_1 / (S * t_S)
    against the single-device ``xla`` oracle at the same global size;
  * **weak scaling** — fixed *per-shard* problem, global size grows with S:
      efficiency(S) = t_1(base) / t_S(S * base).

stencil7 is measured once per *decomposition variant* — 1-D z slabs and 2-D
``(sz, sy)`` pencils, the ``xla_shard`` lanes each with and without
halo/compute overlap — because the decomposition shape governs the
surface-to-volume halo traffic that bounds a memory-bound stencil's
efficiency.  Every timed point consults the PR-2 tuning cache first (Eq.-4
times *best* configurations, not defaults): cached parameters are merged
under the point's forced shard settings and re-timed fresh — cached seconds
never enter a ratio — and the artifact records the tuning provenance per
point.

Hartree-Fock has no linear weak-scaling axis (work is O(N^4) in the atom
count) and records a skip reason instead of a fake curve.

On an accelerator the module measures in process on the devices it has and
never starts a child: the process holds its chips, and a child that needs
them would fail or hang.  On one chip that is the single-device point of
each kernel, with the curves skipped for the stated reason.  Under
``JAX_PLATFORMS=cpu`` with one visible device the module re-execs itself in
a subprocess with ``--xla_force_host_platform_device_count`` appended to
XLA_FLAGS (``repro.launch.hostsim`` — a user-set value is respected, never
clobbered).  The child's CSV rows are replayed into
``benchmarks.common.ROWS`` in the parent, so orchestrated runs
(``benchmarks.run``) see them like any other module's.  CPU caveat:
"devices" are threads of one host — and the ``shard_pallas`` kernels run in
interpret mode there — so efficiencies here validate the *machinery* and the
shapes of the curves, not hardware scaling.

    PYTHONPATH=src python -m benchmarks.run [--smoke] --only scaling
    PYTHONPATH=src python -m benchmarks.scaling [--smoke] [--devices 8]

Artifact schema (``repro.scaling/v3``; v2 had a single implicit backend per
kernel — v3 hoists a ``backends`` list so the ``xla_shard`` and
``shard_pallas`` curves sit side by side; v1 additionally lacked
decomposition curves and tuning provenance)::

    {"schema": "repro.scaling/v3", "platform": str, "smoke": bool,
     "num_devices": int,
     "kernels": [
       {"kernel": str, "baseline_backend": "xla",
        "single_device": {"shape": str, "seconds": float,
                          "tuning": TUNING},       # one-device runs only
        "backends": [
          {"backend": "xla_shard" | "shard_pallas", "skipped": str | null,
           "curves": [
             {"decomp": "slab" | "pencil", "overlap": bool | null,
              "strong": {"shape": str, "baseline_seconds": float,
                         "baseline_tuning": TUNING,
                         "points": [{"num_shards": int,
                                     "shard_grid": [sz, sy] | null,
                                     "seconds": float, "speedup": float,
                                     "efficiency": float,
                                     "tuning": TUNING}]},
              "weak": {"base_shape": str, "baseline_seconds": float,
                       "baseline_tuning": TUNING,
                       "points": [{"num_shards": int,
                                   "shard_grid": [sz, sy] | null,
                                   "shape": str, "seconds": float,
                                   "efficiency": float, "tuning": TUNING}]}
                      | {"skipped": str}}]}]}]}

    TUNING = {"cached": bool, "params": {...}, "search": str | null}

``overlap`` is null for ``shard_pallas`` curves: the composite has a single
structure (the halo-padded local block feeds one Pallas call — that is what
keeps it bitwise equal to the single-device kernel), so there is no
halo/compute-overlap axis to sweep.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import Any, Dict, List, Tuple

from benchmarks.common import emit, header

ARTIFACT = "BENCH_scaling.json"
SCHEMA = "repro.scaling/v3"
DEFAULT_DEVICES = 8
CSV_HEADER = "name,us_per_call,derived"


# --------------------------------------------------------------------------
# problem-size catalogue (global extents divisible by every swept shard count)
# --------------------------------------------------------------------------
def _stencil_args(nz, smoke, ny_mult=1):
    import jax.numpy as jnp
    import numpy as np
    # nx is the 128-lane width the Pallas kernel requires, so the
    # shard_pallas curves share the exact shapes the xla_shard curves time
    ny, nx = (16, 128) if smoke else (64, 128)
    u = np.random.default_rng(0).standard_normal((nz, ny * ny_mult, nx))
    return (jnp.asarray(u, jnp.float32),)


def _stream_args(n, smoke, nargs):
    import jax.numpy as jnp
    import numpy as np
    r = np.random.default_rng(0)
    return tuple(jnp.asarray(r.standard_normal(n), jnp.float32)
                 for _ in range(nargs))


def _minibude_args(nposes, smoke):
    from repro.kernels.minibude import ops as mb_ops
    natpro, natlig = (16, 4) if smoke else (64, 8)
    return mb_ops.make_deck(natpro=natpro, natlig=natlig, nposes=nposes,
                            seed=0)


def _hf_args(natoms, smoke):
    from repro.kernels.hartree_fock import ref as hf_ref
    return (hf_ref.helium_lattice(natoms), hf_ref.initial_density(natoms))


#: kernel -> (strong extent, weak per-shard extent, args factory); extents
#: are the decomposed axis (stencil z planes, stream elements, poses, atoms),
#: sized so every swept shard count divides them AND the per-shard blocks
#: admit the shard_pallas tile grids (>= 128*128 stream elements and >= 64
#: poses per shard).  stencil7 additionally declares its decomposition
#: variants per backend and a 2-D weak factory (weak pencils grow z by sz
#: and y by sy, keeping the per-shard block fixed); the xla_shard lanes
#: carry the halo/compute-overlap axis, the shard_pallas composite has a
#: single structure (overlap = None in the artifact).
def _catalogue(smoke: bool) -> Dict[str, Dict[str, Any]]:
    return {
        "stencil7": {
            "strong": 16 if smoke else 64,
            "weak": 2 if smoke else 8,
            "make": lambda n: _stencil_args(n, smoke),
            "make_grid": lambda n, sy: _stencil_args(n, smoke, ny_mult=sy),
            "curves": {
                "xla_shard": [("slab", False), ("slab", True),
                              ("pencil", False), ("pencil", True)],
                "shard_pallas": [("slab", None), ("pencil", None)],
            },
        },
        "babelstream.triad": {
            "strong": 1 << 16 if smoke else 1 << 20,
            "weak": 1 << 14 if smoke else 1 << 17,
            "make": lambda n: _stream_args(n, smoke, 2),
        },
        "babelstream.dot": {
            "strong": 1 << 16 if smoke else 1 << 20,
            "weak": 1 << 14 if smoke else 1 << 17,
            "make": lambda n: _stream_args(n, smoke, 2),
        },
        "minibude.fasten": {
            "strong": 256 if smoke else 1024,
            "weak": 64 if smoke else 256,
            "make": lambda n: _minibude_args(n, smoke),
        },
        "hartree_fock.twoel": {
            "strong": 8 if smoke else 16,
            "weak": None,  # O(N^4) work: no linear weak-scaling axis
            "weak_skip": "work is O(N^4) in atoms; no linear weak axis",
            "make": lambda n: _hf_args(n, smoke),
        },
    }


def _shape_sig(args) -> str:
    from repro.core.tuning import shape_signature
    return shape_signature(*args)


# --------------------------------------------------------------------------
# timing: every point consults the tuning cache, re-times fresh, and records
# provenance (the Eq.-4 "best configuration" rule from benchmarks/portability)
# --------------------------------------------------------------------------
def _timed_point(kernel, args, backend, cache, iters, warmup,
                 forced: Dict[str, Any]) -> Tuple[float, Dict[str, Any]]:
    """Median seconds at the cache's best params (merged *under* the forced
    shard settings — the sweep axis always wins), plus the provenance
    record.  Cached seconds are historical (another session, another load):
    only the *parameters* are reused; the timing is always fresh.

    The cache key does not encode shard settings, so an entry tuned under a
    different grid can carry tile params (``by`` / ``block_rows``) that are
    invalid for *this* point's forced grid (e.g. ``by=64`` tuned on a slab
    does not divide a pencil's 32-wide local block).  The merged point is
    therefore re-validated against the backend's declared constraint and
    falls back to the declared defaults when it fails — a dropped cache
    hit, never a crashed benchmark.
    """
    from repro.core import tuning

    hit = cache.get(tuning.make_key(kernel, *args, backend=backend))
    cached = tuning.params_from_cache(hit["params"]) if hit else {}
    merged = {**cached, **forced}
    space = kernel.tunable_space(backend)
    if cached and space is not None and space.constraint is not None:
        point = {k: merged[k] for k in space.params if k in merged}
        if set(point) == set(space.params) and not space.constraint(
                point, *args):
            hit, merged = None, dict(forced)  # incompatible hit: dropped
    provenance = {"cached": hit is not None,
                  "params": dict(merged),
                  "search": hit.get("search", "exhaustive") if hit else None}
    secs = kernel.time_backend(*args, backend=backend, iters=iters,
                               warmup=warmup, **merged)
    return secs, provenance


def _curve_label(decomp: str, overlap) -> str:
    return decomp + ("+ov" if overlap else "")


def _measure(smoke: bool, json_path: str) -> Dict[str, Any]:
    import jax

    import repro.kernels  # noqa: F401  (registers the sharded backends)
    from repro.core.portable import registry
    from repro.core.tuning import TuningCache
    from repro.distributed.domain import (SHARD_BACKEND,
                                          balanced_pencil_grid)
    from repro.distributed.shard_pallas import PALLAS_SHARD_BACKEND

    backends = (SHARD_BACKEND, PALLAS_SHARD_BACKEND)
    dc = jax.device_count()
    cache = TuningCache()
    shard_counts = [s for s in ((2, 4) if smoke else (2, 4, 8)) if s <= dc]
    iters, warmup = (1, 1) if smoke else (3, 1)
    records: List[Dict[str, Any]] = []

    for name, spec in _catalogue(smoke).items():
        kernel = registry.get(name)
        rec: Dict[str, Any] = {"kernel": name,
                               "baseline_backend": kernel.oracle,
                               "backends": []}
        records.append(rec)
        if not any(kernel.backends.get(bk) is not None
                   and kernel.backends[bk].is_available()
                   for bk in backends):
            # one device: its point is the whole measurement
            strong_args = spec["make"](spec["strong"])
            t1, t1_prov = _timed_point(kernel, strong_args, kernel.oracle,
                                       cache, iters, warmup, {})
            rec["single_device"] = {"shape": _shape_sig(strong_args),
                                    "seconds": t1, "tuning": t1_prov}
            emit(f"scaling.{name}.{kernel.oracle}.single_device", t1,
                 f"devices={dc}")
            for bk in backends:
                rec["backends"].append(
                    {"backend": bk, "curves": [],
                     "skipped": f"{bk} unavailable ({dc} device(s)): a "
                                f"scaling curve needs >= 2 devices"})
            continue

        # baselines are per kernel (shared by every backend's curves)
        strong_args = spec["make"](spec["strong"])
        t1, t1_prov = _timed_point(kernel, strong_args, kernel.oracle, cache,
                                   iters, warmup, {})
        weak_base = None
        if spec["weak"] is not None:
            weak_base = spec["make"](spec["weak"])
            t1w, t1w_prov = _timed_point(kernel, weak_base, kernel.oracle,
                                         cache, iters, warmup, {})

        for backend in backends:
            b = kernel.backends.get(backend)
            brec: Dict[str, Any] = {"backend": backend, "skipped": None,
                                    "curves": []}
            rec["backends"].append(brec)
            if b is None or not b.is_available():
                brec["skipped"] = f"{backend} unavailable ({dc} device(s))"
                continue
            curves = (spec["curves"][backend] if "curves" in spec
                      else [("slab", False if backend == SHARD_BACKEND
                             else None)])

            for decomp, overlap in curves:
                label = _curve_label(decomp, overlap)

                def _point_plan(s, args):
                    """(shard_grid, forced kwargs) for S total shards, or
                    None when this decomposition cannot use S shards here.
                    ``args`` is the *fixed* global problem (strong lane);
                    weak lanes pass ``None`` and get the shape-agnostic
                    grid — their global extents are built *from* the grid,
                    so they divide by construction."""
                    if "curves" not in spec:   # 1-D kernels: num_shards
                        return None, {"num_shards": s}
                    if decomp == "slab":
                        grid = (s, 1)
                        if args is not None and args[0].shape[0] % s:
                            grid = None
                    elif args is not None:
                        grid = balanced_pencil_grid(s, args[0].shape[0],
                                                    args[0].shape[1])
                    else:
                        grid = balanced_pencil_grid(s)
                    if grid is None:
                        return None, None
                    forced = {"decomp": decomp, "shard_grid": grid}
                    if overlap is not None:   # shard_pallas has no axis
                        forced["overlap"] = overlap
                    return grid, forced

                # strong: fixed global problem, shards grow
                points = []
                for s in shard_counts:
                    grid, forced = _point_plan(s, strong_args)
                    if forced is None:
                        continue
                    ts, prov = _timed_point(kernel, strong_args, backend,
                                            cache, iters, warmup, forced)
                    eff = t1 / (s * ts)
                    points.append({"num_shards": s,
                                   "shard_grid": list(grid) if grid else
                                   None,
                                   "seconds": ts, "speedup": t1 / ts,
                                   "efficiency": eff, "tuning": prov})
                    emit(f"scaling.{name}.{backend}.{label}.strong.s{s}",
                         ts, f"eff={eff:.3f} speedup={t1 / ts:.2f}x")
                curve: Dict[str, Any] = {
                    "decomp": decomp, "overlap": overlap,
                    "strong": {"shape": _shape_sig(strong_args),
                               "baseline_seconds": t1,
                               "baseline_tuning": t1_prov,
                               "points": points}}

                # weak: fixed per-shard problem, global grows with shards
                if spec["weak"] is None:
                    curve["weak"] = {"skipped": spec["weak_skip"]}
                else:
                    points = []
                    for s in shard_counts:
                        grid, forced = _point_plan(s, None)
                        if forced is None:
                            continue
                        if grid is not None and grid[1] > 1:
                            args_s = spec["make_grid"](
                                spec["weak"] * grid[0], grid[1])
                        else:
                            args_s = spec["make"](spec["weak"] * s)
                        ts, prov = _timed_point(kernel, args_s, backend,
                                                cache, iters, warmup,
                                                forced)
                        eff = t1w / ts
                        points.append({"num_shards": s,
                                       "shard_grid": list(grid) if grid
                                       else None,
                                       "shape": _shape_sig(args_s),
                                       "seconds": ts, "efficiency": eff,
                                       "tuning": prov})
                        emit(f"scaling.{name}.{backend}.{label}.weak.s{s}",
                             ts, f"eff={eff:.3f}")
                    curve["weak"] = {"base_shape": _shape_sig(weak_base),
                                     "baseline_seconds": t1w,
                                     "baseline_tuning": t1w_prov,
                                     "points": points}
                brec["curves"].append(curve)

    artifact = {
        "schema": SCHEMA,
        "platform": jax.devices()[0].platform,
        "smoke": smoke,
        "num_devices": dc,
        "kernels": records,
    }
    with open(json_path, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
    return artifact


# --------------------------------------------------------------------------
# entry points: re-exec under simulated devices when pinned to one
# --------------------------------------------------------------------------
def _replay_child_line(line: str) -> None:
    """Feed one line of child stdout back through ``emit`` so the parent's
    ``benchmarks.common.ROWS`` sees the child's CSV rows (the scaffold
    aggregates ROWS, not raw stdout).  Header lines are dropped (the parent
    context already printed one); anything non-CSV passes through."""
    if not line or line == CSV_HEADER:
        return
    parts = line.split(",", 2)
    if len(parts) == 3:
        try:
            us = float(parts[1])
        except ValueError:
            pass
        else:
            emit(parts[0], us / 1e6, parts[2])
            return
    print(line, flush=True)


def run(smoke: bool = False, json_path: str = ARTIFACT,
        devices: int = DEFAULT_DEVICES) -> Dict[str, Any]:
    """Measure in-process on an accelerator or when >= 2 devices are
    visible; otherwise (one CPU device) re-exec this module in a subprocess
    with the host-device-count flag appended (jax reads XLA_FLAGS once, at
    backend init — too late for *this* process).  Returns the artifact dict
    (also written to ``json_path``)."""
    import jax
    if jax.device_count() >= 2 or jax.devices()[0].platform != "cpu":
        return _measure(smoke=smoke, json_path=json_path)
    if os.environ.get("REPRO_SCALING_CHILD"):
        # we *are* the re-exec and still see one device: the user's own
        # XLA_FLAGS pins the topology — fail loudly instead of forking again
        raise RuntimeError(
            "scaling needs >= 2 devices but XLA_FLAGS pins a 1-device "
            "topology; unset --xla_force_host_platform_device_count or "
            "raise it")

    from repro.launch.hostsim import merged_xla_flags
    env = dict(os.environ)
    env["XLA_FLAGS"] = merged_xla_flags(devices, env)
    env["REPRO_SCALING_CHILD"] = "1"
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                       "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")
    # the child runs from the repo root (so `-m benchmarks.scaling`
    # resolves); absolutize the artifact path against OUR cwd first or the
    # parent would read a missing/stale file after the child succeeded
    json_path = os.path.abspath(json_path)
    cmd = [sys.executable, "-m", "benchmarks.scaling", "--json", json_path,
           "--devices", str(devices)]
    if smoke:
        cmd.append("--smoke")
    # child CSV rows are replayed line-by-line into OUR emit/ROWS (not just
    # streamed to stdout); stderr passes through untouched
    proc = subprocess.Popen(
        cmd, env=env, stdout=subprocess.PIPE, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.stdout is not None
    for line in proc.stdout:
        _replay_child_line(line.rstrip("\n"))
    if proc.wait():
        raise RuntimeError(
            f"scaling subprocess failed with exit code {proc.returncode}")
    with open(json_path) as f:
        return json.load(f)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json", default=ARTIFACT)
    ap.add_argument("--devices", type=int, default=DEFAULT_DEVICES)
    args = ap.parse_args(argv)
    # standalone runs get the scaffold's CSV header line (benchmarks.run
    # prints its own before dispatching, so run() itself must not)
    header()
    run(smoke=args.smoke, json_path=args.json, devices=args.devices)


if __name__ == "__main__":
    main()
