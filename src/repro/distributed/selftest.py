"""Multi-device self-test for the domain-decomposition subsystem.

    PYTHONPATH=src python -m repro.distributed.selftest [--devices 8]
                                                        [--only BATTERY,...]

Runs on simulated host devices (``hostsim`` appends
``--xla_force_host_platform_device_count`` before jax initializes — an
XLA_FLAGS value you already exported is respected).  The test suite invokes
this module in a subprocess (``tests/test_distributed_domain.py``) because
pytest's process has already pinned jax to the 1-device topology: the full
battery set runs in the slow pytest lane, while ``--only smoke`` is a
seconds-scale single battery the tier-1 lane keeps.

Checks, each against the single-device ``xla`` oracle:
  * stencil7 slab decomposition is **bitwise identical** at 2/4/8 shards —
    and so are the 2-D pencil grids ((2,2)/(4,2)/(2,4)) and the
    halo/compute-overlap variants of both decompositions, including the
    one-plane-per-shard edge case of the boundary mask;
  * the halo exchange round-trips shard-boundary planes (zeros at the open
    ends), wraps periodically with ``wrap=True``, and moves ``halo``-thick
    multi-plane slabs;
  * BabelStream copy/mul/add/triad are bitwise identical; ``dot`` matches
    within fp32 reduction tolerance (psum changes the summation order);
    scalar ops trace the scalar (two scalars share one compiled program);
  * miniBUDE pose-parallel energies are bitwise identical;
  * Hartree-Fock psum-accumulated Fock matrices match within oracle
    tolerance;
  * divisibility / device-count constraints raise ``ValueError`` and the
    autotuner sweeps the decomp/shard-grid/overlap axes through the
    unchanged registry path (tuple-valued tunables round-trip the cache);
  * the ``shard_pallas`` composites (shard_map around the unchanged Pallas
    kernels, interpret mode off-TPU) are **bitwise identical to the
    single-device Pallas backend** for stencil7 (slab, pencil, every ``by``
    tile, one plane per shard) and the elementwise streams / miniBUDE;
    ``dot`` and Hartree-Fock match within psum-reduction tolerances; the
    composite tile x shard tunable spaces sweep through ``tune()``;
  * the registry-wide differential conformance matrix
    (``repro.core.conformance``) passes for every backend available here —
    on 8 forced host devices that is everything except compiled-TPU
    ``pallas``.
"""

from __future__ import annotations

import argparse
import sys


def _check_stencil(np, jnp, get_kernel, shard_counts):
    k = get_kernel("stencil7")
    u = jnp.asarray(np.random.default_rng(0).standard_normal((16, 16, 32)),
                    jnp.float32)
    want = np.asarray(k(u, backend="xla"))
    for s in shard_counts:
        got = np.asarray(k(u, backend="xla_shard", num_shards=s))
        assert np.array_equal(want, got), \
            f"stencil7 xla_shard num_shards={s} is not bitwise equal"
    # default shard-count resolution also matches
    got = np.asarray(k(u, backend="xla_shard"))
    assert np.array_equal(want, got), "stencil7 auto num_shards mismatch"
    print(f"  stencil7: bitwise equal at shards {shard_counts} + auto")


def _check_stencil_pencil(np, jnp, get_kernel, n_devices):
    if n_devices < 4:
        print("  stencil7: pencil checks skipped (< 4 devices)")
        return
    k = get_kernel("stencil7")
    u = jnp.asarray(np.random.default_rng(3).standard_normal((16, 16, 32)),
                    jnp.float32)
    want = np.asarray(k(u, backend="xla"))
    grids = [g for g in ((2, 2), (4, 2), (2, 4))
             if g[0] * g[1] <= n_devices]
    for grid in grids:
        for overlap in (False, True):
            got = np.asarray(k(u, backend="xla_shard", decomp="pencil",
                               shard_grid=grid, overlap=overlap))
            assert np.array_equal(want, got), \
                f"stencil7 pencil grid={grid} overlap={overlap} mismatch"
    # slab overlap variant, and auto pencil-grid resolution
    for s in (2, 4):
        got = np.asarray(k(u, backend="xla_shard", decomp="slab",
                           shard_grid=(s, 1), overlap=True))
        assert np.array_equal(want, got), f"stencil7 slab+overlap s={s}"
    got = np.asarray(k(u, backend="xla_shard", decomp="pencil"))
    assert np.array_equal(want, got), "stencil7 auto pencil grid mismatch"
    print(f"  stencil7: pencil grids {grids} and overlap variants "
          f"bitwise equal")


def _check_stencil_one_plane_per_shard(np, jnp, get_kernel, n_devices):
    """nz == num_shards: each shard owns exactly one plane, so its first
    and last local plane coincide and the boundary mask must AND the two
    edge conditions rather than overwrite one with the other."""
    k = get_kernel("stencil7")
    s = min(8, n_devices)
    u = jnp.asarray(np.random.default_rng(4).standard_normal((s, 8, 16)),
                    jnp.float32)
    want = np.asarray(k(u, backend="xla"))
    for overlap in (False, True):
        got = np.asarray(k(u, backend="xla_shard", num_shards=s,
                           overlap=overlap))
        assert np.array_equal(want, got), \
            f"stencil7 one-plane-per-shard overlap={overlap} mismatch"
    print(f"  stencil7: one plane per shard ({s} shards) bitwise equal")


def _check_halo_exchange(np, jnp, n_shards):
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.distributed import collectives
    from repro.distributed.domain import AXIS, shard_mesh

    rows = 2 * n_shards
    x = jnp.arange(rows * 3, dtype=jnp.float32).reshape(rows, 3)

    def local(u):
        lo, hi = collectives.halo_exchange(u, AXIS, n_shards, axis=0)
        return jnp.concatenate([lo, hi], axis=0)

    halos = np.asarray(jax.jit(jax.shard_map(
        local, mesh=shard_mesh(n_shards), in_specs=P(AXIS),
        out_specs=P(AXIS), check_vma=False))(x))
    xs = np.asarray(x).reshape(n_shards, 2, 3)
    halos = halos.reshape(n_shards, 2, 3)
    for i in range(n_shards):
        want_lo = xs[i - 1][-1] if i > 0 else np.zeros(3)
        want_hi = xs[i + 1][0] if i < n_shards - 1 else np.zeros(3)
        assert np.array_equal(halos[i][0], want_lo), f"halo from_prev {i}"
        assert np.array_equal(halos[i][1], want_hi), f"halo from_next {i}"
    print(f"  halo_exchange: round-trips at {n_shards} shards, "
          f"zero at the open ends")


def _check_halo_wrap_and_multiplane(np, jnp, n_shards):
    """The wrap=True periodic ring and halo>1 multi-plane slabs."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.distributed import collectives
    from repro.distributed.domain import AXIS, shard_mesh

    planes = 3
    rows = planes * n_shards
    x = jnp.arange(rows * 2, dtype=jnp.float32).reshape(rows, 2)
    xs = np.asarray(x).reshape(n_shards, planes, 2)

    def run(local):
        return np.asarray(jax.jit(jax.shard_map(
            local, mesh=shard_mesh(n_shards), in_specs=P(AXIS),
            out_specs=P(AXIS), check_vma=False))(x))

    # periodic shift: every shard receives its predecessor's block, the
    # first shard wrapping around to the last
    shifted = run(lambda u: collectives.shift(u, AXIS, n_shards, offset=1,
                                              wrap=True))
    shifted = shifted.reshape(n_shards, planes, 2)
    for i in range(n_shards):
        assert np.array_equal(shifted[i], xs[(i - 1) % n_shards]), \
            f"periodic shift shard {i}"

    # halo=2 multi-plane exchange, open ends: the previous shard's trailing
    # two planes / the next shard's leading two planes, zeros at the edges
    halos = run(lambda u: jnp.concatenate(
        collectives.halo_exchange(u, AXIS, n_shards, axis=0, halo=2),
        axis=0)).reshape(n_shards, 4, 2)
    for i in range(n_shards):
        want_lo = xs[i - 1][-2:] if i > 0 else np.zeros((2, 2))
        want_hi = xs[i + 1][:2] if i < n_shards - 1 else np.zeros((2, 2))
        assert np.array_equal(halos[i][:2], want_lo), f"halo=2 prev {i}"
        assert np.array_equal(halos[i][2:], want_hi), f"halo=2 next {i}"

    # halo=2, periodic: the edge shards exchange with each other
    halos = run(lambda u: jnp.concatenate(
        collectives.halo_exchange(u, AXIS, n_shards, axis=0, halo=2,
                                  wrap=True), axis=0))
    halos = halos.reshape(n_shards, 4, 2)
    for i in range(n_shards):
        assert np.array_equal(halos[i][:2], xs[(i - 1) % n_shards][-2:]), \
            f"wrap halo=2 prev {i}"
        assert np.array_equal(halos[i][2:], xs[(i + 1) % n_shards][:2]), \
            f"wrap halo=2 next {i}"
    print(f"  halo_exchange: wrap=True periodic ring and halo=2 "
          f"multi-plane slabs at {n_shards} shards")


def _check_babelstream(np, jnp, get_kernel, shard_counts):
    r = np.random.default_rng(1)
    n = 1 << 12
    a = jnp.asarray(r.standard_normal(n), jnp.float32)
    b = jnp.asarray(r.standard_normal(n), jnp.float32)
    cases = {"copy": (a,), "mul": (a,), "add": (a, b), "triad": (a, b),
             "dot": (a, b)}
    for op, args in cases.items():
        k = get_kernel(f"babelstream.{op}")
        want = np.asarray(k(*args, backend="xla"))
        for s in shard_counts:
            got = np.asarray(k(*args, backend="xla_shard", num_shards=s))
            if op == "dot":
                np.testing.assert_allclose(got, want, rtol=1e-6)
            else:
                assert np.array_equal(want, got), \
                    f"babelstream.{op} num_shards={s} not bitwise equal"
    print(f"  babelstream: copy/mul/add/triad bitwise equal, dot within "
          f"1e-6, shards {shard_counts}")

    # the scalar is traced, not baked into the compile cache: two distinct
    # scalars must share one jitted program per (op, num_shards)
    from repro.distributed import domain
    k = get_kernel("babelstream.triad")
    want = np.asarray(k(a, b, backend="xla", scalar=2.5))
    got = np.asarray(k(a, b, backend="xla_shard", num_shards=2, scalar=2.5))
    assert np.array_equal(want, got), "triad scalar=2.5 not bitwise equal"
    size = domain._stream_sharded.cache_info().currsize
    k(a, b, backend="xla_shard", num_shards=2, scalar=7.25)
    assert domain._stream_sharded.cache_info().currsize == size, \
        "a new scalar recompiled the sharded stream kernel"
    print("  babelstream: scalar is traced (one compile serves all values)")


def _check_minibude(np, jnp, get_kernel, shard_counts):
    from repro.kernels.minibude import ops as mb_ops
    deck = mb_ops.make_deck(natpro=16, natlig=4, nposes=128, seed=0)
    k = get_kernel("minibude.fasten")
    want = np.asarray(k(*deck, backend="xla"))
    for s in shard_counts:
        got = np.asarray(k(*deck, backend="xla_shard", num_shards=s))
        assert np.array_equal(want, got), \
            f"minibude.fasten num_shards={s} not bitwise equal"
    print(f"  minibude: pose-parallel bitwise equal at shards "
          f"{shard_counts}")


def _check_hartree_fock(np, jnp, get_kernel, shard_counts):
    from repro.kernels.hartree_fock import ref as hf_ref
    pos, dens = hf_ref.helium_lattice(8), hf_ref.initial_density(8)
    k = get_kernel("hartree_fock.twoel")
    want = np.asarray(k(pos, dens, backend="xla"))
    for s in shard_counts:
        got = np.asarray(k(pos, dens, backend="xla_shard", num_shards=s))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    print(f"  hartree_fock: psum Fock within oracle tolerance at shards "
          f"{shard_counts}")


def _check_constraints(np, jnp, get_kernel):
    import tempfile

    from repro.core import tuning
    from repro.distributed.domain import (resolve_num_shards,
                                          resolve_shard_grid)

    for bad in ({"extent": 15, "num_shards": 2},    # indivisible
                {"extent": 16, "num_shards": 1},    # < 2
                {"extent": 16, "num_shards": 1024}):  # > devices
        try:
            resolve_num_shards(bad["extent"], bad["num_shards"])
        except ValueError:
            pass
        else:
            raise AssertionError(f"resolve_num_shards accepted {bad}")

    for kw in ({"decomp": "pencil", "shard_grid": (2, 1)},   # not 2-D
                {"decomp": "slab", "shard_grid": (2, 2)},    # slab has sy=1
                {"decomp": "pencil", "shard_grid": (2, 3)},  # 8 % 3 != 0
                {"decomp": "pencil", "shard_grid": (64, 64)},  # > devices
                {"decomp": "block"}):                        # unknown
        try:
            resolve_shard_grid(16, 8, **kw)
        except ValueError:
            pass
        else:
            raise AssertionError(f"resolve_shard_grid accepted {kw}")

    # the declared tunable grid only admits valid (divisible, in-budget)
    # points, and tune() sweeps the decomp/shard-grid/overlap axes through
    # the unchanged registry path
    k = get_kernel("stencil7")
    u = jnp.asarray(np.random.default_rng(2).standard_normal((4, 8, 16)),
                    jnp.float32)
    pts = k.tunable_space("xla_shard").valid_points(u)
    grids = sorted({(p["decomp"], p["shard_grid"]) for p in pts})
    assert grids == [("pencil", (2, 2)), ("pencil", (2, 4)),
                     ("pencil", (4, 2)), ("slab", (2, 1)),
                     ("slab", (4, 1))], grids
    assert all({True, False} == {q["overlap"] for q in pts
                                 if (q["decomp"], q["shard_grid"]) == g}
               for g in grids)
    with tempfile.TemporaryDirectory() as td:
        cache = tuning.TuningCache(path=td + "/tuning.json")
        r = tuning.tune(k, u, backend="xla_shard", cache=cache, iters=1,
                        warmup=0)
        assert r.skipped is None and not r.cached, r
        assert r.params["decomp"] in ("slab", "pencil"), r
        # tuple-valued shard_grid round-trips the JSON cache as a tuple
        r2 = tuning.tune(k, u, backend="xla_shard", cache=cache, iters=1,
                         warmup=0)
        assert r2.cached and r2.params == r.params, (r, r2)
        assert isinstance(r2.params["shard_grid"], tuple), r2
    print("  constraints: invalid shard counts/grids rejected, tunable "
          "grid filtered, tune() sweeps decomp/shard_grid/overlap")


def _check_shard_pallas_stencil(np, jnp, get_kernel, n_devices):
    """The composite backend must be bitwise identical to the single-device
    Pallas backend — sharding must not change the kernel's output — across
    slab/pencil grids, every admissible ``by`` tile, and the
    one-plane-per-shard edge (where the whole local block is halo)."""
    k = get_kernel("stencil7")
    # ny=32 keeps every pencil grid's local block >= the smallest declared
    # by tile ((2,4) leaves an 8-wide block)
    u = jnp.asarray(np.random.default_rng(5).standard_normal((16, 32, 128)),
                    jnp.float32)
    want_pi = np.asarray(k(u, backend="pallas_interpret", by=16))
    want_x = np.asarray(k(u, backend="xla"))
    np.testing.assert_allclose(want_pi, want_x, rtol=1e-5, atol=1e-5)
    cases = [{"num_shards": s} for s in (2, 4, 8) if s <= n_devices]
    cases += [{"num_shards": min(4, n_devices), "by": 8}, {}]
    if n_devices >= 4:
        cases += [{"decomp": "pencil", "shard_grid": g}
                  for g in ((2, 2), (4, 2), (2, 4))
                  if g[0] * g[1] <= n_devices]
    for kw in cases:
        got = np.asarray(k(u, backend="shard_pallas", **kw))
        assert np.array_equal(want_pi, got), \
            f"stencil7 shard_pallas {kw} != single-device pallas"
    s = min(8, n_devices)
    u1 = jnp.asarray(np.random.default_rng(6).standard_normal((s, 16, 128)),
                     jnp.float32)
    want1 = np.asarray(k(u1, backend="pallas_interpret", by=16))
    got1 = np.asarray(k(u1, backend="shard_pallas", num_shards=s))
    assert np.array_equal(want1, got1), \
        "stencil7 shard_pallas one-plane-per-shard mismatch"
    print(f"  shard_pallas stencil7: bitwise equal to single-device pallas "
          f"({len(cases)} grids incl. pencil + one plane per shard)")


def _check_shard_pallas_streams(np, jnp, get_kernel, n_devices):
    r = np.random.default_rng(7)
    n = 1 << 17
    a = jnp.asarray(r.standard_normal(n), jnp.float32)
    b = jnp.asarray(r.standard_normal(n), jnp.float32)
    shard_counts = [s for s in (2, 8) if s <= n_devices]
    cases = {"copy": ((a,), {}), "mul": ((a,), {"scalar": 2.5}),
             "add": ((a, b), {}), "triad": ((a, b), {"scalar": 2.5})}
    for op, (args, kw) in cases.items():
        k = get_kernel(f"babelstream.{op}")
        want = np.asarray(k(*args, backend="pallas_interpret", **kw))
        for s in shard_counts:
            got = np.asarray(k(*args, backend="shard_pallas", num_shards=s,
                               **kw))
            assert np.array_equal(want, got), \
                f"babelstream.{op} shard_pallas num_shards={s} mismatch"
    k = get_kernel("babelstream.dot")
    want = np.asarray(k(a, b, backend="pallas_interpret"))
    for s in shard_counts:
        got = np.asarray(k(a, b, backend="shard_pallas", num_shards=s))
        np.testing.assert_allclose(got, want, rtol=1e-5)
    print(f"  shard_pallas babelstream: elementwise bitwise equal to "
          f"single-device pallas, dot within 1e-5, shards {shard_counts}")


def _check_shard_pallas_minibude(np, jnp, get_kernel, n_devices):
    from repro.kernels.minibude import ops as mb_ops
    deck = mb_ops.make_deck(natpro=16, natlig=4, nposes=512, seed=0)
    k = get_kernel("minibude.fasten")
    want = np.asarray(k(*deck, backend="pallas_interpret"))
    shard_counts = [s for s in (2, 4) if s <= n_devices]
    for s in shard_counts:
        got = np.asarray(k(*deck, backend="shard_pallas", num_shards=s))
        assert np.array_equal(want, got), \
            f"minibude shard_pallas num_shards={s} mismatch"
    print(f"  shard_pallas minibude: bitwise equal to single-device pallas "
          f"at shards {shard_counts}")


def _check_shard_pallas_hartree_fock(np, jnp, get_kernel, n_devices):
    from repro.kernels.hartree_fock import ref as hf_ref
    pos, dens = hf_ref.helium_lattice(8), hf_ref.initial_density(8)
    k = get_kernel("hartree_fock.twoel")
    want = np.asarray(k(pos, dens, backend="xla"))
    shard_counts = [s for s in (2, 4, 8) if s <= n_devices]
    for s in shard_counts:
        got = np.asarray(k(pos, dens, backend="shard_pallas", num_shards=s))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    print(f"  shard_pallas hartree_fock: l-slab Pallas psum within oracle "
          f"tolerance at shards {shard_counts}")


def _check_shard_pallas_tuning(np, jnp, get_kernel):
    """The composite tile x shard space sweeps through the unchanged
    registry/tuning path (48-point grid -> budgeted coordinate descent)."""
    import tempfile

    from repro.core import tuning

    k = get_kernel("stencil7")
    u = jnp.asarray(np.random.default_rng(8).standard_normal((8, 16, 128)),
                    jnp.float32)
    pts = k.tunable_space("shard_pallas").valid_points(u)
    assert all((u.shape[1] // p["shard_grid"][1]) % p["by"] == 0
               for p in pts), pts
    assert {p["decomp"] for p in pts} == {"slab", "pencil"}, pts
    with tempfile.TemporaryDirectory() as td:
        cache = tuning.TuningCache(path=td + "/tuning.json")
        r = tuning.tune(k, u, backend="shard_pallas", cache=cache, iters=1,
                        warmup=0, budget=4)
        assert r.skipped is None and not r.cached, r
        assert {"decomp", "shard_grid", "by"} <= set(r.params), r
        r2 = tuning.tune(k, u, backend="shard_pallas", cache=cache, iters=1,
                         warmup=0, budget=4)
        assert r2.cached and r2.params == r.params, (r, r2)
        assert isinstance(r2.params["shard_grid"], tuple), r2
    print("  shard_pallas tuning: composite tile x shard space sweeps and "
          "round-trips the cache")


def _check_conformance(np, jnp, get_kernel):
    """The registry-wide differential matrix, on this multi-device host:
    every (kernel, backend) cell either validates against its oracle or
    skips with a ``BackendUnavailableError`` reason — here only the
    compiled-TPU ``pallas`` backends may skip."""
    from repro.core import conformance
    from repro.core.portable import BackendUnavailableError

    ran, skipped = [], []
    for name, backend in conformance.conformance_pairs():
        try:
            conformance.check_backend(name, backend)
            ran.append((name, backend))
        except BackendUnavailableError:
            skipped.append((name, backend))
    assert all(b == "pallas" for _, b in skipped), skipped
    for b in ("xla_shard", "shard_pallas"):
        assert any(x[1] == b for x in ran), f"{b} never ran: {ran}"
    print(f"  conformance: {len(ran)} registry cells validated "
          f"({len(skipped)} TPU-only skips)")


def _check_smoke(np, jnp, get_kernel, n_devices):
    """Seconds-scale single battery for the tier-1 lane: one sharded-oracle
    and one sharded-Pallas stencil, bitwise, at 2 shards."""
    k = get_kernel("stencil7")
    u = jnp.asarray(np.random.default_rng(9).standard_normal((4, 8, 128)),
                    jnp.float32)
    want_x = np.asarray(k(u, backend="xla"))
    got = np.asarray(k(u, backend="xla_shard", num_shards=2))
    assert np.array_equal(want_x, got), "smoke: xla_shard mismatch"
    want_pi = np.asarray(k(u, backend="pallas_interpret", by=8))
    got = np.asarray(k(u, backend="shard_pallas", num_shards=2))
    assert np.array_equal(want_pi, got), "smoke: shard_pallas mismatch"
    np.testing.assert_allclose(got, want_x, rtol=1e-5, atol=1e-5)
    print("  smoke: xla_shard + shard_pallas stencil bitwise at 2 shards")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--only", default=None, metavar="BATTERY[,BATTERY...]",
                    help="run only the named batteries (default: every "
                         "battery except the tier-1 'smoke' shortcut)")
    args = ap.parse_args(argv)

    # must precede the first jax device query
    from repro.launch.hostsim import ensure_host_device_count
    ensure_host_device_count(args.devices)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.kernels  # noqa: F401  (registers the sharded backends)
    from repro.core.portable import get_kernel

    n = jax.device_count()
    if n < 2:
        print(f"selftest needs >= 2 devices, got {n} (is XLA_FLAGS already "
              f"forcing a 1-device topology?)", file=sys.stderr)
        return 2
    shard_counts = [s for s in (2, 4, 8) if s <= n]

    batteries = {
        "stencil": lambda: _check_stencil(np, jnp, get_kernel, shard_counts),
        "stencil_pencil": lambda: _check_stencil_pencil(np, jnp, get_kernel,
                                                        n),
        "stencil_one_plane": lambda: _check_stencil_one_plane_per_shard(
            np, jnp, get_kernel, n),
        "halo": lambda: _check_halo_exchange(np, jnp, min(4, n)),
        "halo_wrap": lambda: _check_halo_wrap_and_multiplane(np, jnp,
                                                             min(4, n)),
        "babelstream": lambda: _check_babelstream(np, jnp, get_kernel,
                                                  shard_counts),
        "minibude": lambda: _check_minibude(np, jnp, get_kernel,
                                            shard_counts),
        "hartree_fock": lambda: _check_hartree_fock(np, jnp, get_kernel,
                                                    shard_counts),
        "constraints": lambda: _check_constraints(np, jnp, get_kernel),
        "shard_pallas_stencil": lambda: _check_shard_pallas_stencil(
            np, jnp, get_kernel, n),
        "shard_pallas_streams": lambda: _check_shard_pallas_streams(
            np, jnp, get_kernel, n),
        "shard_pallas_minibude": lambda: _check_shard_pallas_minibude(
            np, jnp, get_kernel, n),
        "shard_pallas_hf": lambda: _check_shard_pallas_hartree_fock(
            np, jnp, get_kernel, n),
        "shard_pallas_tuning": lambda: _check_shard_pallas_tuning(
            np, jnp, get_kernel),
        "conformance": lambda: _check_conformance(np, jnp, get_kernel),
        "smoke": lambda: _check_smoke(np, jnp, get_kernel, n),
    }
    if args.only is None:
        selected = [b for b in batteries if b != "smoke"]
    else:
        selected = [b.strip() for b in args.only.split(",") if b.strip()]
        unknown = [b for b in selected if b not in batteries]
        if unknown:
            print(f"unknown batteries {unknown}; known: "
                  f"{sorted(batteries)}", file=sys.stderr)
            return 2

    print(f"selftest on {n} simulated {jax.devices()[0].platform} devices, "
          f"shard counts {shard_counts}, batteries {selected}")
    for name in selected:
        batteries[name]()
    print(f"selftest ok ({len(selected)} batteries)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
