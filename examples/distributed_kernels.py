"""The science kernels multi-device: domain decomposition through the registry.

    PYTHONPATH=src python examples/distributed_kernels.py [--devices 8]

Simulates a multi-device host (the flag must be set before jax initializes,
which is why this script — not the library — does it), then runs each
science family on its single-device oracle and on the sharded backends the
distributed subsystem registered, checking every distributed result against
the oracle:

  * ``xla_shard`` — the oracle arithmetic under shard_map:
      - stencil7     1-D z slabs AND 2-D (sz, sy) pencils + per-axis
                     ppermute halo exchange, each with the double-buffered
                     halo/compute-overlap variant (interior computes while
                     halos are in flight)
      - babelstream  block-partitioned triad (elementwise) + psum dot
      - minibude     pose-parallel energies
      - hartree_fock l-slab quartet contributions accumulated with psum
  * ``shard_pallas`` — the *unchanged Pallas kernels* under shard_map
    (interpret mode off-TPU), the shard grid composing with each family's
    tile tunables (stencil ``by``, stream ``block_rows``, pose/i tiles);
    the stencil/stream/pose results are additionally bitwise identical to
    the single-device Pallas backend — sharding does not change the
    kernel's output.

CPU caveat: the "devices" are threads of one host (and shard_pallas runs
interpret-mode kernels there), so the timings prove the decomposition
machinery, not hardware scaling — see benchmarks/scaling.py for the
weak/strong curves and BENCH_scaling.json (per-backend since v3).
"""

import argparse

from repro.launch.hostsim import ensure_host_device_count

_args = argparse.ArgumentParser()
_args.add_argument("--devices", type=int, default=8)
ARGS = _args.parse_args()
ensure_host_device_count(ARGS.devices)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.kernels  # noqa: E402,F401  (registers xla_shard backends)
from repro.core.portable import get_kernel  # noqa: E402
from repro.kernels.hartree_fock import ref as hf_ref  # noqa: E402
from repro.kernels.minibude import ops as mb_ops  # noqa: E402
from repro.launch.compile_cache import (  # noqa: E402
    configure_compile_cache)


def show(name, kernel, args, exact=True, label=None, backend="xla_shard",
         against="xla", **shard_kw):
    t_x = kernel.time_backend(*args, backend="xla", iters=3)
    t_s = kernel.time_backend(*args, backend=backend, iters=3, **shard_kw)
    want = np.asarray(kernel(*args, backend=against))
    got = np.asarray(kernel(*args, backend=backend, **shard_kw))
    if exact:
        assert np.array_equal(want, got), \
            f"{name}: {backend} != {against}"
        match = f"bitwise vs {against}"
    else:
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        match = f"~1e-4 vs {against}"
    label = label or ",".join(f"{k}={v}" for k, v in shard_kw.items())
    print(f"{name:18s} xla {t_x * 1e3:8.2f}ms   {backend}[{label}] "
          f"{t_s * 1e3:8.2f}ms   match: {match}")


def main() -> None:
    configure_compile_cache()
    n = jax.device_count()
    if n < 2:
        raise SystemExit(
            f"need >= 2 devices, got {n}: XLA_FLAGS already pinned a "
            f"1-device topology before this script could append the flag")
    shards = min(4, n)
    print(f"{n} simulated {jax.devices()[0].platform} devices; "
          f"running every family at num_shards={shards}\n")
    rng = np.random.default_rng(0)

    # (ny, nx) = (64, 128): the Pallas lane width and default y-tile, so
    # the same array feeds both sharded backends AND the single-device
    # Pallas baseline at its defaults
    u = jnp.asarray(rng.standard_normal((32, 64, 128)), jnp.float32)
    s7 = get_kernel("stencil7")
    show("stencil7", s7, (u,), label=f"slab {shards}x1",
         num_shards=shards)
    show("stencil7", s7, (u,), label=f"slab {shards}x1 +overlap",
         num_shards=shards, overlap=True)
    if n >= 4:
        show("stencil7", s7, (u,), label="pencil 2x2", decomp="pencil",
             shard_grid=(2, 2))
        show("stencil7", s7, (u,), label="pencil 2x2 +overlap",
             decomp="pencil", shard_grid=(2, 2), overlap=True)

    a = jnp.asarray(rng.standard_normal(1 << 16), jnp.float32)
    b = jnp.asarray(rng.standard_normal(1 << 16), jnp.float32)
    show("babelstream.triad", get_kernel("babelstream.triad"), (a, b),
         num_shards=shards)
    show("babelstream.dot", get_kernel("babelstream.dot"), (a, b),
         exact=False, num_shards=shards)

    deck = mb_ops.make_deck(natpro=32, natlig=4, nposes=256, seed=0)
    show("minibude.fasten", get_kernel("minibude.fasten"), deck,
         num_shards=shards)

    pos, dens = hf_ref.helium_lattice(8), hf_ref.initial_density(8)
    show("hartree_fock", get_kernel("hartree_fock.twoel"), (pos, dens),
         exact=False, num_shards=shards)

    # the shard_pallas composites: the SAME Pallas kernel source, sharded
    # (interpret mode on these simulated host devices) — bitwise against
    # the single-device Pallas backend where the math is reduction-free
    print()
    show("stencil7", s7, (u,), label=f"slab {shards}x1",
         backend="shard_pallas", against="pallas_interpret",
         num_shards=shards)
    if n >= 4:
        show("stencil7", s7, (u,), label="pencil 2x2",
             backend="shard_pallas", against="pallas_interpret",
             decomp="pencil", shard_grid=(2, 2))
    show("babelstream.triad", get_kernel("babelstream.triad"), (a, b),
         backend="shard_pallas", against="pallas_interpret",
         num_shards=shards)
    show("babelstream.dot", get_kernel("babelstream.dot"), (a, b),
         exact=False, backend="shard_pallas", num_shards=shards)
    show("minibude.fasten", get_kernel("minibude.fasten"), deck,
         backend="shard_pallas", against="pallas_interpret",
         num_shards=shards)
    show("hartree_fock", get_kernel("hartree_fock.twoel"), (pos, dens),
         exact=False, backend="shard_pallas", num_shards=shards)

    print("\nevery sharded backend validated against its oracle (and the "
          "shard_pallas composites against their single-device Pallas "
          "kernels); see BENCH_scaling.json for the per-backend efficiency "
          "curves")


if __name__ == "__main__":
    main()
