"""The paper's four science workloads end to end (Fig. 3/4/6-7/Table 4).

    PYTHONPATH=src python examples/science_kernels.py

Runs each proxy app through the portable registry on both backends and
prints the paper's figure of merit for each, plus Phi-bar (Table 5).
"""

import jax.numpy as jnp
import numpy as np

import repro.kernels.babelstream.ops  # noqa: F401 (registration)
import repro.kernels.stencil7.ops  # noqa: F401
import repro.kernels.minibude.ops as mb_ops
import repro.kernels.hartree_fock.ops as hf_ops
from repro.core import (Efficiency, babelstream_bytes, get_kernel,
                        minibude_ops, phi_bar, stencil7_effective_bytes)
from repro.kernels.hartree_fock import ref as hf_ref
from repro.launch.compile_cache import configure_compile_cache


def main() -> None:
    configure_compile_cache()
    rng = np.random.default_rng(0)
    terms = []

    # --- seven-point stencil (Eq. 1) ---
    u = jnp.asarray(rng.standard_normal((64, 64, 128)), jnp.float32)
    k = get_kernel("stencil7")
    t_x = k.time_backend(u, backend="xla")
    t_p = k.time_backend(u, backend="pallas_interpret", iters=3)
    bw = stencil7_effective_bytes(64, 4) / t_x / 1e9
    print(f"stencil7      xla {t_x*1e3:7.2f}ms ({bw:6.2f} GB/s eff)  "
          f"pallas-interp {t_p*1e3:7.2f}ms")
    terms.append(Efficiency("cpu", "stencil7", 1/t_p, 1/t_x))

    # --- BabelStream (Eq. 2) ---
    n = 1 << 20
    a = jnp.asarray(rng.standard_normal(n), jnp.float32)
    b = jnp.asarray(rng.standard_normal(n), jnp.float32)
    for op, args in (("triad", (a, b)), ("dot", (a, b))):
        k = get_kernel(f"babelstream.{op}")
        t_x = k.time_backend(*args, backend="xla")
        t_p = k.time_backend(*args, backend="pallas_interpret", iters=3)
        bw = babelstream_bytes(op, n, 4) / t_x / 1e9
        print(f"stream.{op:6s} xla {t_x*1e3:7.2f}ms ({bw:6.2f} GB/s)      "
              f"pallas-interp {t_p*1e3:7.2f}ms")
        terms.append(Efficiency("cpu", op, 1/t_p, 1/t_x))

    # --- miniBUDE (Eq. 3) ---
    deck = mb_ops.make_deck(natpro=128, natlig=8, nposes=1024, seed=0)
    k = get_kernel("minibude.fasten")
    t_x = k.time_backend(*deck, backend="xla")
    t_p = k.time_backend(*deck, backend="pallas_interpret", iters=3)
    gf = minibude_ops(128, 8, 128, 1024) / t_x / 1e9
    print(f"minibude      xla {t_x*1e3:7.2f}ms ({gf:6.2f} GFLOP/s)    "
          f"pallas-interp {t_p*1e3:7.2f}ms")
    terms.append(Efficiency("cpu", "minibude", 1/t_p, 1/t_x))

    # --- Hartree-Fock (Table 4: wall-clock) ---
    pos = hf_ref.helium_lattice(16)
    dens = hf_ref.initial_density(16)
    k = get_kernel("hartree_fock.twoel")
    t_x = k.time_backend(pos, dens, backend="xla", iters=5)
    t_p = k.time_backend(pos, dens, backend="pallas_interpret", iters=2)
    print(f"hartree-fock  xla {t_x*1e3:7.2f}ms                     "
          f"pallas-interp {t_p*1e3:7.2f}ms")
    terms.append(Efficiency("cpu", "hartree_fock", 1/t_p, 1/t_x))

    print(f"\nPhi-bar across workloads on this host (Eq. 4): "
          f"{phi_bar(terms):.3f}")
    print("(interpret-mode wall-clock != TPU perf; see EXPERIMENTS.md "
          "§Roofline for TPU-projected numbers)")


if __name__ == "__main__":
    main()
