"""Pure-jnp oracle for the Hartree-Fock two-electron (`twoel`) kernel.

The proxy app (Fletcher et al., basic-hf-proxy) builds the electron-repulsion
contribution to the Fock matrix from s-type Gaussian (ssss) integrals over a
system of helium atoms, all sharing one contracted basis:

    (ij|kl) = sum_{g1..g4} c1 c2 c3 c4 * ssss(z1@Ri, z2@Rj, z3@Rk, z4@Rl)

    ssss = 2 pi^{5/2} / (p q sqrt(p+q))
           * exp(-z1 z2/p |Ri-Rj|^2 - z3 z4/q |Rk-Rl|^2)
           * F0( p q/(p+q) |P-Q|^2 )
    p = z1+z2, q = z3+z4, P = (z1 Ri + z2 Rj)/p, Q = (z3 Rk + z4 Rl)/q
    F0(t) = 0.5 sqrt(pi/t) erf(sqrt t),  F0(0) = 1

Fock build (restricted HF closed form):

    F[i,j] = sum_{k,l} D[k,l] * ( 2 (ij|kl) - (ik|jl) )

GPU->TPU adaptation note (DESIGN.md §3): the paper's CUDA/HIP/Mojo kernels
loop over *unique* quartets (8-fold symmetry) and scatter six atomic updates
into F — atomics are their measured bottleneck.  The closed form above is the
*gather* formulation of exactly the same contraction: for symmetric D the six
scatter-adds over unique quartets sum to the same F (the symmetry weights are
absorbed by letting k,l range freely).  We trade the 8x FLOP saving for
contention-free parallelism.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

TWO_PI_POW_2_5 = 2.0 * np.pi ** 2.5


@dataclasses.dataclass(frozen=True)
class Basis:
    """One shared contracted s-shell: exponents + (normalized) coefficients."""

    exponents: jnp.ndarray  # (G,)
    coefficients: jnp.ndarray  # (G,)

    @property
    def ngauss(self) -> int:
        return self.exponents.shape[0]


def sto_basis(ngauss: int = 3, dtype=jnp.float32) -> Basis:
    """STO-nG-like helium s-shell (proxy-app style values, normalized)."""
    if ngauss == 3:
        expo = np.array([6.36242139, 1.15892300, 0.31364979])
        coef = np.array([0.15432897, 0.53532814, 0.44463454])
    elif ngauss == 6:
        expo = np.array([65.98456824, 12.09819836, 3.38438995,
                         1.16259185, 0.45178004, 0.18599939])
        coef = np.array([0.00916360, 0.04936150, 0.16853830,
                         0.37056280, 0.41649150, 0.13033400])
    else:
        raise ValueError("ngauss must be 3 or 6 (paper's cases)")
    # primitive normalization for s gaussians: (2a/pi)^(3/4)
    norm = (2.0 * expo / np.pi) ** 0.75
    return Basis(exponents=jnp.asarray(expo, dtype),
                 coefficients=jnp.asarray(coef * norm, dtype))


def boys_f0(t: jnp.ndarray, erf=jax.lax.erf) -> jnp.ndarray:
    """F0 Boys function, series-guarded at t -> 0 (``erf`` is swappable for
    kernels whose lowering lacks ``lax.erf``)."""
    t_safe = jnp.maximum(t, 1e-12)
    big = 0.5 * jnp.sqrt(jnp.pi / t_safe) * erf(jnp.sqrt(t_safe))
    small = 1.0 - t / 3.0 + t * t / 10.0
    return jnp.where(t < 1e-6, small, big)


def _pair_tables(positions: jnp.ndarray, basis: Basis):
    """Stacked (G^2,) pair quantities over all primitive pairs."""
    R = positions
    z, c = basis.exponents, basis.coefficients
    G = basis.ngauss
    g1, g2 = jnp.meshgrid(jnp.arange(G), jnp.arange(G), indexing="ij")
    g1, g2 = g1.reshape(-1), g2.reshape(-1)
    p = z[g1] + z[g2]                                        # (G2,)
    d2 = jnp.sum((R[:, None, :] - R[None, :, :]) ** 2, -1)   # (N,N)
    # P centers (G2, N, N, 3); Kab (G2, N, N)
    P = (z[g1][:, None, None, None] * R[None, :, None, :]
         + z[g2][:, None, None, None] * R[None, None, :, :]) \
        / p[:, None, None, None]
    Kab = jnp.exp(-(z[g1] * z[g2] / p)[:, None, None] * d2[None]) \
        * (c[g1] * c[g2])[:, None, None]
    return p, P, Kab


def eri_tensor(positions: jnp.ndarray, basis: Basis) -> jnp.ndarray:
    """All (ij|kl) integrals: (N, N, N, N). Reference-sized N only."""
    N = positions.shape[0]
    G2 = basis.ngauss ** 2
    p, P, Kab = _pair_tables(positions, basis)

    def body(eri, ab):
        a, b = ab // G2, ab % G2
        pa, qb = p[a], p[b]
        pq_d2 = jnp.sum((P[a][:, :, None, None, :]
                         - P[b][None, None, :, :, :]) ** 2, -1)
        t = (pa * qb / (pa + qb)) * pq_d2
        pref = TWO_PI_POW_2_5 / (pa * qb * jnp.sqrt(pa + qb))
        eri = eri + (pref * boys_f0(t)
                     * Kab[a][:, :, None, None] * Kab[b][None, None, :, :])
        return eri, None

    eri0 = jnp.zeros((N, N, N, N), positions.dtype)
    eri, _ = jax.lax.scan(body, eri0, jnp.arange(G2 * G2))
    return eri


def fock_build(positions: jnp.ndarray, density: jnp.ndarray,
               basis: Basis) -> jnp.ndarray:
    """F[i,j] = sum_kl D[k,l] (2 (ij|kl) - (ik|jl)) — the gather form."""
    eri = eri_tensor(positions, basis)
    j_term = 2.0 * jnp.einsum("ijkl,kl->ij", eri, density)
    k_term = jnp.einsum("ikjl,kl->ij", eri, density)
    return j_term - k_term


def helium_lattice(natoms: int, spacing: float = 1.4,
                   dtype=jnp.float32) -> jnp.ndarray:
    """Deterministic cubic-ish lattice of He atoms (proxy test-deck style)."""
    side = int(np.ceil(natoms ** (1.0 / 3.0)))
    pts = []
    for ix in range(side):
        for iy in range(side):
            for iz in range(side):
                if len(pts) < natoms:
                    pts.append((ix * spacing, iy * spacing, iz * spacing))
    return jnp.asarray(np.array(pts), dtype)


def initial_density(natoms: int, dtype=jnp.float32) -> jnp.ndarray:
    """Symmetric positive test density (identity-dominated, like an SCF guess)."""
    rng = np.random.default_rng(42)
    a = rng.standard_normal((natoms, natoms)) * 0.05
    d = np.eye(natoms) + (a + a.T) / 2.0
    return jnp.asarray(d, dtype)
