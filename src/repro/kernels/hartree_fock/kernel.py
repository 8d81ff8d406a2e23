"""Hartree-Fock `twoel` Pallas-TPU kernel (gather formulation).

TPU adaptation (DESIGN.md §3): the GPU versions scatter six atomic updates
per unique quartet; Pallas-TPU has no global atomics and the paper shows the
atomics serialize both vendors.  We grid over (i-tile) rows of the Fock
matrix; each grid step GATHERS its full
    F[i,:] = sum_kl D[k,l] (2 (ij|kl) - (ik|jl))
contribution with zero write contention:

    sublanes  <- i-tile (8 rows of F)
    lanes     <- j (all atoms)
    sequential fori over (k*l) pairs x (g3,g4) x (g1,g2) primitives

Both the J tile (ij|kl) and the K tile (ik|jl) for fixed (k,l,g...) are
(bi, N) VPU expressions sharing the same loop nest.  erf/exp/rsqrt are the
transcendental hot ops (the paper's "fast-math" sensitivity analogue).

What the loop indexes by its scalars (exponents, coefficients, the k and l
positions, the density element) is read from flat SMEM tables: the TPU
lowering has no dynamic slice of a vector value.  It has no ``erf`` either,
so the Boys function uses a rational f32 erf (``_erf``) of the same accuracy
as ``lax.erf``.

``twoel_slab_tiled`` is the local-block entry point of the family: the same
kernel body with the quartet loop's *l* index restricted to an
``[l0, l0+nl)`` slab, the slab offset a traced scalar operand — the sharded
composite backend runs one slab per device and ``psum``s the partial Fock
matrices (the distributed analogue of the paper's atomic scatter-adds).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.hartree_fock.ref import TWO_PI_POW_2_5, Basis, boys_f0

I_TILE = 8  # Fock rows per grid step (sublane height)
#: declared i-tile grid (ops.py registers it; sharded composites reuse it)
I_TILE_GRID = (4, 8, 16)


def local_i_tile(natoms: int, i_tile: Optional[int] = None) -> int:
    """Fock-row tile for an ``natoms``-row build (the *i* rows stay whole
    under the l-slab decomposition — only the quartet loop shards).  An
    explicit ``i_tile`` is validated; ``None`` picks the largest declared
    tile that divides the row count."""
    if i_tile is not None:
        if natoms % i_tile:
            raise ValueError(
                f"i_tile={i_tile} does not divide natoms={natoms}")
        return i_tile
    for cand in sorted(I_TILE_GRID, reverse=True):
        if natoms % cand == 0:
            return cand
    raise ValueError(
        f"no declared i-tile {I_TILE_GRID} divides natoms={natoms}")


#: odd numerator / even denominator coefficients (highest power first) of
#: Eigen's single-precision rational erf; against ``math.erf`` on [0, 8]
#: its max abs error is 2.6e-7, ``lax.erf``'s 2.9e-7 (XLA:CPU)
_ERF_P = (-2.72614225801306e-10, 2.77068142495902e-08, -2.10102402082508e-06,
          -5.69250639462346e-05, -7.34990630326855e-04, -2.95459980854025e-03,
          -1.60960333262415e-02)
_ERF_Q = (-1.45660718464996e-05, -2.13374055278905e-04, -1.68282697438203e-03,
          -7.37332916720468e-03, -1.42647390514189e-02)


def _erf(x):
    """erf from VPU arithmetic: the TPU kernel lowering has no ``erf``."""
    x = jnp.clip(x, -4.0, 4.0)   # erf is +-1 in f32 beyond
    x2 = x * x
    p = jnp.full_like(x, _ERF_P[0])
    for c in _ERF_P[1:]:
        p = p * x2 + c
    q = jnp.full_like(x, _ERF_Q[0])
    for c in _ERF_Q[1:]:
        q = q * x2 + c
    return x * p / q


def _ssss_tile(dt, ax, ay, az, za, bx, by, bz, zb,
               cx, cy, cz, zc, dx, dy, dz, zd):
    """(bi,N)-broadcast ssss integral for one primitive quartet."""
    p = za + zb
    q = zc + zd
    ab2 = (ax - bx) ** 2 + (ay - by) ** 2 + (az - bz) ** 2
    cd2 = (cx - dx) ** 2 + (cy - dy) ** 2 + (cz - dz) ** 2
    kab = jnp.exp(-(za * zb / p) * ab2)
    kcd = jnp.exp(-(zc * zd / q) * cd2)
    px_, py_, pz_ = ((za * ax + zb * bx) / p, (za * ay + zb * by) / p,
                     (za * az + zb * bz) / p)
    qx_, qy_, qz_ = ((zc * cx + zd * dx) / q, (zc * cy + zd * dy) / q,
                     (zc * cz + zd * dz) / q)
    pq2 = (px_ - qx_) ** 2 + (py_ - qy_) ** 2 + (pz_ - qz_) ** 2
    t = (p * q / (p + q)) * pq2
    pref = dt.type(TWO_PI_POW_2_5) / (p * q * jnp.sqrt(p + q))
    return pref * kab * kcd * boys_f0(t, erf=_erf)


def _quartet_term(dt, pos_i_refs, pos_s, dens_s, zc_s, *,
                  natoms: int, ngauss: int, nl, l0, idx):
    """One (k, l, g1..g4) step of the gather loop: the J and K (bi, N)
    tiles scaled by the density element, with ``l`` enumerated over an
    ``[l0, l0+nl)`` slab (the full build is the ``l0=0, nl=natoms`` slab).

    Everything indexed by the loop's scalars comes from flat SMEM tables
    (the TPU lowering has no dynamic slice of a vector value): ``zc_s`` is
    ``[exponents..., coefficients...]``, ``pos_s`` the row-major ``(N, 4)``
    positions and ``dens_s`` the row-major density.  Each scalar read is
    broadcast to a ``(1, 1)`` vector so the integral arithmetic stays on
    the vector unit."""
    N, G = natoms, ngauss
    xi, yi, zi, xj, yj, zj = pos_i_refs
    kl, g_all = idx // (G * G * G * G), idx % (G * G * G * G)
    k, l = kl // nl, l0 + kl % nl
    g34, g12 = g_all // (G * G), g_all % (G * G)
    g3, g4 = g34 // G, g34 % G
    g1, g2 = g12 // G, g12 % G

    vec = lambda x: jnp.full((1, 1), x, dt)
    z1, z2, z3, z4 = (vec(zc_s[g]) for g in (g1, g2, g3, g4))
    cc = vec(zc_s[G + g1] * zc_s[G + g2] * zc_s[G + g3] * zc_s[G + g4])
    kx, ky, kz = (vec(pos_s[4 * k + c]) for c in range(3))
    lx, ly, lz = (vec(pos_s[4 * l + c]) for c in range(3))
    dkl = vec(dens_s[k * N + l])

    # J: (i j | k l) -> bra pair (i-tile, all-j), ket (k, l) fixed
    j_tile = _ssss_tile(dt, xi, yi, zi, z1, xj, yj, zj, z2,
                        kx, ky, kz, z3, lx, ly, lz, z4)
    # K: (i k | j l) -> bra pair (i-tile, k), ket (all-j, l)
    k_tile = _ssss_tile(dt, xi, yi, zi, z1, kx, ky, kz, z2,
                        xj, yj, zj, z3, lx, ly, lz, z4)
    return cc * dkl * (2.0 * j_tile - k_tile)


def _i_tile_coords(pos_i_ref, pos_t_ref):
    xi = pos_i_ref[:, 0:1]  # (bi, 1) i-tile coordinates
    yi = pos_i_ref[:, 1:2]
    zi = pos_i_ref[:, 2:3]
    xj = pos_t_ref[0:1, :]  # (1, N) all-atom coordinates
    yj = pos_t_ref[1:2, :]
    zj = pos_t_ref[2:3, :]
    return xi, yi, zi, xj, yj, zj


def _twoel_body(pos_i_ref, pos_t_ref, pos_s, dens_s, zc_s, o_ref, *,
                natoms: int, ngauss: int):
    dt = o_ref.dtype
    coords = _i_tile_coords(pos_i_ref, pos_t_ref)

    def body(idx, f_tile):
        return f_tile + _quartet_term(dt, coords, pos_s, dens_s, zc_s,
                                      natoms=natoms, ngauss=ngauss,
                                      nl=natoms, l0=0, idx=idx)

    f0 = jnp.zeros(o_ref.shape, dt)
    total = natoms * natoms * ngauss ** 4
    o_ref[...] = jax.lax.fori_loop(0, total, body, f0)


def _twoel_slab_body(l0_s, pos_i_ref, pos_t_ref, pos_s, dens_s, zc_s, o_ref,
                     *, natoms: int, ngauss: int, nl: int):
    dt = o_ref.dtype
    coords = _i_tile_coords(pos_i_ref, pos_t_ref)
    l0 = l0_s[0]  # traced slab offset (one value per device)

    def body(idx, f_tile):
        return f_tile + _quartet_term(dt, coords, pos_s, dens_s, zc_s,
                                      natoms=natoms, ngauss=ngauss,
                                      nl=nl, l0=l0, idx=idx)

    f0 = jnp.zeros(o_ref.shape, dt)
    total = natoms * nl * ngauss ** 4
    o_ref[...] = jax.lax.fori_loop(0, total, body, f0)


def _operands(positions4, density, basis, i_tile):
    """(in_specs, args) shared by both entry points: the i-tile rows and the
    transposed (4, N) positions in VMEM for the (bi, N) tiles, plus the flat
    scalar tables in SMEM for the quartet loop's indexed reads."""
    N = positions4.shape[0]
    zc = jnp.concatenate([basis.exponents, basis.coefficients]).astype(
        positions4.dtype)  # (2G,)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [
        pl.BlockSpec((i_tile, 4), lambda i: (i, 0)),  # i-tile positions
        pl.BlockSpec((4, N), lambda i: (0, 0)),       # all positions, (4, N)
        smem,                                         # positions, flat
        smem,                                         # density, flat
        smem,                                         # basis, flat
    ]
    args = [positions4, positions4.T, positions4.reshape(-1),
            density.reshape(-1), zc]
    return in_specs, args


def twoel_tiled(positions4: jnp.ndarray, density: jnp.ndarray,
                basis: Basis, *, i_tile: int = I_TILE,
                interpret: bool = False) -> jnp.ndarray:
    """positions4 (N, 4) [xyz + pad], density (N, N) -> Fock (N, N)."""
    N = positions4.shape[0]
    if N % i_tile:
        raise ValueError(f"natoms={N} must be a multiple of i_tile={i_tile}")
    G = basis.ngauss
    in_specs, args = _operands(positions4, density, basis, i_tile)
    return pl.pallas_call(
        functools.partial(_twoel_body, natoms=N, ngauss=G),
        grid=(N // i_tile,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((i_tile, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, N), positions4.dtype),
        interpret=interpret,
    )(*args)


def twoel_slab_tiled(positions4: jnp.ndarray, density: jnp.ndarray,
                     basis: Basis, l0, nl: int, *, i_tile: int = I_TILE,
                     interpret: bool = False) -> jnp.ndarray:
    """Partial Fock build over the quartet slab ``l in [l0, l0+nl)``.

    ``nl`` is static (it sizes the loop); ``l0`` may be traced (each device
    passes ``axis_index * nl``), carried into the kernel as a (1,) scalar
    operand in SMEM.  Summing the slabs over a disjoint cover of ``[0, N)``
    reconstructs the full ``twoel_tiled`` result up to summation order.
    """
    N = positions4.shape[0]
    if N % i_tile:
        raise ValueError(f"natoms={N} must be a multiple of i_tile={i_tile}")
    if not 1 <= nl <= N:
        raise ValueError(f"slab width nl={nl} outside [1, {N}]")
    G = basis.ngauss
    in_specs, args = _operands(positions4, density, basis, i_tile)
    l0a = jnp.asarray(l0, jnp.int32).reshape(1)
    return pl.pallas_call(
        functools.partial(_twoel_slab_body, natoms=N, ngauss=G, nl=nl),
        grid=(N // i_tile,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + in_specs,
        out_specs=pl.BlockSpec((i_tile, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, N), positions4.dtype),
        interpret=interpret,
    )(l0a, *args)
