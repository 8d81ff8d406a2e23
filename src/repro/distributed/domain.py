"""Domain decomposition: the science kernels as multi-device registry backends.

The paper measures portability across *compiler backends* on one GPU; the
Eq.-4 methodology generalizes to the device-count axis (Godoy et al., 2023
run these same workloads across full exascale nodes).  This module supplies
that axis: each science-kernel family gains an ``xla_shard`` backend that
runs the oracle arithmetic under ``jax.shard_map`` over a 1-D device mesh —

  * **stencil7** — tunable decomposition shape: 1-D z slabs or 2-D
    ``(sz, sy)`` pencils over a named ``(shards_z, shards_y)`` mesh, with
    per-axis ``ppermute`` halo exchange (``collectives.halo_exchange`` /
    ``halo_exchange_nd``) and an ``overlap=True`` variant that issues the
    halo traffic first, computes the halo-free interior while it is in
    flight, and patches only the O(surface) boundary planes afterwards;
    every variant applies the unchanged oracle arithmetic, so the sharded
    field is *bitwise identical* to the single-device result;
  * **babelstream** — block-partitioned 1-D arrays; copy/mul/add/triad are
    embarrassingly parallel (bitwise identical), ``dot`` reduces each block
    locally in the accumulation dtype and combines partials with ``psum``;
  * **minibude.fasten** — pose-parallel: poses shard across devices, the
    protein/ligand decks replicate, per-pose energies are independent
    (bitwise identical);
  * **hartree_fock.twoel** — each device computes the ERI slab for its range
    of the *l* quartet index, contracts it with the matching density
    columns, and the partial Fock matrices accumulate with ``psum`` — the
    distributed analogue of the paper's atomic scatter-adds, without the
    contention.

Backends register in the existing ``PortableKernel`` registry with
``available = device_count >= 2`` and a tunable ``num_shards`` grid, so
``repro.core.tuning`` and the Eq.-4 sweep extend to the device axis with no
registry changes.  On a CPU host, simulate devices with
``repro.launch.hostsim.ensure_host_device_count(8)`` *before* importing jax
(``benchmarks/scaling.py`` and ``repro.distributed.selftest`` do).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core.portable import get_kernel
from repro.distributed import collectives
from repro.kernels.babelstream import ref as stream_ref
from repro.kernels.hartree_fock import ref as hf_ref
from repro.kernels.minibude import ref as mb_ref
from repro.kernels.stencil7 import ref as s7_ref

__all__ = [
    "AXIS",
    "AXIS_Z",
    "AXIS_Y",
    "SHARD_BACKEND",
    "shard_mesh",
    "shard_mesh2d",
    "multi_device",
    "resolve_num_shards",
    "balanced_pencil_grid",
    "resolve_shard_grid",
    "laplacian_shard",
    "stream_shard_fns",
    "fasten_shard",
    "fock_shard",
    "stencil_comm_contract",
    "register_sharded_backends",
]

#: mesh axis name every 1-D sharded kernel maps over
AXIS = "shards"
#: named axes of the 2-D pencil mesh (z outermost, matching array layout)
AXIS_Z = "shards_z"
AXIS_Y = "shards_y"
#: registry backend name (xla arithmetic + sharding, hence the prefix)
SHARD_BACKEND = "xla_shard"
#: num_shards grid declared to the autotuner (1-D decompositions)
SHARD_GRID = (2, 4, 8)
#: stencil7 decomposition tunables: shape of the shard grid is a tunable,
#: not a hard-coded choice (slab = (s, 1); pencil splits z AND y)
STENCIL_DECOMPS = ("slab", "pencil")
STENCIL_SHARD_GRIDS = ((2, 1), (4, 1), (8, 1), (2, 2), (4, 2), (2, 4))
OVERLAP_GRID = (False, True)


def multi_device() -> bool:
    """Availability predicate for every ``xla_shard`` backend."""
    try:
        return jax.device_count() >= 2
    except Exception:  # pragma: no cover - no jax backend at all
        return False


@functools.lru_cache(maxsize=None)
def shard_mesh(num_shards: int) -> Mesh:
    """1-D mesh over the first ``num_shards`` local devices."""
    devices = jax.devices()
    if num_shards > len(devices):
        raise ValueError(
            f"num_shards={num_shards} exceeds the {len(devices)} available "
            f"device(s)")
    return Mesh(np.array(devices[:num_shards]), (AXIS,))


@functools.lru_cache(maxsize=None)
def shard_mesh2d(sz: int, sy: int) -> Mesh:
    """2-D ``(shards_z, shards_y)`` mesh over the first ``sz*sy`` devices."""
    devices = jax.devices()
    if sz * sy > len(devices):
        raise ValueError(
            f"shard grid ({sz}, {sy}) needs {sz * sy} devices, have "
            f"{len(devices)}")
    return Mesh(np.array(devices[:sz * sy]).reshape(sz, sy),
                (AXIS_Z, AXIS_Y))


def resolve_num_shards(extent: int, num_shards: Optional[int] = None,
                       device_count: Optional[int] = None) -> int:
    """Validate an explicit shard count, or pick the largest usable one.

    ``extent`` is the decomposed axis length; a valid count divides it, is
    at least 2, and does not exceed the device count.  ``num_shards=None``
    chooses the largest valid count (deterministic), raising when even 2
    shards cannot be used.
    """
    if device_count is None:
        device_count = jax.device_count()
    if num_shards is not None:
        if num_shards < 2:
            raise ValueError(f"num_shards must be >= 2, got {num_shards}")
        if num_shards > device_count:
            raise ValueError(
                f"num_shards={num_shards} exceeds device_count="
                f"{device_count}")
        if extent % num_shards:
            raise ValueError(
                f"num_shards={num_shards} does not divide the decomposed "
                f"extent {extent}")
        return num_shards
    for s in range(min(device_count, extent), 1, -1):
        if extent % s == 0:
            return s
    raise ValueError(
        f"no valid shard count for extent {extent} on {device_count} "
        f"device(s)")


def _shard_ok(num_shards: int, extent: int,
              device_count: Optional[int] = None) -> bool:
    """Tunable-space constraint twin of ``resolve_num_shards``.

    ``device_count=None`` reads the live topology; tests (and any caller
    reasoning about a hypothetical host) inject an explicit count.
    """
    if device_count is None:
        device_count = jax.device_count()
    return (num_shards >= 2 and num_shards <= device_count
            and extent % num_shards == 0)


def balanced_pencil_grid(total: int, nz: Optional[int] = None,
                         ny: Optional[int] = None):
    """Deterministic most-balanced ``(sz, sy)`` with ``sz * sy == total``
    and both factors >= 2, optionally constrained to divide the ``nz``/
    ``ny`` extents.  ``None`` when no such grid exists (e.g. total=2 has
    no true 2-D grid).  Every factorization is considered (a short z axis
    may only admit ``sy > sz``); ties prefer the z-major grid.  Single
    source of the pencil-picking policy — the scaling benchmark's
    recorded grids must match what the registry resolves."""
    pairs = [(total // sy, sy) for sy in range(2, total // 2 + 1)
             if total % sy == 0 and total // sy >= 2]
    pairs.sort(key=lambda p: (abs(p[0] - p[1]), p[0] < p[1]))
    for sz, sy in pairs:
        if nz is not None and nz % sz:
            continue
        if ny is not None and ny % sy:
            continue
        return sz, sy
    return None


def resolve_shard_grid(nz: int, ny: int, *, decomp: str = "slab",
                       shard_grid=None, num_shards: Optional[int] = None,
                       device_count: Optional[int] = None):
    """Validate or pick the ``(sz, sy)`` shard grid for the stencil.

    ``decomp="slab"`` decomposes z only (``sy == 1``; ``num_shards`` is the
    legacy alias for ``sz``); ``decomp="pencil"`` splits z *and* y
    (``sz, sy >= 2``).  A valid grid divides both decomposed extents and
    fits in the device count.  With no explicit grid, slab reuses
    ``resolve_num_shards`` and pencil deterministically picks the largest
    total shard count, most-balanced grid first.
    """
    if decomp not in STENCIL_DECOMPS:
        raise ValueError(
            f"unknown decomp {decomp!r}; expected one of {STENCIL_DECOMPS}")
    if device_count is None:
        device_count = jax.device_count()
    if shard_grid is None:
        if decomp == "slab":
            return resolve_num_shards(nz, num_shards, device_count), 1
        totals = ([num_shards] if num_shards is not None
                  else range(device_count, 3, -1))
        for total in totals:
            if total > device_count:
                break
            grid = balanced_pencil_grid(total, nz, ny)
            if grid is not None:
                return grid
        raise ValueError(
            f"no valid pencil grid for extents ({nz}, {ny}) on "
            f"{device_count} device(s)"
            + (f" with num_shards={num_shards}" if num_shards else ""))
    sz, sy = (int(shard_grid[0]), int(shard_grid[1]))
    if num_shards is not None and num_shards != sz * sy:
        raise ValueError(
            f"num_shards={num_shards} contradicts shard_grid=({sz}, {sy})")
    if decomp == "slab" and sy != 1:
        raise ValueError(f"slab decomposition needs sy=1, got sy={sy}")
    if decomp == "pencil" and (sz < 2 or sy < 2):
        raise ValueError(
            f"pencil decomposition needs sz, sy >= 2, got ({sz}, {sy})")
    if sz * sy < 2:
        raise ValueError(f"shard grid ({sz}, {sy}) has fewer than 2 shards")
    if sz * sy > device_count:
        raise ValueError(
            f"shard grid ({sz}, {sy}) needs {sz * sy} devices, have "
            f"{device_count}")
    if nz % sz or ny % sy:
        raise ValueError(
            f"shard grid ({sz}, {sy}) does not divide extents ({nz}, {ny})")
    return sz, sy


def _stencil_point_ok(p, nz: int, ny: int,
                      device_count: Optional[int] = None) -> bool:
    """Tunable-space constraint twin of ``resolve_shard_grid``."""
    if device_count is None:
        device_count = jax.device_count()
    try:
        sz, sy = (int(x) for x in p["shard_grid"])
    except (KeyError, TypeError, ValueError):
        return False
    if sz * sy < 2 or sz * sy > device_count:
        return False
    if nz % sz or ny % sy:
        return False
    if p.get("decomp") == "pencil":
        return sz >= 2 and sy >= 2
    return sy == 1 and sz >= 2


# --------------------------------------------------------------------------
# stencil7: slab / pencil decomposition + (optionally overlapped) halo
# exchange
# --------------------------------------------------------------------------
def _boundary_keep(extent, idx, n_shards):
    """Per-plane keep mask along one decomposed axis: the first/last local
    plane is zeroed on the shards owning the *global* boundary (the oracle
    fixes boundary cells to 0; with one plane per shard the two edges are
    the same plane and both conditions AND together)."""
    return (jnp.ones((extent,), bool).at[0].set(idx != 0)
            & jnp.ones((extent,), bool).at[-1].set(idx != n_shards - 1))


def _slab_local(u, num_shards, coeffs, overlap):
    """One shard of the 1-D slab decomposition (z split)."""
    lo, hi = collectives.halo_exchange(u, AXIS, num_shards, axis=0)
    if overlap and u.shape[0] >= 2:
        # double-buffered: the ppermutes above have no data dependency on
        # the interior stencil, so XLA overlaps the halo traffic with the
        # O(volume) compute on the local buffer; only the two O(surface)
        # boundary planes wait for the halos and get patched afterwards.
        # Same per-element expression as the oracle -> bitwise equal.
        out = s7_ref.laplacian(u, *coeffs)
        lo_plane = s7_ref.laplacian(
            jnp.concatenate([lo, u[:2]], axis=0), *coeffs)[1:2]
        hi_plane = s7_ref.laplacian(
            jnp.concatenate([u[-2:], hi], axis=0), *coeffs)[1:2]
        out = out.at[:1].set(lo_plane).at[-1:].set(hi_plane)
    else:
        # one plane per shard has no halo-free interior: plain exchange
        padded = jnp.concatenate([lo, u, hi], axis=0)
        out = s7_ref.laplacian(padded, *coeffs)[1:-1]
    keep = _boundary_keep(out.shape[0], lax.axis_index(AXIS), num_shards)
    return jnp.where(keep[:, None, None], out, jnp.zeros_like(out))


def _pencil_local(u, sz, sy, coeffs, overlap):
    """One shard of the 2-D pencil decomposition (z and y split)."""
    if overlap and u.shape[0] >= 2 and u.shape[1] >= 2:
        # all four ppermutes are issued on the raw block up front (the
        # seven-point stencil has no corner coupling, so per-axis halos of
        # the *unpadded* block suffice), the halo-free interior overlaps
        # with them, and four thin O(surface) slabs patch the boundary.
        (lo_z, hi_z), (lo_y, hi_y) = collectives.halo_exchange_nd(
            u, (AXIS_Z, AXIS_Y), (sz, sy), axes=(0, 1))
        out = s7_ref.laplacian(u, *coeffs)
        uz = jnp.concatenate([lo_z, u, hi_z], axis=0)
        uy = jnp.concatenate([lo_y, u, hi_y], axis=1)
        # z-boundary planes: 3-plane slabs, middle plane's y-halos attached
        # (its y-edge cells read the y-neighbour); the outer planes' y-pads
        # are stencil-dead corners and stay zero
        slab = jnp.pad(uz[0:3], ((0, 0), (1, 1), (0, 0)))
        slab = slab.at[1:2, :1].set(lo_y[:1]).at[1:2, -1:].set(hi_y[:1])
        z_lo = s7_ref.laplacian(slab, *coeffs)[1:2, 1:-1]
        slab = jnp.pad(uz[-3:], ((0, 0), (1, 1), (0, 0)))
        slab = slab.at[1:2, :1].set(lo_y[-1:]).at[1:2, -1:].set(hi_y[-1:])
        z_hi = s7_ref.laplacian(slab, *coeffs)[1:2, 1:-1]
        # y-boundary rows: 3-column slabs, middle column's z-halos attached
        slab = jnp.pad(uy[:, 0:3], ((1, 1), (0, 0), (0, 0)))
        slab = slab.at[:1, 1:2].set(lo_z[:, :1]).at[-1:, 1:2].set(
            hi_z[:, :1])
        y_lo = s7_ref.laplacian(slab, *coeffs)[1:-1, 1:2]
        slab = jnp.pad(uy[:, -3:], ((1, 1), (0, 0), (0, 0)))
        slab = slab.at[:1, 1:2].set(lo_z[:, -1:]).at[-1:, 1:2].set(
            hi_z[:, -1:])
        y_hi = s7_ref.laplacian(slab, *coeffs)[1:-1, 1:2]
        # corner cells appear in both a z- and a y-patch; both compute the
        # identical expression on identical values, so order is irrelevant
        out = out.at[:1].set(z_lo).at[-1:].set(z_hi)
        out = out.at[:, :1].set(y_lo).at[:, -1:].set(y_hi)
    else:
        # staged exchange: z first, then y on the z-padded block (the
        # second exchange carries the corner rows for free)
        lo_z, hi_z = collectives.halo_exchange(u, AXIS_Z, sz, axis=0)
        uz = jnp.concatenate([lo_z, u, hi_z], axis=0)
        lo_y, hi_y = collectives.halo_exchange(uz, AXIS_Y, sy, axis=1)
        padded = jnp.concatenate([lo_y, uz, hi_y], axis=1)
        out = s7_ref.laplacian(padded, *coeffs)[1:-1, 1:-1]
    keep_z = _boundary_keep(out.shape[0], lax.axis_index(AXIS_Z), sz)
    keep_y = _boundary_keep(out.shape[1], lax.axis_index(AXIS_Y), sy)
    keep = keep_z[:, None, None] & keep_y[None, :, None]
    return jnp.where(keep, out, jnp.zeros_like(out))


@functools.lru_cache(maxsize=None)
def _stencil_sharded(sz, sy, overlap, invhx2, invhy2, invhz2, invhxyz2):
    # audit: compile-time-constant(invhx2, invhy2, invhz2, invhxyz2) —
    # grid-spacing coefficients are fixed for a given problem and baking
    # them matches the single-device backends' static_argnames contract
    coeffs = (invhx2, invhy2, invhz2, invhxyz2)
    if sy == 1:
        mesh, spec = shard_mesh(sz), P(AXIS)
        local = functools.partial(_slab_local, num_shards=sz, coeffs=coeffs,
                                  overlap=overlap)
    else:
        mesh, spec = shard_mesh2d(sz, sy), P(AXIS_Z, AXIS_Y)
        local = functools.partial(_pencil_local, sz=sz, sy=sy, coeffs=coeffs,
                                  overlap=overlap)
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=spec,
                                 out_specs=spec, check_vma=False))


def laplacian_shard(u, invhx2=1.0, invhy2=1.0, invhz2=1.0, invhxyz2=-6.0,
                    *, num_shards: Optional[int] = None,
                    decomp: str = "slab", shard_grid=None,
                    overlap: bool = False):
    """Domain-decomposed seven-point stencil.

    ``decomp="slab"`` splits z across ``num_shards`` devices (PR-3
    behaviour); ``decomp="pencil"`` splits z and y across a
    ``shard_grid=(sz, sy)`` device mesh.  ``overlap=True`` issues the halo
    ``ppermute``s first, computes the halo-free interior while they are in
    flight, then patches the boundary planes — all variants are bitwise
    equal to the single-device oracle.
    """
    sz, sy = resolve_shard_grid(u.shape[0], u.shape[1], decomp=decomp,
                                shard_grid=shard_grid, num_shards=num_shards)
    return _stencil_sharded(sz, sy, bool(overlap), invhx2, invhy2, invhz2,
                            invhxyz2)(u)


# --------------------------------------------------------------------------
# BabelStream: block-partitioned arrays, psum dot
# --------------------------------------------------------------------------
def _dot_local(a, b):
    # partials stay in the accumulation dtype across the psum (the oracle
    # only downcasts once, at the very end)
    acc = jnp.float32 if a.dtype in (jnp.bfloat16, jnp.float16) else a.dtype
    part = jnp.sum(a.astype(acc) * b.astype(acc))
    return lax.psum(part, AXIS).astype(a.dtype)


_STREAM_LOCAL = {
    "copy": (stream_ref.copy, 1, False),
    "mul": (stream_ref.mul, 1, True),
    "add": (stream_ref.add, 2, False),
    "triad": (stream_ref.triad, 2, True),
    "dot": (_dot_local, 2, False),
}


@functools.lru_cache(maxsize=None)
def _stream_sharded(op, num_shards):
    mesh = shard_mesh(num_shards)
    body, nargs, takes_scalar = _STREAM_LOCAL[op]
    out_spec = P() if op == "dot" else P(AXIS)
    if takes_scalar:
        # the scalar is a *traced*, replicated argument — baking it into
        # this cache key would compile (and pin) one jitted program per
        # distinct Python float
        def local(*args):
            return body(*args[:-1], scalar=args[-1])
        in_specs = (P(AXIS),) * nargs + (P(),)
    else:
        local, in_specs = body, (P(AXIS),) * nargs
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_spec, check_vma=False))


def _make_stream_shard(op, nargs, takes_scalar):
    if takes_scalar:
        def run(*args, scalar: Optional[float] = None,
                num_shards: Optional[int] = None):
            arrays, rest = args[:nargs], args[nargs:]
            if rest:
                scalar = rest[0]
            elif scalar is None:
                scalar = stream_ref.START_SCALAR
            s = resolve_num_shards(arrays[0].shape[0], num_shards)
            return _stream_sharded(op, s)(
                *arrays, jnp.asarray(scalar, arrays[0].dtype))
    else:
        def run(*arrays, num_shards: Optional[int] = None):
            s = resolve_num_shards(arrays[0].shape[0], num_shards)
            return _stream_sharded(op, s)(*arrays)
    run.__name__ = f"{op}_shard"
    return run


def stream_shard_fns():
    """op name -> sharded backend fn, signatures matching the xla oracle."""
    return {op: _make_stream_shard(op, nargs, takes_scalar)
            for op, (_, nargs, takes_scalar) in _STREAM_LOCAL.items()}


# --------------------------------------------------------------------------
# miniBUDE: pose-parallel
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _fasten_sharded(num_shards):
    mesh = shard_mesh(num_shards)
    # decks replicate, poses (6, P) shard along P; per-pose energies are
    # independent, so out_specs concatenation reassembles the exact result
    return jax.jit(jax.shard_map(
        mb_ref.fasten, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(None, AXIS)),
        out_specs=P(AXIS), check_vma=False))


def fasten_shard(protein_pos, protein_par, ligand_pos, ligand_par, poses,
                 *, num_shards: Optional[int] = None):
    """Pose-parallel miniBUDE energy evaluation."""
    s = resolve_num_shards(poses.shape[1], num_shards)
    return _fasten_sharded(s)(protein_pos, protein_par, ligand_pos,
                              ligand_par, poses)


# --------------------------------------------------------------------------
# Hartree-Fock: l-slab quartet decomposition, psum Fock accumulation
# --------------------------------------------------------------------------
def _eri_slab(positions, basis, l0, ls):
    """(N, N, N, ls) slab of the ERI tensor: all (ij|kl) with l in
    [l0, l0+ls).  Mirrors ``hf_ref.eri_tensor`` with the second pair's l
    index restricted (``l0`` may be traced; ``ls`` is static)."""
    N = positions.shape[0]
    G2 = basis.ngauss ** 2
    p, Pc, Kab = hf_ref._pair_tables(positions, basis)

    def body(eri, ab):
        a, b = ab // G2, ab % G2
        pa, qb = p[a], p[b]
        Pb = lax.dynamic_slice_in_dim(Pc[b], l0, ls, axis=1)   # (N, ls, 3)
        Kb = lax.dynamic_slice_in_dim(Kab[b], l0, ls, axis=1)  # (N, ls)
        pq_d2 = jnp.sum((Pc[a][:, :, None, None, :]
                         - Pb[None, None, :, :, :]) ** 2, -1)
        t = (pa * qb / (pa + qb)) * pq_d2
        pref = hf_ref.TWO_PI_POW_2_5 / (pa * qb * jnp.sqrt(pa + qb))
        eri = eri + (pref * hf_ref.boys_f0(t)
                     * Kab[a][:, :, None, None] * Kb[None, None, :, :])
        return eri, None

    eri0 = jnp.zeros((N, N, N, ls), positions.dtype)
    eri, _ = lax.scan(body, eri0, jnp.arange(G2 * G2))
    return eri


@functools.lru_cache(maxsize=None)
def _fock_sharded(num_shards, ngauss):
    mesh = shard_mesh(num_shards)

    def local(positions, density):
        basis = hf_ref.sto_basis(ngauss, positions.dtype)
        N = positions.shape[0]
        ls = N // num_shards
        l0 = lax.axis_index(AXIS) * ls
        eri = _eri_slab(positions, basis, l0, ls)
        d_slab = lax.dynamic_slice_in_dim(density, l0, ls, axis=1)
        # F[i,j] = sum_kl D[k,l] (2 (ij|kl) - (ik|jl)); both terms read
        # the same l-slab, so each device owns a disjoint set of quartet
        # contributions and psum replaces the paper's atomic scatter-adds
        j_term = 2.0 * jnp.einsum("ijkl,kl->ij", eri, d_slab)
        k_term = jnp.einsum("ikjl,kl->ij", eri, d_slab)
        return lax.psum(j_term - k_term, AXIS)

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(P(), P()),
                                 out_specs=P(), check_vma=False))


def fock_shard(positions, density, *, ngauss: int = 3,
               num_shards: Optional[int] = None):
    """Distributed two-electron Fock build (quartets sharded over l)."""
    s = resolve_num_shards(positions.shape[0], num_shards)
    return _fock_sharded(s, ngauss)(positions, density)


# --------------------------------------------------------------------------
# registration: plug into the existing PortableKernel registry
# --------------------------------------------------------------------------
#: collective traffic of the 1-D sharded families (static-auditor contract)
NO_COLLECTIVES = {"ppermute": 0, "psum": 0, "all_gather": 0}
ONE_PSUM = {"ppermute": 0, "psum": 1, "all_gather": 0}


def stencil_comm_contract(u, *args):
    """Audited variants of the sharded stencil: a slab step exchanges two
    halos (one ppermute each way), a pencil step four (two axes); the
    overlap variants pin a shard grid leaving >= 2 local planes (the
    one-plane-per-shard edge legitimately falls back to plain exchange)
    and additionally require an interior compute of the full local-block
    shape with no data dependency on the halo ppermutes."""
    nz, ny, nx = u.shape
    variants = [
        ({"decomp": "slab"}, {**NO_COLLECTIVES, "ppermute": 2}),
        ({"decomp": "pencil"}, {**NO_COLLECTIVES, "ppermute": 4}),
    ]
    for sz in (4, 2):
        if nz % sz == 0 and nz // sz >= 2:
            variants.append((
                {"decomp": "slab", "shard_grid": (sz, 1), "overlap": True},
                {**NO_COLLECTIVES, "ppermute": 2,
                 "overlap_shape": (nz // sz, ny, nx)}))
            break
    if nz % 2 == 0 and ny % 2 == 0 and nz // 2 >= 2 and ny // 2 >= 2:
        variants.append((
            {"decomp": "pencil", "shard_grid": (2, 2), "overlap": True},
            {**NO_COLLECTIVES, "ppermute": 4,
             "overlap_shape": (nz // 2, ny // 2, nx)}))
    return variants


def register_sharded_backends() -> None:
    """Attach ``xla_shard`` backends + ``num_shards`` tunables to every
    science-kernel family already in the registry.  Idempotent."""
    k = get_kernel("stencil7")
    if SHARD_BACKEND not in k.backends:
        k.add_backend(SHARD_BACKEND, laplacian_shard, available=multi_device)
        # the decomposition *shape* is a tunable, not a hard-coded choice:
        # the sweep walks slab vs pencil grids and halo/compute overlap
        k.declare_tunables(
            SHARD_BACKEND, decomp=STENCIL_DECOMPS,
            shard_grid=STENCIL_SHARD_GRIDS, overlap=OVERLAP_GRID,
            constraint=lambda p, u, *a, device_count=None, **kw:
                _stencil_point_ok(p, u.shape[0], u.shape[1], device_count))
        k.declare_comm_contract(SHARD_BACKEND, stencil_comm_contract)

    for op, fn in stream_shard_fns().items():
        k = get_kernel(f"babelstream.{op}")
        if SHARD_BACKEND in k.backends:
            continue
        k.add_backend(SHARD_BACKEND, fn, available=multi_device)
        k.declare_tunables(
            SHARD_BACKEND, num_shards=SHARD_GRID,
            constraint=lambda p, *arrays, device_count=None, **kw:
                _shard_ok(p["num_shards"], arrays[0].shape[0], device_count))
        # dot combines per-block partials with one psum; the elementwise
        # ops are embarrassingly parallel
        k.declare_comm_contract(
            SHARD_BACKEND, ONE_PSUM if op == "dot" else NO_COLLECTIVES)
        # sharding does not change the streaming AI: still memory-bound
        # on every modeled chip
        k.declare_roofline_contract(SHARD_BACKEND, bound="memory")

    k = get_kernel("minibude.fasten")
    if SHARD_BACKEND not in k.backends:
        k.add_backend(SHARD_BACKEND, fasten_shard, available=multi_device)
        k.declare_tunables(
            SHARD_BACKEND, num_shards=SHARD_GRID,
            constraint=lambda p, *deck, device_count=None, **kw:
                _shard_ok(p["num_shards"], deck[4].shape[1], device_count))
        k.declare_comm_contract(SHARD_BACKEND, NO_COLLECTIVES)

    k = get_kernel("hartree_fock.twoel")
    if SHARD_BACKEND not in k.backends:
        k.add_backend(SHARD_BACKEND, fock_shard, available=multi_device)
        k.declare_tunables(
            SHARD_BACKEND, num_shards=SHARD_GRID,
            constraint=lambda p, positions, *a, device_count=None, **kw:
                _shard_ok(p["num_shards"], positions.shape[0], device_count))
        # per-device Fock partials accumulate with exactly one psum
        k.declare_comm_contract(SHARD_BACKEND, ONE_PSUM)
        # O(N^4) work dwarfs the one Fock psum: compute-bound everywhere
        k.declare_roofline_contract(SHARD_BACKEND, bound="compute")


# importing the ops modules (not the package, to stay cycle-safe when
# repro.kernels.__init__ imports this module last) registers the base
# kernels; we then attach the sharded backends on top
import repro.kernels.babelstream.ops  # noqa: E402,F401
import repro.kernels.hartree_fock.ops  # noqa: E402,F401
import repro.kernels.minibude.ops  # noqa: E402,F401
import repro.kernels.stencil7.ops  # noqa: E402,F401

register_sharded_backends()
