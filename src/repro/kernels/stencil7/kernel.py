"""Seven-point stencil Pallas-TPU kernel.

TPU adaptation (DESIGN.md §3): instead of the GPU one-thread-per-cell model
with cache-served halos, each y-tile of ``by`` rows streams down z through
a rolling window of planes in VMEM.  The input stays in HBM
(``memory_space=pl.ANY``) and the kernel copies it in itself, so every
input plane is fetched once per call:

    grid    (y-tile, z), z inner and sequential
    window  SLOTS = 4 plane-tiles of (by, nx); plane p lives in slot p % 4.
            At step z the kernel computes output plane z from planes z-1,
            z and z+1 while plane z+2 is already being copied in.
    halo    row tiles (by < ny) also copy the 8-row group above and the one
            below the tile with each plane, for the one row of each that a
            y-neighbour reads (HBM slices keep the (8, 128) tiling);
            clamped at the domain edge, where only masked cells read them.
            The whole plane (by == ny) needs none.

The output block (1, by, nx) is pipelined by Pallas as before.
x-neighbours are in-tile lane shifts (pad+slice on the 128-lane axis);
y-neighbours are the tile's own rows plus the two halo rows.  Boundary
cells are masked with a vector predicate rather than the CUDA-style
`if (i>0 && ...) return` guard — TPU is vector-predicated, not
thread-divergent.  All coefficients are compile-time constants (the Mojo
`alias` analogue).

The tile height defaults to the whole plane (``by = ny``) where the window
and the double-buffered output fit :data:`VMEM_BUDGET`, else to the largest
declared height in :data:`BY_GRID` that divides ``ny`` and fits.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import telemetry as tel

LANES = 128
#: plane-tiles resident per y-tile: z-1, z, z+1 and the z+2 prefetch
SLOTS = 4
#: rows in a halo copy: HBM slices keep the (8, 128) sublane tiling
HALO = 8
#: declared y-tile grid (ops.py registers it; sharded composites reuse it),
#: up to the whole plane of the paper's L=512
BY_GRID = (8, 16, 32, 64, 128, 256, 512)
#: VMEM the window, its halo rows and the double-buffered output may take
VMEM_BUDGET = 16 << 20


def _halo_rows(ny: int, by: int) -> int:
    """Rows of each halo copy: none for the whole plane."""
    return math.gcd(by, HALO) if by < ny else 0


def vmem_working_set_bytes(u_shape: Tuple[int, int, int], itemsize: int,
                           by: int) -> int:
    """Claimed VMEM footprint: SLOTS plane-tiles of ``by`` rows with their
    two halo groups, plus two output tiles (the pipeline double-buffers)."""
    _, ny, nx = u_shape
    rows = SLOTS * (by + 2 * _halo_rows(ny, by)) + 2 * by
    return rows * nx * itemsize


def default_by(u_shape: Tuple[int, int, int], itemsize: int) -> int:
    """Tile height for a call that names none: the whole plane where it
    fits :data:`VMEM_BUDGET`, else the largest dividing height that does."""
    ny = u_shape[1]
    for by in (ny,) + tuple(sorted(BY_GRID, reverse=True)):
        if ny % by == 0 and \
                vmem_working_set_bytes(u_shape, itemsize, by) <= VMEM_BUDGET:
            return by
    raise ValueError(f"no y-tile in {BY_GRID} divides ny={ny} within the "
                     f"{VMEM_BUDGET}-byte VMEM budget")


def local_block_by(ny_local: int, by: Optional[int] = None) -> int:
    """y-tile height for a (possibly sharded) local block.

    The sharded composite backends tile the *post-shard* local block, so the
    admissible heights depend on the decomposition: an explicit ``by`` is
    validated against the local extent (a tile larger than the block can
    never divide it), ``None`` picks the largest declared tile that does.
    """
    if by is not None:
        if ny_local % by:
            raise ValueError(
                f"by={by} does not divide the local y extent {ny_local}")
        return by
    for cand in sorted(BY_GRID, reverse=True):
        if ny_local % cand == 0:
            return cand
    raise ValueError(
        f"no declared y-tile {BY_GRID} divides the local y extent "
        f"{ny_local}")


def _stencil_body(u_hbm, o_ref, win, sem, *halos,
                  nz: int, ny: int, nx: int, by: int, halo: int,
                  invhx2: float, invhy2: float, invhz2: float,
                  invhxyz2: float):
    yb = pl.program_id(0)
    z = pl.program_id(1)
    y0 = pl.multiple_of(yb * by, by)
    dt = o_ref.dtype

    def copies(p):
        s = p % SLOTS
        cps = [pltpu.make_async_copy(u_hbm.at[p, pl.ds(y0, by)], win.at[s],
                                     sem.at[s])]
        # the halo group above ends at row y0 - 1, the one below starts at
        # row y0 + by
        for start, dst in zip((y0 - halo, y0 + by), halos):
            start = jnp.clip(start, 0, ny - halo)
            cps.append(pltpu.make_async_copy(
                u_hbm.at[p, pl.ds(pl.multiple_of(start, halo), halo)],
                dst.at[s], sem.at[s]))
        return cps

    def start(p):
        for cp in copies(p):
            cp.start()

    def wait(p):
        for cp in copies(p):
            cp.wait()

    # each y-tile primes its window at z = 0, then prefetches one plane
    # ahead: plane p is started at step p - 2 and waited for at step p - 1
    @pl.when(z == 0)
    def _():
        start(0)
        if nz > 1:
            start(1)
        wait(0)

    if nz > 2:
        @pl.when(z + 2 < nz)
        def _():
            start(z + 2)

    if nz > 1:
        @pl.when(z + 1 < nz)
        def _():
            wait(z + 1)

    s = z % SLOTS
    c = win[s]                                   # (by, nx) resident tile
    up = win[jnp.maximum(z - 1, 0) % SLOTS]      # clamped: masked at z = 0
    dn = win[jnp.minimum(z + 1, nz - 1) % SLOTS]

    if halos:
        above = halos[0][s, halo - 1, :][None, :]
        below = halos[1][s, 0, :][None, :]
    else:                                        # whole plane: edges masked
        above, below = c[:1], c[-1:]
    y_prev = jnp.concatenate([above, c[:-1]], axis=0)
    y_next = jnp.concatenate([c[1:], below], axis=0)

    # x halo via lane shifts (edge columns masked out below)
    x_prev = jnp.pad(c, ((0, 0), (1, 0)))[:, :-1]
    x_next = jnp.pad(c, ((0, 0), (0, 1)))[:, 1:]

    out = (c * dt.type(invhxyz2)
           + (x_prev + x_next) * dt.type(invhx2)
           + (y_prev + y_next) * dt.type(invhy2)
           + (up + dn) * dt.type(invhz2))

    # interior-cell predicate
    gy = y0 + jax.lax.broadcasted_iota(jnp.int32, (by, nx), 0)
    gx = jax.lax.broadcasted_iota(jnp.int32, (by, nx), 1)
    interior = ((gy > 0) & (gy < ny - 1) & (gx > 0) & (gx < nx - 1)
                & (z > 0) & (z < nz - 1))
    o_ref[0] = jnp.where(interior, out, jnp.zeros_like(out))


def laplacian_3d(u: jnp.ndarray, invhx2: float, invhy2: float, invhz2: float,
                 invhxyz2: float, *, by: Optional[int] = None,
                 interpret: bool = False) -> jnp.ndarray:
    """Pallas seven-point stencil over a (nz, ny, nx) volume."""
    nz, ny, nx = u.shape
    if nx % LANES:
        raise ValueError(f"nx={nx} must be a multiple of {LANES}")
    if by is None:
        by = default_by(u.shape, u.dtype.itemsize)
    if ny % by:
        raise ValueError(f"ny={ny} must be a multiple of by={by}")
    tel.counter("stencil7.tile.plane" if by == ny else "stencil7.tile.rows",
                proc="dispatch")

    halo = _halo_rows(ny, by)
    body = functools.partial(
        _stencil_body, nz=nz, ny=ny, nx=nx, by=by, halo=halo,
        invhx2=float(invhx2), invhy2=float(invhy2), invhz2=float(invhz2),
        invhxyz2=float(invhxyz2))
    window = vmem_working_set_bytes(u.shape, u.dtype.itemsize, by)

    return pl.pallas_call(
        body,
        grid=(ny // by, nz),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, by, nx), lambda y, z: (z, y, 0)),
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        scratch_shapes=[pltpu.VMEM((SLOTS, by, nx), u.dtype),
                        pltpu.SemaphoreType.DMA((SLOTS,))]
        + [pltpu.VMEM((SLOTS, halo, nx), u.dtype)] * (2 if halo else 0),
        # z carries the window from step to step; the compiler keeps the
        # tile's temporaries in VMEM beside it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, 2 * window)),
        interpret=interpret,
    )(u)
